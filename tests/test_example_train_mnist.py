"""``examples/train_mnist.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_train_mnist_example_converges(load_script):
    # lr 0.05 / 3 epochs: the example's reference-default lr 0.1 has a rare
    # early-collapse tail under unlucky (init, batch-order) combos (observed
    # ~1/40); this gate config scored 1.0 on 40/40 seedxorder combos
    acc = load_script("examples/train_mnist.py").main(
        ["--num-epochs", "3", "--num-synthetic", "600", "--lr", "0.05"])
    assert acc > 0.9, acc
