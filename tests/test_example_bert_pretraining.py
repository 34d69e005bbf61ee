"""``examples/bert_pretraining.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_bert_pretraining_example_runs(load_script):
    loss = load_script("examples/bert_pretraining.py").main(
        ["--model", "bert_2_128_2", "--steps", "6", "--batch-size", "4",
         "--seq-len", "64"])
    assert loss == loss and loss < 20.0  # finite, sane
