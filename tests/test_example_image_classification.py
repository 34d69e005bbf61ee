"""``examples/image_classification.py`` must run end-to-end and learn
(reference mechanism: tests/python/train/ convergence smoke tests, SURVEY
§4.6). One example per file: under ``--dist loadfile`` a file is what one
worker runs."""


def test_image_classification_example_learns(load_script):
    acc = load_script("examples/image_classification.py").main(
        ["--model", "mobilenet0.25", "--epochs", "2", "--classes", "4",
         "--batch-size", "16"])
    assert acc > 0.5, acc
