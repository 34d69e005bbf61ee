"""``examples/train_ssd.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_train_ssd_example_detects(load_script):
    # end-to-end SSD recipe: anchors -> target matching -> CE+SmoothL1 ->
    # NMS decode; the mAP proxy is top-detection (class, IoU>0.5) hit rate
    acc = load_script("examples/train_ssd.py").main(["--steps", "150"])
    assert acc > 0.8, acc
