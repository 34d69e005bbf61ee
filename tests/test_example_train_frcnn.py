"""``examples/train_frcnn.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_train_frcnn_example_detects(load_script):
    # end-to-end Faster-RCNN recipe: RPN anchors -> MultiProposal ->
    # AnchorTarget/ProposalTarget -> 4-way loss -> per-class decode+NMS;
    # same mAP proxy as the SSD gate. 400 steps / floor 0.25: the r5
    # 20-seed sweep measured 0.75..1.0 (spread 0.25) with the reference
    # Normal(0.01) head init; 0.25 keeps margin >= 2x that spread while
    # staying >3x the untrained baseline (~0.08)
    acc = load_script("examples/train_frcnn.py").main(["--steps", "400"])
    assert acc > 0.25, acc
