"""``examples/dcgan.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_dcgan_example_matches_moments(load_script):
    # adversarial training on the disc distribution: the generator's first
    # moments must land near the real data's (fixed seeds; D dominance is
    # expected and not asserted against)
    # 300 steps: the r5 20-seed sweep at 150 steps measured worst normalized
    # distance 0.88 with spread 0.33 (margin < 2x spread = seed-sensitive);
    # at 300 the worst sweep seed scores 0.17 (untrained ~1.85)
    stats = load_script("examples/dcgan.py").main(["--steps", "300"])
    assert abs(stats["fake_mean"] - stats["real_mean"]) < 0.3, stats
    assert abs(stats["fake_std"] - stats["real_std"]) < 0.4, stats
