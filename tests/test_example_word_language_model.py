"""``examples/word_language_model.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_word_language_model_example_learns(load_script):
    # the synthetic Markov corpus has ppl floor ~2.1; untrained sits at ~50.
    # threshold 12: the r5 20-seed sweep measured ppl 6.66..8.27 (spread
    # 1.61) at this config — 12 keeps margin >= 2x spread while still
    # separating cleanly from the untrained baseline
    ppl = load_script("examples/word_language_model.py").main(
        ["--steps", "40", "--epochs", "2"])
    assert ppl < 12.0, ppl
