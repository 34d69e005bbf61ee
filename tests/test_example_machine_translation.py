"""``examples/machine_translation.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""


def test_machine_translation_example_beam_decodes(load_script):
    acc = load_script("examples/machine_translation.py").main(
        ["--task", "copy", "--steps", "300", "--seq-len", "5",
         "--vocab", "12", "--lr", "0.002", "--batch-size", "32"])
    assert acc > 0.8, acc
