"""``examples/serving.py`` must run end-to-end and learn (reference
mechanism: tests/python/train/ convergence smoke tests, SURVEY §4.6). One
example per file: under ``--dist loadfile`` a file is what one worker runs."""
import pytest


@pytest.mark.slow
def test_serving_example_zero_recompiles(load_script):
    # end-to-end serving recipe: export bucketed artifact -> registry
    # cold-load -> batcher -> metrics JSON; rc enforces the zero
    # post-warmup-recompile contract
    assert load_script("examples/serving.py").main(["--requests", "60"]) == 0
