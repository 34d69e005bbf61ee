"""The op corpus re-run under the accelerator context — the reference's key
portability trick (SURVEY §4: tests/python/gpu/test_operator_gpu.py imports
the unittest modules and overrides the default context to mx.gpu()).

Gated behind MXTPU_TEST_TPU=1 because the default run pins
JAX_PLATFORMS=cpu (conftest): a chip belongs to one process at a time, and
the suite runs under several workers. On a TPU host, in one process:

    MXTPU_TEST_TPU=1 JAX_PLATFORMS='' python -m pytest tests/test_operator_tpu.py

Every ``test_*`` function of the CPU corpus is re-exported here and runs
with ``mx.tpu()`` as the default context, exactly like the reference's
re-import + ctx-override pattern.
"""
import os

import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import test_utils

if os.environ.get("MXTPU_TEST_TPU") != "1":
    pytest.skip("set MXTPU_TEST_TPU=1 on a TPU host to run the op corpus "
                "under the accelerator context", allow_module_level=True)

import test_operator  # noqa: E402  (the CPU corpus, re-run under mx.tpu())

# The corpus checks NUMERICS: force true-f32 matmuls for the whole run
# (default TPU matmul precision is bf16 operands, rel-err ~1e-2, which
# blows the corpus' f32 rtol=1e-4 on every dot/conv/linalg case — the
# analog of the reference running its GPU corpus on cuBLAS fp32, not
# tensor-core fp16). Process-wide is right: this pytest process exists
# only for this corpus (module-level skip above). Perf benches keep the
# fast default.
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True, scope="session")
def _tpu_warmup():
    # Pay the one-time device client init (about 15 s on a TPU host)
    # OUTSIDE any per-test alarm, so it is not charged to the first test.
    import jax.numpy as jnp

    jnp.ones((8, 8)).block_until_ready()


@pytest.fixture(autouse=True)
def _tpu_default_context(_tpu_warmup):
    test_utils.set_default_context(mx.tpu(0))

    # Per-test budget: the corpus runs eagerly, one small compile and one
    # dispatch per op, so one pathological test (finite-difference sweeps
    # do hundreds of dispatches) can eat a budgeted chip call. SIGALRM
    # fires between dispatches and fails just that test by name.
    import signal

    budget = int(os.environ.get("MXTPU_TPU_TEST_TIMEOUT", "150"))

    def _alarm(signum, frame):
        raise TimeoutError(f"TPU corpus per-test budget {budget}s exceeded")

    prev_alarm = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(budget)
    try:
        with mx.tpu(0):
            yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev_alarm)
        test_utils.set_default_context(None)


# re-export the whole corpus; the autouse fixture swaps the context
for _name in dir(test_operator):
    if _name.startswith("test_"):
        globals()[_name] = getattr(test_operator, _name)
del _name
