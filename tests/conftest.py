"""Test-session config.

Tests run on an 8-device *virtual CPU mesh* (SURVEY §4 mechanism 4) so every
multi-chip sharding path executes everywhere without real chips. Two details
matter:

- The platform is pinned to the CPU here, in the environment (children
  inherit it) and in jax's config, before any backend is initialized. On a
  machine with a TPU, JAX would otherwise take the chip, which one process
  at a time may hold — and the suite runs under several workers.
- ``xla_force_host_platform_device_count`` must be in XLA_FLAGS before the
  CPU client is created, i.e. before the first jax.devices() call.
"""
import os

prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
_TPU_MODE = os.environ.get("MXTPU_TEST_TPU") == "1"
if _TPU_MODE:
    # accelerator-context corpus run (tests/test_operator_tpu.py): keep the
    # real device visible — pinning cpu here would silently turn the whole
    # TPU suite into a CPU re-run.  Collection is restricted to that file
    # below: every other test is written for the forced 8-CPU-device mesh.
    import jax
else:
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

import importlib.util

import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the tier-1 run")
    config.addinivalue_line(
        "markers", "lint: mx.analysis / mxlint static-analysis tests "
        "(select with -m lint, skip with -m 'not lint')")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection tests (mx.fault.inject) "
        "— the CI chaos job runs exactly -m chaos")


def pytest_collection_modifyitems(config, items):
    if not _TPU_MODE:
        return
    keep, drop = [], []
    for item in items:
        (keep if item.fspath.basename == "test_operator_tpu.py" else
         drop).append(item)
    if drop:
        config.hook.pytest_deselected(items=drop)
        items[:] = keep


@pytest.fixture(autouse=True)
def _seed_all(request):
    """with_seed() parity: reproducible-yet-random seeding with the failing
    seed logged (reference: tests/python/unittest/common.py)."""
    seed = onp.random.randint(0, 2**31)
    explicit = os.environ.get("MXNET_TEST_SEED")
    if explicit:
        seed = int(explicit)
    onp.random.seed(seed)
    import incubator_mxnet_tpu as mx

    mx.random.seed(seed)
    yield
    failed = getattr(getattr(request.node, "rep_call", None), "failed", False)
    if failed:
        print(f"To reproduce: MXNET_TEST_SEED={seed}")


@pytest.fixture
def load_script():
    """``load_script("examples/train_mnist.py")``: a script of the repo,
    by its path from the root, as a module (the one-example-per-file
    ``test_example_*.py``, ``chip_smoke.py``, ``benchmark/int8_probe.py``).
    Importing one starts nothing: each runs under its ``__main__`` check."""
    def load(relpath):
        name = os.path.splitext(os.path.basename(relpath))[0]
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, relpath))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return load


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)
