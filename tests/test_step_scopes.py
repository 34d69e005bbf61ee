"""The compiled training step names its phases: the optimizer update and the
norms carry their ``jax.named_scope`` into every device operation's HLO
``op_name``, and the compile log hands the step's text out on request only
(CPU, a tiny ``ShardedTrainer``)."""
import gc
import re

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, parallel
from incubator_mxnet_tpu.models.afmoe import RMSNorm
from incubator_mxnet_tpu.telemetry import compile_log

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _trainer():
    mx.random.seed(3)
    net = gluon.nn.HybridSequential(prefix="scoped_")
    with net.name_scope():
        net.add(gluon.nn.Dense(16, in_units=8, flatten=False),
                gluon.nn.LayerNorm(in_channels=16),
                RMSNorm(16),
                gluon.nn.Dense(4, in_units=16, flatten=False))
    net.initialize()
    return parallel.ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
                                   {"learning_rate": 1e-2}, mesh=parallel.make_mesh(dp=8))


def _batch():
    rng = onp.random.RandomState(0)
    return rng.randn(8, 8).astype("float32"), rng.randint(0, 4, (8,)).astype("float32")


@pytest.fixture
def clean_log():
    compile_log.clear()
    yield
    compile_log.clear()


def test_the_step_text_carries_the_update_and_norm_scopes(clean_log):
    assert compile_log.program_text("trainer.step") is None        # nothing compiled yet
    tr = _trainer()
    for _ in range(2):
        tr.step(*_batch()).wait_to_read()
    text = compile_log.program_text("trainer.step")
    assert text and text.startswith("HloModule")
    names = _OP_NAME.findall(text)
    words = [set(filter(None, re.split(r"[/()]", n))) for n in names]
    for scope in ("optimizer_update", "layer_norm", "rms_norm"):
        assert any(scope in w for w in words), scope
    # the backward pass of a norm keeps the norm's scope inside jax's own
    assert any(n.startswith("jit(step)/transpose(jvp(") and "rms_norm" in w
               for n, w in zip(names, words))
    # asking twice neither traces nor compiles a second step entry
    assert compile_log.program_text("trainer.step") == text
    assert tr._step_fn._cache_size() == 1


def test_the_text_read_back_is_the_program_that_ran(clean_log):
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    tr = _trainer()
    tr.step(*_batch()).wait_to_read()
    with active_mesh(tr.mesh):
        ran = tr._step_fn.lower(*tr.step_trace_args(*_batch())).compile().as_text()
    assert compile_log.program_text("trainer.step") == ran


def test_the_kept_program_holds_no_trainer_unless_a_trace_saw_it(clean_log, tmp_path):
    import jax
    tr = _trainer()
    tr.step(*_batch()).wait_to_read()
    assert compile_log.program_text("trainer.step")
    del tr
    gc.collect()
    assert compile_log.program_text("trainer.step") is None
    # a step under a profiler trace pins its program: the trace can be
    # read against it after the trainer is gone
    tr = _trainer()
    tr.step(*_batch()).wait_to_read()
    text = compile_log.program_text("trainer.step")
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.step(*_batch()).wait_to_read()
    finally:
        jax.profiler.stop_trace()
    del tr
    gc.collect()
    assert compile_log.program_text("trainer.step") == text
