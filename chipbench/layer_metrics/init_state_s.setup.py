"""The trainer's ``trainer.init_state`` span: the eager training-mode
forward that finishes deferred init, then parameters and optimizer state
copied onto the mesh. One a run (their sum, should a run build more)."""
LAYER, UNIT, MOVES = "entry / harness", "s", "setup_s"


def compute(samples, trace):
    from incubator_mxnet_tpu import profiler
    span = profiler.span_records().get("trainer.init_state")
    return span["total_ms"] / 1e3 if span else None
