"""What ``ShardedTrainer.step`` costs round place and dispatch: per ``step``
frame of the window its duration less its child spans', median. From the
program's ring, on the host's clock."""
import statistics

LAYER, UNIT, MOVES = "step driver (host)", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    from incubator_mxnet_tpu import profiler
    steps = int(samples.get("steps") or 0)
    spans = profiler.recent_spans()
    frames = [r for r in spans if r.kind == "frame" and r.name == "step"][-steps:] if steps else []
    if not frames:
        return None
    inside = {}
    for r in spans:
        if r.parent == "step":
            inside[r.step] = inside.get(r.step, 0.0) + r.dur_ms
    return statistics.median(f.dur_ms - inside.get(f.step, 0.0) for f in frames)
