"""Traffic kind ``train_steps``: a training loop over a pool of host batches.

Parameters (the workload file's ``traffic`` block): ``batch``, ``seq_len``,
``masked``, ``pool`` (seeded host batches, cycled), ``in_flight`` (the loop
reads the loss of step i - in_flight before it enqueues step i),
``trace_steps`` (steps wrapped in the profiler in a traced run).

The window runs from the first step enqueued after warm-up to the last loss
being on the host; ``train_tokens_per_s_per_chip`` is every token of every
step of the window over all of that time, over the chips.
"""
import collections
import contextlib
import statistics
import time
import traceback

import numpy as np

WARMUP_STEPS = 3   # the first compiles or reads the cache; two more settle the allocator


def run(family, cfg, traffic, devices, seed, seconds, tracer, on_chip, counter):
    """Build, check, warm up, measure. Returns what ``run.py`` prints and
    what the per-layer readers read."""
    B, L = traffic["batch"], traffic["seq_len"]
    clock = [time.perf_counter()]

    def lap():
        clock.append(time.perf_counter())
        return clock[-1] - clock[-2]

    system = family.build_train(cfg, devices, seed)
    pool = family.train_batches(cfg, traffic, seed, traffic["pool"], B)
    check_batch = family.train_batches(cfg, traffic, seed + 1, 1, 2)[0]
    check_batch[2][1] = L * 3 // 4            # one padded row, so the key mask is exercised
    check_batch[3][1] %= L * 3 // 4
    phases = {"build_s": lap()}
    checks = {"reference": system.reference_check(check_batch)}
    phases["reference_check_s"] = lap()
    losses = [system.step(pool[0])]
    losses[0].wait_to_read()
    phases["first_call_s"] = lap()
    for i in range(1, WARMUP_STEPS):
        losses.append(system.step(pool[i % len(pool)]))
    losses[-1].wait_to_read()
    phases["warmup_s"] = lap()
    checks["program"] = system.program_check(pool[0], on_chip)
    phases["program_check_s"] = lap()
    setup_cache = counter.take()

    host_ms, pending = [], collections.deque()
    traced = None if tracer is None else (WARMUP_STEPS, WARMUP_STEPS + traffic["trace_steps"])
    n, failed = 0, 0
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < seconds:
        if traced and n == traced[0]:
            _drain(pending)
            tracer.start()
        elif traced and n == traced[1]:
            _drain(pending)
            tracer.stop()
        if len(pending) >= traffic["in_flight"]:
            pending.popleft().wait_to_read()
        t1 = time.perf_counter()
        try:
            with tracer.span("bench.step") if tracer else contextlib.nullcontext():
                loss = system.step(pool[(WARMUP_STEPS + n) % len(pool)])
        except Exception:               # a step that raises is a failed step, not a lost run
            traceback.print_exc()
            failed += 1
            n += 1
            continue
        host_ms.append((time.perf_counter() - t1) * 1e3)
        pending.append(loss)
        losses.append(loss)
        n += 1
    _drain(pending)
    if tracer:
        tracer.stop()
    window_s = time.perf_counter() - t_start
    compiles_in_window = counter.take()["compile_requests"]
    losses = [float(x.asnumpy()) for x in losses]

    head, tail = losses[:10], losses[-10:]
    checks["losses"] = {"finite": bool(np.isfinite(losses).all()),
                        "first10_mean": float(np.mean(head)), "last10_mean": float(np.mean(tail)),
                        "first": losses[:WARMUP_STEPS + 3], "last": losses[-3:]}
    checks["losses"]["ok"] = bool(checks["losses"]["finite"] and np.mean(tail) < np.mean(head))
    traces = system.trainer._step_fn._cache_size()
    checks["one_trace"] = {"step_traces": traces, "compiles_in_window": compiles_in_window,
                           "ok": traces == 1 and compiles_in_window == 0}
    done = n - failed
    tokens_per_s = done * B * L / window_s
    return {
        "window_start": t_start, "window_s": window_s,
        "attempted": n, "failed": failed, "checks": checks, "phases": phases,
        "end_to_end": {"train_tokens_per_s_per_chip": tokens_per_s / len(devices)},
        "samples": {
            "first_call_s": phases["first_call_s"], "setup_cache": setup_cache,
            "host_step_ms_p50": statistics.median(host_ms),
            "steps": done, "tokens_per_step": B * L, "chips": len(devices),
            "flops_per_token": family.flops_per_token(cfg, traffic),
            "attention": family.attention_roofline_inputs(cfg, traffic),
            "program_bytes": checks["program"]["program_bytes"],
        },
    }


def _drain(pending) -> None:
    while pending:
        pending.popleft().wait_to_read()
