"""What the readers of the program's own spans and kernel names share.

The program's ``profiler.Scope`` / ``Frame`` write each span twice: into the
trace's host plane (``Trace.host``, the device's clock) and into the
program's ring (``profiler.recent_spans()``, the host's). The flash kernels
carry their ``pl.pallas_call(name=...)`` in the HLO instruction's name:
``transpose_jvp_flash_bwd_dkv__.25 custom-call tpu_custom_call (...)``.
A program without the span or the name gives ``None``, never an error.
"""
import statistics

from chipbench import tracered


def kernel_ms_per_step(trace, kernel: str):
    """Device milliseconds a traced step spends in the Pallas kernels whose
    instruction name holds ``kernel``."""
    steps = trace.span_count("bench.step") if trace else 0
    seconds = trace.seconds_matching(kernel + r"\S*" + tracered.CUSTOM_CALL) if steps else 0.0
    return seconds / steps * 1e3 if seconds else None


def host_span_ms_p50(trace, name: str):
    """Median milliseconds of the host spans called ``name`` in the trace."""
    durs = [(e - s) * 1e3 for n, s, e in trace.host if n == name] if trace else []
    return statistics.median(durs) if durs else None


def compile_phases(site: str):
    """jax's own account of the compiles at one of the program's sites, or
    ``None`` from a program that keeps none (the benchmark's files are also
    run over the commit before the program learnt this)."""
    from incubator_mxnet_tpu.telemetry import compile_log
    read = getattr(compile_log, "phase_seconds", None)
    phases = read(site) if read else None
    return phases if phases and phases["events"] else None
