"""Median host time of one ``trainer.step(*batch)`` call that returns
without a sync (host-to-device copy of the batch included)."""
LAYER, UNIT, MOVES = "step driver (host)", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return samples.get("host_step_ms_p50")
