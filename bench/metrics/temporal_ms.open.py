"""Temporal: median per batch of the engine's temporal stage, the
``lazyvlm.engine.temporal`` span (bitmaps, conjoin, chain matching,
segment ranking and their syncs), in ms, as the engine records it in each
answer's ``stats.stage_seconds["temporal"]``."""
from bench.spans import stage_ms


def read(run):
    return stage_ms(run, "temporal")
