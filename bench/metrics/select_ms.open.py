"""Relational filter: median per batch of the engine's selection stage,
the ``lazyvlm.engine.select`` span (host packing of the candidates,
``_triple_selections``, the row-count sync and the SQL renderers), in ms,
as the engine records it in each answer's
``stats.stage_seconds["symbolic"]``."""
from bench.spans import stage_ms


def read(run):
    return stage_ms(run, "symbolic")
