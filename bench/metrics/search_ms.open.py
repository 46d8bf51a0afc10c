"""Entity search: median per batch of the engine's search stage, the
``lazyvlm.engine.search`` span (embedding, entity and predicate top-k and
their syncs), in ms, as the engine records it in each answer's
``stats.stage_seconds["entity_match"]``."""
from bench.spans import stage_ms


def read(run):
    return stage_ms(run, "entity_match")
