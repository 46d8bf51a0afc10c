"""The control of the comparison that decides ``correct``.

    python3 -m bench.control --workload <cell> --seeds 11,12,13

For each seed, at the cell's own size and on its own traffic (the queries
of one window of ``run_seconds``), the reference is put in the program's
place with its scores computed one precision lower than the configuration
states: bfloat16 operands and float32 sums, for the float32 embeddings.
Its answers, and its entity searches at the window's batch shapes (up to
``run.MAX_BATCH`` queries a batch, each batch searched at its largest
``top_k``), go through the harness's own check and verdict
(``run.check``, ``run.verdict``), which have to say ``correct`` false.

It prints one line per seed, each number compared beside its limit. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from types import SimpleNamespace

import numpy as np


def control_run(cfg: dict, world, seed: int, plan: list) -> dict:
    """The harness's verdict on the bfloat16 control over ``plan``."""
    import jax.numpy as jnp

    from bench import run
    from bench.embed import entity_bank, salt_of, text_vectors
    from bench.reference import Reference
    D, salt = cfg["embedding_dim"], salt_of(seed)
    texts = sorted({t for q in plan for t in q["entities"]})
    rels = sorted({p for q in plan for f in q["frames"] for _, p, _ in f})
    bank = entity_bank(world.texts, world.desc_of.reshape(-1), D, seed,
                       cfg["embedding_noise"], "text")

    def low(a, b):
        return np.asarray(jnp.einsum(
            "nd,td->tn", jnp.asarray(a).astype(jnp.bfloat16),
            jnp.asarray(b).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32), np.float64)
    ent = dict(zip(texts, low(bank, text_vectors(texts, D, salt))))
    del bank
    pred = dict(zip(rels, low(text_vectors(list(cfg["predicates"]), D,
                                           salt),
                              text_vectors(rels, D, salt))))
    control = Reference(world.rows, world.videos, world.frames,
                        world.entities_per_video, ent, pred)
    served = []
    for q in plan:
        segs, scores, ef = control.answer_with(q, ent, pred)
        ticket = SimpleNamespace(done=True, error=None, result=SimpleNamespace(
            segments=segs, scores=scores, end_frames=ef))
        served.append(run.Served(q, q["due"], q["due"], ticket))
    searches, texts_of = [], {}
    for lo in range(0, len(plan), run.MAX_BATCH):
        batch = plan[lo: lo + run.MAX_BATCH]
        rows = [t for q in batch for t in dict.fromkeys(q["entities"])]
        k = max(q["top_k"] for q in batch)
        qv = text_vectors(rows, D, salt)
        ids = np.stack([np.argsort(-ent[t], kind="stable")[:k]
                        for t in rows])
        searches.append((qv, k, np.stack([ent[t][i]
                                          for t, i in zip(rows, ids)]), ids))
        texts_of.update((v.tobytes(), t) for t, v in zip(rows, qv))
    res = run.check(cfg, world, seed, served, searches, texts_of)
    correct, checks = run.verdict(res, failed=0)
    return {"queries": len(plan), "correct": correct, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax

    from bench import traffic
    from bench.run import enable_compile_cache, load_cell
    from bench.world import build_world
    spec = load_cell(args.workload)
    cfg, cell = spec["cfg"], spec["cell"]
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    mix = traffic.load_mix(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        world = build_world(cfg, seed)
        plan = traffic.schedule(mix, spec["run_seconds"], seed, world,
                                list(cfg["predicates"]))
        print(json.dumps({"seed": seed,
                          **control_run(cfg, world, seed, plan)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
