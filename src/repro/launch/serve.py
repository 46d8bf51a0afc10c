"""Production serving driver: the LazyVLM query service.

    PYTHONPATH=src python -m repro.launch.serve --queries 8

Boots the full stack — synthetic world, ingest into Entity/Relationship
stores, the query engine, the refinement verifier (mock or reduced VLM) —
then serves a batch of randomized VMR queries and prints per-stage timings,
pruning statistics and throughput, naming the device they ran on.
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import LazyVLMEngine
from repro.core.query import (Entity, FrameSpec, Relationship,
                              TemporalConstraint, Triple, VMRQuery)
from repro.core.refine import MockVerifier, VLMVerifier
from repro.semantic import OracleEmbedder
from repro.video import PREDICATES, SyntheticWorld, WorldConfig, ingest


def random_queries(world, n, seed=0):
    rng = np.random.default_rng(seed)
    descs = sorted({o.description for seg in world.segments for o in seg})
    out = []
    for i in range(n):
        da, db = rng.choice(descs, 2, replace=False)
        if i % 3 == 2:  # every third query is a temporal chain
            r1, r2 = rng.choice(len(PREDICATES), 2, replace=False)
            out.append(VMRQuery(
                entities=(Entity("a", da), Entity("b", db)),
                relationships=(Relationship("r1", PREDICATES[int(r1)]),
                               Relationship("r2", PREDICATES[int(r2)])),
                frames=(FrameSpec((Triple("a", "r1", "b"),)),
                        FrameSpec((Triple("a", "r2", "b"),))),
                constraints=(TemporalConstraint(0, 1, min_gap=3),),
                top_k=16, text_threshold=0.9))
        else:
            rel = PREDICATES[int(rng.integers(len(PREDICATES)))]
            out.append(VMRQuery(
                entities=(Entity("a", da), Entity("b", db)),
                relationships=(Relationship("r", rel),),
                frames=(FrameSpec((Triple("a", "r", "b"),)),),
                top_k=16, text_threshold=0.9))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--segments", type=int, default=12)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--verifier", choices=["none", "mock", "vlm"],
                    default="mock")
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]

    t0 = time.time()
    world = SyntheticWorld(WorldConfig(
        num_segments=args.segments, frames_per_segment=32,
        objects_per_segment=7, seed=args.seed, drop_prob=0.05,
        spurious_prob=0.1))
    emb = OracleEmbedder(dim=64)
    stores = ingest(world, emb)
    print(f"ingest: {args.segments} segments in {time.time() - t0:.1f}s")

    if args.verifier == "mock":
        verifier = MockVerifier(world)
    elif args.verifier == "vlm":
        cfg = get_config("qwen2.5-vl-7b", reduced_size=True)
        verifier = VLMVerifier(cfg, world=world,
                               entity_desc=stores.entity_desc, batch_size=8)
    else:
        verifier = None
    engine = LazyVLMEngine(stores, emb, verifier=verifier)

    queries = random_queries(world, args.queries, seed=args.seed)
    t0 = time.time()
    total_cand = total_hits = 0
    stage_totals: dict = {}
    for i, q in enumerate(queries):
        res = engine.query(q)
        total_cand += res.stats.refine_candidates
        total_hits += len(res.segments)
        for k, v in res.stats.stage_seconds.items():
            stage_totals[k] = stage_totals.get(k, 0.0) + v
        print(f"  q{i}: segments={res.segments} "
              f"sql_rows={res.stats.sql_rows_per_triple} "
              f"vlm_candidates={res.stats.refine_candidates}")
    dt = time.time() - t0
    frames = args.segments * 32
    print(f"\n{args.queries} queries in {dt:.1f}s "
          f"({args.queries / dt:.2f} qps on {dev.platform} "
          f"{dev.device_kind})")
    print(f"stage seconds: { {k: round(v, 3) for k, v in stage_totals.items()} }")
    print(f"VLM saw {total_cand} candidate frames total vs "
          f"{frames * args.queries} frame-inspections an e2e VLM would do "
          f"({frames * args.queries / max(total_cand, 1):.0f}x pruning)")


if __name__ == "__main__":
    main()
