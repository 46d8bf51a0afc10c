"""Benchmark driver — one module per paper claim. Prints name,value,derived CSV.

  pruning      — VLM-workload pruning vs end-to-end VLM (system efficiency)
  scaling      — query cost vs video length
  updates      — incremental ingest (update-friendliness)
  parallelism  — fused batched stages vs sequential launches + the
                 1→N host-device placed-execution scaling curve
                 (qps, modeled merge bytes, exactness asserted)
  multi_query  — batched multi-query throughput vs sequential query loop
  accuracy     — refinement fixes detector noise (robustness)
  kernels      — fused top-k data-movement model + CPU sanity timing
  topk_search  — fp32 fused vs int8 two-phase vs oracle (bytes + wall-clock)
  cascade      — budgeted VLM cascade: calls avoided + wall-clock vs full
  streaming    — segmented ingest + incremental continuous queries vs full
                 re-execution (bytes/launches model, exactness asserted)
  serving      — multi-tenant runtime: coalesced concurrent queries +
                 scheduled subscription refreshes vs a sequential loop
                 (qps, p50/p99, exactness asserted)
  robustness   — chaos-injected verifier/embedder faults: throughput/p99
                 at 0/5/20% fault rates, faulty-vs-clean exactness and
                 breaker-open degradation asserted
  compaction   — tiered storage: zone-map pruning sub-linear in segment
                 count (64→4096), compaction's segment/launch drop, int4
                 cold-tier bytes ratio (exactness asserted)
  adaptivity   — feedback-driven re-optimization: cost-model error drop,
                 corrected filter ordering, cascade budget auto-tuning's
                 launch collapse, poisoned-prior recovery (exactness
                 asserted)
  roofline     — printed separately: python -m benchmarks.roofline

``--json [PATH]`` additionally writes the machine-readable perf trajectory
(default ``BENCH_lazyvlm.json``): every row as {module, name, value, derived}
plus the backend and git sha, so CI archives comparable numbers per commit.
``--modules a,b`` restricts the run (the CI smoke step runs just
``topk_search`` this way).
"""
import argparse
import json
import subprocess
import sys
import traceback


def _git_sha() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "HEAD"],
                              capture_output=True, text=True, check=True,
                              timeout=10).stdout.strip()
    except Exception:
        return "unknown"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_lazyvlm.json",
                    default=None, metavar="PATH",
                    help="write results as JSON (default %(const)s)")
    ap.add_argument("--modules", default=None,
                    help="comma-separated subset of benchmark modules")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (accuracy, adaptivity, cascade, compaction,
                            kernels, multi_query, parallelism, pruning,
                            robustness, scaling, serving, streaming,
                            topk_search, updates)
    modules = [pruning, scaling, updates, parallelism, multi_query, accuracy,
               kernels, topk_search, cascade, streaming, serving, robustness,
               compaction, adaptivity]
    if args.modules:
        want = {m.strip() for m in args.modules.split(",")}
        short = {m.__name__.rsplit(".", 1)[-1]: m for m in modules}
        unknown = want - set(short)
        if unknown:
            raise SystemExit(f"unknown benchmark module(s): {sorted(unknown)};"
                             f" available: {sorted(short)}")
        modules = [short[name] for name in sorted(want)]

    print("name,value,derived")
    results = []
    failed = []
    for m in modules:
        mod_name = m.__name__.rsplit(".", 1)[-1]
        try:
            for row in m.run():
                print(",".join(str(x) for x in row), flush=True)
                name, value, derived = row
                results.append({"module": mod_name, "name": str(name),
                                "value": value, "derived": str(derived)})
        except Exception:
            failed.append(m.__name__)
            traceback.print_exc()

    if args.json:
        import jax
        payload = {
            "schema": "lazyvlm-bench-v1",
            "backend": jax.default_backend(),
            "git_sha": _git_sha(),
            "failed": failed,
            "rows": results,
        }
        # tiered-storage trajectory metadata: segment population
        # before/after the compaction pass, when that module ran
        seg_counts = {r["name"].rsplit("_", 1)[-1]: r["value"]
                      for r in results
                      if r["name"] in ("compaction/segment_count_pre",
                                       "compaction/segment_count_post")}
        if seg_counts:
            payload["segment_count"] = seg_counts
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json} ({len(results)} rows)", file=sys.stderr)

    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
