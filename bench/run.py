"""Run one benchmark cell on the chip and print one JSON result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 -m bench.run --workload <cell> --rehearse

Everything happens in this one process: the world, the stores and the
engine are built from the seed, every program shape the traffic can reach
is warmed up, then queries are offered to ``ServingRuntime`` for
``--seconds`` and drained. ``setup_s`` runs from the start of this module to
the first due query. What is served is then checked against the plain
reference (``bench/reference.py``), once the device memory peak has been
read and the program's state freed.

The cell, its configuration (``bench/configs/<config>.json``), its traffic
mix (``bench/traffic/<mix>.json``) and its per-layer metrics
(``bench/metrics/<metric>.py``) are found by name in ``BENCHMARK.json``.

Without a TPU, or with fewer chips than the cell asks for, the run prints no
result and exits 2. ``--rehearse`` runs a cell end to end on the CPU at a
tiny size with the kernels in interpret mode, prints what it measured on
standard error, and exits 1: no number from it is a chip measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# queries compared with the reference at most, drawn from the seed
MAX_CHECKED = 600
# a query not answered this long after the window closed never comes
GRACE_S = 60.0
# largest batch ServingRuntime's default BatchBudget admits
MAX_BATCH = 8
REHEARSAL = {"videos": 40, "frames_per_video": 12, "embedding_dim": 64,
             "entity_capacity": 256, "relationship_capacity": 8192,
             "store_segments": 4, "cold_segments": 1, "sessions": 2}


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what the cell is
# ---------------------------------------------------------------------------
# what a configuration file may hold: what the harness reads, and the
# descriptive keys; another key is refused, so that a configuration cannot
# ask for something the harness does not do
CFG_KEYS = {"videos", "frames_per_video", "entities_per_video", "classes",
            "colors", "accessories", "predicates", "description_zipf",
            "spurious_prob", "embedding_dim", "embedding_noise",
            "entity_capacity", "relationship_capacity", "store_segments",
            "cold_segments", "engine", "sessions",
            "name", "source", "deployment", "reduced", "assumed"}
ENGINE_KEYS = {"use_kernels", "search_mode"}


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    unknown = (set(cfg) - CFG_KEYS) | (set(cfg["engine"]) - ENGINE_KEYS)
    if unknown:
        raise SystemExit(f"{cfg_entry['file']}: keys {sorted(unknown)} are "
                         f"not implemented")

    def here(m):
        return "workloads" not in m or name in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if here(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return {"cell": cell, "cfg": cfg, "e2e": e2e, "per_layer": per_layer,
            "run_seconds": spec["run_seconds"]}


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts XLA compiles (``jax.monitoring`` backend-compile events)."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT /
                                                             ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_stores(cfg: dict, world, seed: int):
    """Empty stores of the configuration's capacities, filled through the
    program's append API in ``store_segments`` sealed segments; the oldest
    ``cold_segments`` are then demoted to the int4 cold tier."""
    import jax.numpy as jnp

    from bench.embed import entity_bank, salt_of, text_vectors
    from repro.core.stores import (REL_SCHEMA, EntityStore, PredicateVocab,
                                   RelationshipStore, VideoStores,
                                   append_stores, demote_cold_segments)
    from repro.kernels.topk_similarity_i4 import quantize_rows_i4
    from repro.kernels.topk_similarity_i8 import quantize_rows
    from repro.symbolic.table import Table
    V, E, D = world.videos, world.entities_per_video, cfg["embedding_dim"]
    cap, rcap = cfg["entity_capacity"], cfg["relationship_capacity"]
    zeros = jnp.zeros((cap, D), jnp.float32)
    ent = EntityStore(
        Table({"vid": jnp.zeros((cap,), jnp.int32),
               "eid": jnp.zeros((cap,), jnp.int32)},
              jnp.zeros((cap,), bool)),
        zeros, zeros, text_i8=quantize_rows(zeros),
        image_i8=quantize_rows(zeros), text_i4=quantize_rows_i4(zeros),
        image_i4=quantize_rows_i4(zeros))
    rel = RelationshipStore(Table(
        {c: jnp.zeros((rcap,), jnp.int32) for c in REL_SCHEMA},
        jnp.zeros((rcap,), bool)))
    preds = list(cfg["predicates"])
    stores = VideoStores(
        entities=ent, relationships=rel,
        predicates=PredicateVocab(preds, text_vectors(preds, D,
                                                      salt_of(seed))),
        num_segments=0, frames_per_segment=world.frames)
    desc_flat = world.desc_of.reshape(-1)
    text = entity_bank(world.texts, desc_flat, D, seed,
                       cfg["embedding_noise"], "text")
    image = entity_bank(world.texts, desc_flat, D, seed,
                        cfg["embedding_noise"], "image")
    n_seg = cfg["store_segments"]
    per = -(-V // n_seg)
    row_vid = world.rows[:, 0]
    for lo in range(0, V, per):
        hi = min(V, lo + per)
        vids = np.repeat(np.arange(lo, hi, dtype=np.int32), E)
        eids = np.tile(np.arange(E, dtype=np.int32), hi - lo)
        r0, r1 = np.searchsorted(row_vid, [lo, hi])
        desc = {(int(v), int(e)): world.texts[desc_flat[v * E + e]]
                for v, e in zip(vids, eids)}
        # one segment's rows go in as appends of power-of-two counts, so
        # that every seed's appends compile the same few row shapes
        chunks = _pow2_chunks(int(r1 - r0))
        start = r0
        for j, n in enumerate(chunks):
            e = slice(lo * E, hi * E) if j == 0 else slice(0, 0)
            stores = append_stores(
                stores, vids if j == 0 else vids[:0],
                eids if j == 0 else eids[:0], text[e], image[e],
                world.rows[start: start + n],
                entity_desc=desc if j == 0 else None, num_segments=hi,
                seal=j == len(chunks) - 1)
            start += n
    del text, image
    cold = cfg["cold_segments"]
    if cold:
        cut = stores.segments[cold - 1].sealed_at
        stores = demote_cold_segments(
            stores, demote_after=stores.store_version - cut)
    return stores


def _pow2_chunks(n: int) -> List[int]:
    """``n`` as a sum of distinct powers of two, largest first."""
    return [1 << b for b in reversed(range(n.bit_length()))
            if n >> b & 1] or [0]


def to_query(q: dict):
    from repro.core.query import (Entity, FrameSpec, Relationship,
                                  TemporalConstraint, Triple, VMRQuery)
    rels: Dict[str, str] = {}
    for f in q["frames"]:
        for _, p, _ in f:
            rels.setdefault(p, f"r{len(rels)}")
    return VMRQuery(
        entities=tuple(Entity(f"e{i}", t) for i, t in enumerate(q["entities"])),
        relationships=tuple(Relationship(n, t) for t, n in rels.items()),
        frames=tuple(FrameSpec(tuple(Triple(f"e{a}", rels[p], f"e{b}")
                                     for a, p, b in f)) for f in q["frames"]),
        constraints=tuple(TemporalConstraint(j, j + 1, min_gap=g)
                          for j, g in enumerate(q["min_gaps"])),
        top_k=q["top_k"], text_threshold=q["text_threshold"],
        predicate_top_m=q["predicate_top_m"])


def bytes_in_use(devices) -> int:
    """Device memory in use now, on the fullest of ``devices``."""
    return max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in devices)


class SpannedRuntime:
    """The runtime with the harness's host spans around its calls. After
    each tick it reads the device memory in use, so that ``serving_bytes``
    is the largest footprint seen between ticks."""

    def __init__(self, rt, devices):
        self.rt, self.devices = rt, devices
        self.serving_bytes = 0

    def submit(self, query, session: str):
        import jax
        with jax.profiler.TraceAnnotation("bench.submit"):
            return self.rt.submit(query, session=session)

    def tick(self) -> int:
        import jax
        with jax.profiler.TraceAnnotation("bench.tick"):
            n = self.rt.tick()
        self.serving_bytes = max(self.serving_bytes,
                                 bytes_in_use(self.devices))
        return n


class SearchRecorder:
    """Keeps what the engine's entity search returns in the window (its
    query rows, k, scores and row ids), for the check: the search stage is
    compared with exact scores on its own, since swapping near-tied rows
    rarely changes an answer."""

    def __init__(self, engine):
        self.real = engine._search
        self.calls: list = []
        engine._search = self

    def __call__(self, q_emb, emb, emb_i8, valid, k):
        out = self.real(q_emb, emb, emb_i8, valid, k)
        self.calls.append((q_emb, k, out))
        return out

    def host(self) -> list:
        return [(np.asarray(q), k, np.asarray(s, np.float64), np.asarray(i))
                for q, k, (s, i) in self.calls]


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclass
class Served:
    q: dict
    due: float
    submitted: float = 0.0
    ticket: object = None
    rejected: bool = False


@dataclass
class RunData:
    """What the per-layer metric readers read (``bench/metrics/``)."""

    cfg: dict
    served: List[Served]
    window_s: float
    batches: int
    trace: object = None                 # bench.trace.Trace or None
    trace_window: tuple = ()
    peaks: dict = field(default_factory=dict)
    search_least_bytes: float = 0.0      # one scan of the text bank

    def done(self) -> List[Served]:
        return [s for s in self.served if s.ticket is not None
                and s.ticket.done and s.ticket.error is None]


def open_loop(rt: SpannedRuntime, plan: List[dict], seconds: float,
              sessions: List[str]) -> tuple:
    """Offer ``plan`` at its due times and drain. Returns (served, t0,
    t_end)."""
    import jax
    t0 = time.perf_counter()
    served = [Served(q, t0 + q["due"]) for q in plan]
    queries = [to_query(q) for q in plan]
    i, n = 0, len(served)
    stop = t0 + seconds + GRACE_S
    while True:
        now = time.perf_counter()
        while i < n and served[i].due <= now:
            s = served[i]
            s.submitted = time.perf_counter()
            out = rt.submit(queries[i], sessions[s.q["session"]
                                                 % len(sessions)])
            if getattr(out, "rejected", False):
                s.rejected = True
            else:
                s.ticket = out
            i += 1
        if now > stop:
            break
        if rt.rt.queue_depth:
            rt.tick()
            continue
        if i >= n:
            break
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(max(0.0, served[i].due - time.perf_counter()))
    return served, t0, time.perf_counter()


def percentile(x: np.ndarray, p: float) -> float:
    return float(np.percentile(x, p, method="inverted_cdf"))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def make_reference(cfg: dict, world, seed: int, queries: List[dict],
                   searched: Optional[Dict[str, int]] = None):
    """The reference over the world, with exact scores for every text
    ``queries`` ask for, exact near each query's k-th best and near the
    largest k of ``searched`` (text -> k). Runs after the program's state
    is freed: it makes the entity bank again from the seed, on the
    device."""
    import jax
    import jax.numpy as jnp

    from bench.embed import entity_bank, salt_of, text_vectors
    from bench.reference import Reference, exact_scores
    D, salt = cfg["embedding_dim"], salt_of(seed)
    k_of, thr_of = dict(searched or {}), {}
    for q in queries:
        for t in q["entities"]:
            k_of[t] = max(k_of.get(t, 0), q["top_k"])
            thr_of[t] = q["text_threshold"]
    texts = sorted(k_of)
    bank = entity_bank(world.texts, world.desc_of.reshape(-1), D, seed,
                       cfg["embedding_noise"], "text")
    qv = text_vectors(texts, D, salt)
    approx = np.asarray(jnp.einsum("nd,td->nt", bank, jnp.asarray(qv),
                                   precision=jax.lax.Precision.HIGHEST))
    # rows near the largest k's boundary include those near every
    # smaller k's, so one exact rescore serves every query of a text
    ks = np.array([k_of[t] for t in texts])
    host_bank = np.asarray(bank)
    scores = exact_scores(lambda idx: host_bank[idx], qv, approx, ks,
                          np.array([thr_of.get(t, 1.0) for t in texts]))
    del bank, host_bank, approx
    preds = list(cfg["predicates"])
    pv = text_vectors(preds, D, salt).astype(np.float64)
    rel_texts = sorted({p for q in queries for f in q["frames"]
                        for _, p, _ in f})
    rv = text_vectors(rel_texts, D, salt).astype(np.float64)
    return Reference(world.rows, world.videos, world.frames,
                     world.entities_per_video,
                     dict(zip(texts, scores)),
                     {t: pv @ v for t, v in zip(rel_texts, rv)})


def search_check(ref, searches: list, texts_of: Dict[bytes, str]) -> dict:
    """The recorded searches against exact scores: the largest distance of
    a returned score from the exact score of its row, and how many
    returned rows cannot be in a top-k of the exact scores (more than
    twice the rounding allowance below the k-th best), or are missing
    though above it by as much."""
    err, bad, rows = 0.0, 0, 0
    for q, k, scores, ids in searches:
        for t in range(q.shape[0]):
            exact = ref.entity_scores[texts_of[q[t].tobytes()]]
            kk = min(k, exact.size)
            kth = np.partition(exact, exact.size - kk)[exact.size - kk]
            got = ids[t, :kk]
            err = max(err, float(np.max(np.abs(scores[t, :kk] - exact[got]))))
            must = np.nonzero(exact > kth + 2 * ref.tol)[0]
            bad += int(np.sum(exact[got] < kth - 2 * ref.tol))
            bad += int(np.setdiff1d(must, got).size)
            rows += kk
    return {"search_rows": rows, "search_score_error": err,
            "search_rank_errors": bad}


def check(cfg: dict, world, seed: int, served: List[Served],
          searches: list, texts_of: Dict[bytes, str]) -> dict:
    done = [s for s in served if s.ticket is not None and s.ticket.done
            and s.ticket.error is None]
    rng = np.random.default_rng([seed, 0xC4EC])
    if len(done) > MAX_CHECKED:
        pick = np.sort(rng.choice(len(done), MAX_CHECKED, replace=False))
        done = [done[i] for i in pick]
    t0 = time.perf_counter()
    searched: Dict[str, int] = {}
    for q, k, _, _ in searches:
        for row in q:
            t = texts_of[row.tobytes()]
            searched[t] = max(searched.get(t, 0), k)
    ref = make_reference(cfg, world, seed, [s.q for s in done], searched)
    wrong, exact, first_wrong = 0, 0, ""
    for s in done:
        r = s.ticket.result
        v = ref.check(s.q, r.segments, r.scores, r.end_frames)
        exact += v.exact
        if not v.ok:
            wrong += 1
            if not first_wrong:
                from bench.traffic import describe
                first_wrong = f"{describe(s.q)}: {v.detail}"
    return {"checked": len(done), "wrong": wrong, "exact": exact,
            "first_wrong": first_wrong, "tol": ref.tol,
            **search_check(ref, searches, texts_of),
            "seconds": time.perf_counter() - t0}


def verdict(res: dict, failed: int) -> tuple:
    """(correct, checks) from :func:`check`'s readings and the count of
    queries never answered: each number compared beside its limit."""
    checks = {"wrong_answers": {"value": res["wrong"], "limit": 0},
              "unanswered": {"value": failed, "limit": 0},
              "search_rank_errors": {"value": res["search_rank_errors"],
                                     "limit": 0},
              "search_score_error": {"value": res["search_score_error"],
                                     "limit": res["tol"]}}
    correct = (res["search_rows"] > 0
               and all(v["value"] <= v["limit"] for v in checks.values()))
    return correct, checks


def sweep(rt, mix, args, seconds, world, preds, names, compiles) -> int:
    """Offer the mix at each rate of ``--sweep`` for ``seconds`` and print,
    per rate, the latencies of the first and second half of the arrivals
    and how long the backlog took to drain after the window: a rate above
    the knee leaves a backlog that grows through the window."""
    from bench import traffic
    for j, rate in enumerate(float(r) for r in args.sweep.split(",")):
        m = dict(mix, rate_per_s=rate)
        plan = traffic.schedule(m, seconds, args.seed + 1 + j, world, preds)
        b0, c0 = rt.rt.metrics.batches, compiles.n
        served, t0, t_end = open_loop(rt, plan, seconds, names)
        lat = np.array([(s.ticket.completed_at - s.due) * 1e3
                        if s.ticket is not None and s.ticket.done
                        and s.ticket.error is None else np.inf
                        for s in served])
        h = len(lat) // 2
        row = {"rate": rate, "queries": len(lat),
               "p50_ms": percentile(lat, 50), "p90_ms": percentile(lat, 90),
               "p50_first_half_ms": percentile(lat[:h], 50),
               "p50_second_half_ms": percentile(lat[h:], 50),
               "drain_s": t_end - t0 - seconds,
               "batches": rt.rt.metrics.batches - b0,
               "compiles": compiles.n - c0}
        print("SWEEP " + json.dumps(row), flush=True)
    return 0


# ---------------------------------------------------------------------------
def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, interpret-mode kernels; exits 1")
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates (queries/s): after "
                         "set-up, run one window at each and print its "
                         "latencies and backlog instead of a result")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    out, rc = execute(parse_args(argv))
    if out is not None and rc == 0:
        print(json.dumps(out), flush=True)
    return rc


def execute(args, warmup: bool = True) -> tuple:
    """One run. Returns (result, exit code); the result is None where no
    run was made. A rehearsal returns its result with exit code 1.
    ``warmup=False`` skips the warm-up (tests: compiles then fall inside
    the window)."""
    spec = load_cell(args.workload)
    cell, cfg = spec["cell"], dict(spec["cfg"])
    seconds = args.seconds or spec["run_seconds"]

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        log(f"no TPU: JAX found {dev.platform}; this is not a chip run")
        return None, 2
    if len(devices) < cell["chips"]:
        log(f"{cell['name']} needs {cell['chips']} chips, found "
            f"{len(devices)}")
        return None, 2
    devices = devices[: cell["chips"]]
    if args.rehearse:
        cfg.update(REHEARSAL)
    from bench.peaks import peaks
    pk = peaks(dev.device_kind) if not args.rehearse else \
        peaks("TPU v5 lite")
    log(f"device {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    from bench import traffic
    from bench.embed import BenchEmbedder, salt_of
    from bench.flops import topk_least_bytes
    from bench.world import build_world
    from repro.serving.runtime import ServingRuntime
    from repro.session import SessionRegistry, open_video_store

    world = build_world(cfg, args.seed)
    log(f"world: {world.videos} videos x {world.frames} frames x "
        f"{world.entities_per_video} entities, {len(world.rows)} "
        f"relationship rows, {time.perf_counter() - T_START:.3f} s")
    stores = build_stores(cfg, world, args.seed)
    eng = cfg["engine"]
    embedder = BenchEmbedder(cfg["embedding_dim"], salt_of(args.seed))
    session = open_video_store(
        stores, embedder, verifier=None, use_kernels=eng["use_kernels"],
        search_mode=eng["search_mode"])
    recorder = SearchRecorder(session.engine)
    registry = SessionRegistry(session.engine)
    names = [f"tenant{i}" for i in range(cfg["sessions"])]
    for n in names:
        registry.open(n)
    rt = SpannedRuntime(ServingRuntime(registry), devices)
    log(f"stores: {len(stores.segments)} segments, tiers "
        f"{[s.tier for s in stores.segments]}, "
        f"{time.perf_counter() - T_START:.3f} s")

    mix = traffic.load_mix(cell["traffic"])
    preds = list(cfg["predicates"])
    n_warm = 0
    for batch in traffic.warmup_batches(mix, MAX_BATCH if warmup else 0,
                                        world, preds, args.seed):
        for i, q in enumerate(batch):
            rt.submit(to_query(q), names[i % len(names)])
        t_batch = time.perf_counter()
        while rt.rt.queue_depth:
            rt.tick()
        log(f"warm-up batch {n_warm}: {len(batch)} queries, "
            f"{time.perf_counter() - t_batch:.3f} s")
        n_warm += 1
    plan = traffic.schedule(mix, seconds, args.seed, world, preds)
    if args.sweep:
        return None, sweep(rt, mix, args, seconds, world, preds, names,
                           compiles)
    log(f"warm-up: {n_warm} batches; {compiles.n} compiles "
        f"({compiles.seconds:.3f} s) so far")

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    batches0 = rt.rt.metrics.batches
    compiles0 = compiles.n
    recorder.calls.clear()
    rt.serving_bytes = warm_bytes = bytes_in_use(devices)
    setup_s = time.perf_counter() - T_START
    served, t0, t_end = open_loop(rt, plan, seconds, names)
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.n - compiles0
    batches = rt.rt.metrics.batches - batches0
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    serving_bytes = rt.serving_bytes
    valid_rows = int(np.asarray(stores.entities.table.count()))
    smallest = "int4" if stores.entities.text_i4 is not None else "int8"
    least = topk_least_bytes(valid_rows, cfg["embedding_dim"], smallest)
    searches = recorder.host()
    del rt, registry, session, stores, recorder
    gc.collect()

    attempted = len(served)
    ok = [s for s in served if s.ticket is not None and s.ticket.done
          and s.ticket.error is None]
    failed = attempted - len(ok)
    answered = {id(s) for s in ok}
    lat = np.array([(s.ticket.completed_at - s.due) * 1e3
                    if id(s) in answered else np.inf for s in served])
    late = np.array([s.submitted - s.due for s in served]) * 1e3
    log(f"window: {attempted} queries over {seconds} s, {len(ok)} answered, "
        f"{failed} failed, {batches} batches, drained "
        f"{t_end - t0 - seconds:.3f} s after the window")
    log(f"generator lateness ms: median {np.median(late):.3f}, "
        f"max {late.max():.3f}")
    log(f"compiles inside the window: {window_compiles}")
    log(f"device memory: peak {mem_peak} (set-up included), in use "
        f"{warm_bytes} after warm-up, at most {serving_bytes} between ticks")

    run = RunData(cfg=cfg, served=served, window_s=t_end - t0,
                  batches=batches, peaks=pk, search_least_bytes=least)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak),
              "serving_bytes_in_use": int(serving_bytes)}
    out: Dict[str, object] = {}
    metrics: Dict[str, dict] = {}
    units = {m["name"]: m["unit"] for m in spec["e2e"] + spec["per_layer"]}
    if args.trace:
        from bench import trace as tr
        trace = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window(trace)
        run.trace, run.trace_window = trace, (lo, hi)
        device["busy_s"] = tr.busy_s(trace, lo, hi)
        device["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": tr.top_ops(trace),
                            "idle_gaps": tr.idle_gaps(trace, lo, hi)}
        for m in spec["per_layer"]:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        e2e = {"setup_s": setup_s,
               "query_p50_ms": percentile(lat, 50),
               "query_p90_ms": percentile(lat, 90),
               "queries_per_s": len(ok) / (t_end - t0)}
        for m in spec["e2e"]:
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": units[m["name"]]}

    res = check(cfg, world, args.seed, served, searches, embedder.texts_of)
    log(f"check: {res['checked']} answers compared with the reference in "
        f"{res['seconds']:.3f} s, {res['exact']} with one right answer, "
        f"{res['wrong']} wrong {res['first_wrong']}; {len(searches)} "
        f"searches, {res['search_rows']} rows")
    correct, checks = verdict(res, failed)
    for k, v in checks.items():
        log(f"compared {k}: {v['value']} (limit {v['limit']})")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device, **out, "checks": checks}
    if args.rehearse:
        log(f"rehearsal (CPU, not a chip measurement): {json.dumps(out)}")
        return out, 1
    return out, 0


if __name__ == "__main__":
    sys.exit(main())
