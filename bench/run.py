"""Run one benchmark cell on the chip and print one JSON result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 -m bench.run --workload <cell> --rehearse

Everything happens in this one process: the world, the stores, the
verifier where the configuration has one, and the engine are built from the
seed, every program shape the traffic can reach is warmed up, then queries
are offered to ``ServingRuntime`` for ``--seconds`` and drained: in an open
loop at the mix's arrival times, or in a closed loop by the mix's
``clients`` (``bench/traffic.py``). ``setup_s`` runs from the start of this
module to the first due query. What is served is then checked against the
plain reference (``bench/reference.py``), once the device memory peak has
been read and the program's state freed.

The cell, its configuration (``bench/configs/<config>.json``), its traffic
mix (``bench/traffic/<mix>.json``) and its per-layer metrics
(``bench/metrics/<metric>.py``) are found by name in ``BENCHMARK.json``.

A configuration may hold a ``verifier`` block: the VLM the engine asks
about each candidate row. Its ``plugin`` names ``bench/models/<plugin>.py``,
found by name as a metric reader is; the block's other keys are those the
plug-in lists in ``KEYS``, and another key is refused. A plug-in provides:

* ``build(cfg, *, world, stores, seed, rehearse)``: the verifier the engine
  is given, made through the program's own classes with weights made on the
  device from the seed, at a reduced size under ``rehearse``. It has
  ``verify(rows) -> bool (M,)``, a ``calls`` count, and ``margins(rows)``,
  the yes-minus-no logit margins that its ``verify`` computes and
  thresholds at 0 (as ``repro.core.refine.VLMVerifier`` does); a verifier
  without ``margins``, or whose ``verify`` does not go through it, is
  refused. Its ``cfg`` attribute, where it has one, is the model's
  ``ModelConfig``, and ``batch_size`` the rows it pads a call to;
* ``reference_margins(cfg, rows, *, world, seed, rehearse)``: the plain
  float32 margins of ``rows`` (M, 5), float64 (M,), computed apart from the
  program from the seed alone; it runs after the program's state is freed;
* ``TOLERANCE``: the margin error allowed, as a share of the reference
  margins' RMS, with its reason beside it.

Set-up builds the stores, frees what ``append_stores`` left behind, and only
then builds the verifier, so that the model's weights never meet that
transient; the bytes in use after each step go to standard error.
``VerifierRecorder`` keeps, for every call in the window, the rows asked
about, the verdicts the engine received, the margins and the call's host
time. Then the check takes those verdicts as given (a row counts as
accepted for the fewest candidates where every verdict said yes, for the
most where any did, see ``bench/reference.py``) and adds three numbers:
``unverified_rows`` (limit 0), rows that queries with ``verify_budget`` 0
must have had verified and that the verifier never saw;
``verifier_margin_error``, the largest distance of a recorded margin from
the plug-in's reference over a seed-drawn sample of at most
``MARGIN_SAMPLE`` rows, as a share of the reference's RMS (limit
``TOLERANCE``); and ``verifier_sign_errors`` (limit 0), recorded
verdicts of those rows that disagree with the reference's sign where the
reference lies more than ``TOLERANCE`` x RMS from 0. A configuration
without a verifier gives the engine none and keeps its four checks.

Every wrong answer is described on standard error: the query, when it was
due, the size of the batch it ran in, and what differed from the reference
(the first landings missing or beyond what may land, or the ranking). The
first ``WRONG_SHOWN`` also go into the result line, under ``wrong_detail``,
just before ``checks``.

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
from typing import Dict, Iterator, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# queries compared with the reference at most, drawn from the seed
MAX_CHECKED = 600
# wrong answers described in the result line (all go to standard error)
WRONG_SHOWN = 2
# a query not answered this long after the window closed never comes
GRACE_S = 60.0
# largest batch ServingRuntime's default BatchBudget admits
MAX_BATCH = 8
# distinct verified rows whose margins the check recomputes, drawn from the
# seed: each is a prefill of the reference model
MARGIN_SAMPLE = 48
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
            "cold_segments", "engine", "sessions", "verifier",
            "name", "source", "deployment", "reduced", "assumed"}
ENGINE_KEYS = {"use_kernels", "search_mode"}
# verifier plug-ins, found by the name a configuration's block gives
MODELS = BENCH / "models"


def spec_file() -> Path:
    """``BENCHMARK.json``; a configuration's ``file`` is relative to the
    directory that holds it."""
    return ROOT / "BENCHMARK.json"


def load_cell(name: str) -> dict:
    spec = json.loads(spec_file().read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((spec_file().parent / cfg_entry["file"]).read_text())
    unknown = (set(cfg) - CFG_KEYS) | (set(cfg["engine"]) - ENGINE_KEYS)
    if "verifier" in cfg:
        block = cfg["verifier"]
        unknown |= {f"verifier.{k}" for k in set(block) - {"plugin"}
                    - set(load_plugin(block["plugin"]).KEYS)}
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


def _load(path: Path, prefix: str):
    mod_spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _load(BENCH / "metrics" / f"{name}.py", "bench_metric").read


def load_plugin(name: str):
    """The verifier plug-in ``MODELS/<name>.py`` (see the module's
    docstring for what it provides)."""
    return _load(MODELS / f"{name}.py", "bench_model")


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
        predicate_top_m=q["predicate_top_m"],
        verify_budget=q.get("verify_budget", 0))


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


@dataclass
class VerifierCall:
    """One call of the verifier: the rows asked about (M, 5), the verdicts
    the engine received (M,), the margins the verifier computed for them
    (M,), and the call's host seconds. M is the call's batch of rows; a
    verifier pads it to ``batch_size``."""

    rows: np.ndarray
    verdicts: np.ndarray
    margins: np.ndarray
    seconds: float


@dataclass
class VerifierRun:
    """What the window's verifier did, for the check and the readers: every
    call, the model (a ``repro.configs`` ``ModelConfig``, or None) and the
    rows a call is padded to (or None)."""

    calls: List[VerifierCall]
    model: object = None
    batch_size: Optional[int] = None

    def rows(self) -> tuple:
        """(rows asked (n, 5), verdicts (n,), margins (n,)) over every
        call."""
        if not self.calls:
            return (np.zeros((0, 5), np.int64), np.zeros(0, bool),
                    np.zeros(0))
        return tuple(np.concatenate([getattr(c, f) for c in self.calls])
                     for f in ("rows", "verdicts", "margins"))


class VerifierRecorder:
    """Stands between the engine and the verifier and keeps every call (see
    :class:`VerifierCall`), for the check and the readers: the verdicts
    alone would not show a verifier that answers from a model of lower
    precision. The recorder taps ``margins`` on the verifier itself, so that
    the margins kept are those its ``verify`` thresholded, computed once. A
    verifier without ``margins`` is refused here, and a call of ``verify``
    that does not compute its rows' margins through it raises."""

    def __init__(self, verifier):
        if not callable(getattr(verifier, "margins", None)):
            raise SystemExit(f"verifier {type(verifier).__name__} has no "
                             f"margins(rows): a plug-in's verifier must "
                             f"expose the margins its verify thresholds")
        self.real = verifier
        self.log: List[VerifierCall] = []
        self._seen: list = []
        margins = verifier.margins

        def tap(rows):
            out = margins(rows)
            self._seen.append(np.asarray(out, np.float64))
            return out
        verifier.margins = tap

    @property
    def calls(self) -> int:
        return getattr(self.real, "calls", 0)

    def verify(self, rows: np.ndarray) -> np.ndarray:
        self._seen = []
        t0 = time.perf_counter()
        out = self.real.verify(rows)
        seconds = time.perf_counter() - t0
        rows = np.array(rows, np.int64)
        m = np.concatenate(self._seen) if self._seen else np.zeros(0)
        if len(m) != len(rows):
            raise RuntimeError(f"{type(self.real).__name__}.verify gave "
                               f"{len(rows)} verdicts from {len(m)} margins")
        self.log.append(VerifierCall(rows, np.array(out, bool), m, seconds))
        return out

    def host(self) -> VerifierRun:
        return VerifierRun(self.log, getattr(self.real, "cfg", None),
                           getattr(self.real, "batch_size", None))


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
    verifier: Optional[VerifierRun] = None   # None: no verifier

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


def closed_loop(rt: SpannedRuntime, queries: Iterator[dict], clients: int,
                seconds: float, sessions: List[str]) -> tuple:
    """``clients`` clients, each submitting the next query of ``queries``
    when its previous one is answered (or refused), until ``seconds`` have
    passed; then drain. A query's due time is its submission time, and
    ``q["session"]`` its client. Returns (served, t0, t_end)."""
    t0 = time.perf_counter()
    close, stop = t0 + seconds, t0 + seconds + GRACE_S
    served: List[Served] = []
    current: List[Optional[Served]] = [None] * clients

    def idle(s):
        return s is None or s.rejected or s.ticket.done
    while True:
        now = time.perf_counter()
        if now < close:
            for c in range(clients):
                if not idle(current[c]):
                    continue
                s = Served(dict(next(queries), session=c), 0.0)
                query = to_query(s.q)
                s.due = s.submitted = time.perf_counter()
                out = rt.submit(query, sessions[c % len(sessions)])
                if getattr(out, "rejected", False):
                    s.rejected = True
                else:
                    s.ticket = out
                served.append(s)
                current[c] = s
        if now > stop:
            break
        if rt.rt.queue_depth:
            rt.tick()
            continue
        if now >= close and all(map(idle, current)):
            break
    return served, t0, time.perf_counter()


def percentile(x: np.ndarray, p: float) -> float:
    return float(np.percentile(x, p, method="inverted_cdf"))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def make_reference(cfg: dict, world, seed: int, queries: List[dict],
                   searched: Optional[Dict[str, int]] = None,
                   accepted: Optional[tuple] = None):
    """The reference over the world, with exact scores for every text
    ``queries`` ask for, exact near each query's k-th best and near the
    largest k of ``searched`` (text -> k), and the verifier's per-row
    ``accepted`` masks where it has one. Runs after the program's state
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
                     {t: pv @ v for t, v in zip(rel_texts, rv)},
                     accepted=accepted)


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


def margin_sample(asked: np.ndarray, seed: int) -> np.ndarray:
    """At most ``MARGIN_SAMPLE`` distinct rows of ``asked``, drawn from the
    seed, in row order."""
    uniq = np.unique(asked, axis=0)
    rng = np.random.default_rng([seed, 0x3A291])
    return uniq[np.sort(rng.choice(len(uniq), min(MARGIN_SAMPLE, len(uniq)),
                                   replace=False))]


def margin_check(verifier: VerifierRun, plugin, cfg: dict, world, seed: int,
                 rehearse: bool) -> dict:
    """The recorded margins and verdicts of :func:`margin_sample`'s rows
    against the plug-in's reference margins (see the module's
    docstring)."""
    from bench.reference import row_keys
    tol = float(plugin.TOLERANCE)
    asked, said, got = verifier.rows()
    out = {"margin_rows": 0, "margin_rms": 0.0, "tolerance": tol,
           "margin_error": 0.0, "sign_errors": 0}
    if not len(asked):
        return out
    sample = margin_sample(asked, seed)
    want = np.asarray(plugin.reference_margins(
        cfg, sample, world=world, seed=seed, rehearse=rehearse), np.float64)
    rms = float(np.sqrt(np.mean(want ** 2)))
    k_sample, k_asked = row_keys(sample, asked)
    at = np.searchsorted(k_sample, k_asked)
    hit = k_sample[np.minimum(at, len(k_sample) - 1)] == k_asked
    ref = want[at[hit]]
    err = float(np.max(np.abs(got[hit] - ref)))
    out.update(margin_rows=len(sample), margin_rms=rms,
               # a zero RMS would need every sampled reference margin to be 0
               margin_error=err / rms if rms > 0 else float(err > 0),
               sign_errors=int(np.sum((said[hit] != (ref > 0))
                                      & (np.abs(ref) > tol * rms))))
    return out


def check(cfg: dict, world, seed: int, served: List[Served],
          searches: list, texts_of: Dict[bytes, str],
          verifier: Optional[VerifierRun] = None, plugin=None,
          rehearse: bool = False) -> dict:
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
    accepted = seen = None
    if verifier is not None:
        from bench.reference import row_verdicts
        lo, hi, seen = row_verdicts(world.rows, *verifier.rows()[:2])
        accepted = (lo, hi)
    ref = make_reference(cfg, world, seed, [s.q for s in done], searched,
                         accepted)
    wrong, exact, wrongs = 0, 0, []
    for s in done:
        r = s.ticket.result
        v = ref.check(s.q, r.segments, r.scores, r.end_frames)
        exact += v.exact
        if not v.ok:
            wrong += 1
            from bench.traffic import describe
            wrongs.append(f"{describe(s.q)} due {s.q.get('due', 0.0):.3f} "
                          f"s, in a batch of "
                          f"{getattr(s.ticket, 'coalesced_with', 0)}: "
                          f"{v.detail}")
    out = {"checked": len(done), "wrong": wrong, "exact": exact,
           "wrongs": wrongs, "tol": ref.tol,
           **search_check(ref, searches, texts_of)}
    if verifier is not None:
        need = np.zeros(len(world.rows), bool)
        for s in done:
            if s.q.get("verify_budget", 0) == 0:
                need[ref.candidate_rows(s.q)] = True
        out["verifier"] = {
            "rows": int(sum(len(c.rows) for c in verifier.calls)),
            "unverified_rows": int(np.sum(need & ~seen)),
            **margin_check(verifier, plugin, cfg, world, seed, rehearse)}
    out["seconds"] = time.perf_counter() - t0
    return out


def verdict(res: dict, failed: int) -> tuple:
    """(correct, checks) from :func:`check`'s readings and the count of
    queries never answered: each number compared beside its limit."""
    checks = {"wrong_answers": {"value": res["wrong"], "limit": 0},
              "unanswered": {"value": failed, "limit": 0},
              "search_rank_errors": {"value": res["search_rank_errors"],
                                     "limit": 0},
              "search_score_error": {"value": res["search_score_error"],
                                     "limit": res["tol"]}}
    correct = res["search_rows"] > 0
    v = res.get("verifier")
    if v is not None:
        checks["unverified_rows"] = {"value": v["unverified_rows"],
                                     "limit": 0}
        checks["verifier_margin_error"] = {"value": v["margin_error"],
                                           "limit": v["tolerance"]}
        checks["verifier_sign_errors"] = {"value": v["sign_errors"],
                                          "limit": 0}
        correct = correct and v["rows"] > 0
    correct = correct and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return correct, checks


def sweep(rt, mix, args, seconds, world, preds, names, compiles) -> int:
    """Offer the mix at each rate of ``--sweep`` for ``seconds`` and print,
    per rate, the latencies of the first and second half of the arrivals
    and how long the backlog took to drain after the window: a rate above
    the knee leaves a backlog that grows through the window. A closed-loop
    mix is offered open loop here; its warm-up reached batches of at most
    ``clients`` queries, so larger ones compile in the window (counted)."""
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
    log(f"device bytes in use after the stores: {bytes_in_use(devices)}")
    plugin = verifier = None
    if "verifier" in cfg:
        # the model goes on the device only once append_stores' copies of
        # the banks are freed: the two together need not fit
        gc.collect()
        log(f"device bytes in use once freed: {bytes_in_use(devices)}")
        plugin = load_plugin(cfg["verifier"]["plugin"])
        verifier = VerifierRecorder(plugin.build(
            cfg, world=world, stores=stores, seed=args.seed,
            rehearse=args.rehearse))
        log(f"device bytes in use with the verifier: "
            f"{bytes_in_use(devices)}, {time.perf_counter() - T_START:.3f} s")
    eng = cfg["engine"]
    embedder = BenchEmbedder(cfg["embedding_dim"], salt_of(args.seed))
    session = open_video_store(
        stores, embedder, verifier=verifier, use_kernels=eng["use_kernels"],
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
    clients = mix.get("clients")
    preds = list(cfg["predicates"])
    if verifier is not None:
        # also shows, before any query, that verify goes through margins
        t_batch = time.perf_counter()
        verifier.verify(world.rows[:1])
        log(f"first verifier call: {time.perf_counter() - t_batch:.3f} s")
    n_warm = 0
    # a closed loop never has more queries in a batch than clients
    max_batch = min(MAX_BATCH, clients or MAX_BATCH)
    for batch in traffic.warmup_batches(mix, max_batch if warmup else 0,
                                        world, preds, args.seed):
        for i, q in enumerate(batch):
            rt.submit(to_query(q), names[i % len(names)])
        t_batch = time.perf_counter()
        while rt.rt.queue_depth:
            rt.tick()
        log(f"warm-up batch {n_warm}: {len(batch)} queries, "
            f"{time.perf_counter() - t_batch:.3f} s")
        n_warm += 1
    if clients:
        plan = traffic.stream(mix, args.seed, world, preds)
    else:
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
    if verifier is not None:
        verifier.log.clear()
    rt.serving_bytes = warm_bytes = bytes_in_use(devices)
    setup_s = time.perf_counter() - T_START
    if clients:
        served, t0, t_end = closed_loop(rt, plan, clients, seconds, names)
    else:
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
    verified = verifier.host() if verifier is not None else None
    del rt, registry, session, stores, recorder, verifier
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
                  batches=batches, peaks=pk, search_least_bytes=least,
                  verifier=verified)
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

    res = check(cfg, world, args.seed, served, searches, embedder.texts_of,
                verified, plugin, args.rehearse)
    log(f"check: {res['checked']} answers compared with the reference in "
        f"{res['seconds']:.3f} s, {res['exact']} with one right answer, "
        f"{res['wrong']} wrong; {len(searches)} searches, "
        f"{res['search_rows']} rows")
    for w in res["wrongs"]:
        log(f"wrong answer: {w}")
    if verified is not None:
        v = res["verifier"]
        log(f"verifier: {len(verified.calls)} calls, {v['rows']} rows; "
            f"margins of {v['margin_rows']} rows recomputed, RMS "
            f"{v['margin_rms']:.6g}")
    correct, checks = verdict(res, failed)
    for k, v in checks.items():
        log(f"compared {k}: {v['value']} (limit {v['limit']})")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device, **out}
    if res["wrongs"]:
        # the query, when it was due, the batch it ran in and what differed,
        # so that a refused run's last line says which answer was wrong
        out["wrong_detail"] = res["wrongs"][:WRONG_SHOWN]
    out["checks"] = checks
    if args.rehearse:
        log(f"rehearsal (CPU, not a chip measurement): {json.dumps(out)}")
        return out, 1
    return out, 0


if __name__ == "__main__":
    sys.exit(main())
