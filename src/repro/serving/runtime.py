"""Async multi-tenant serving runtime: many named sessions, one engine.

This is the daemon layer the paper's "drop video in, query at scale"
posture needs: concurrent users (and agents, and dashboards) submit
queries against shared stores, and the runtime multiplexes them through
the PR-1..6 stack — priority/deadline scheduling, cross-user coalescing
into one ``query_batch``, bounded queues with explicit backpressure, and
streamed incremental results for ``follow=true`` subscribers.

Architecture: a **deterministic tick-driven core** (:class:`ServingRuntime`)
plus an **asyncio wrapper** (:class:`AsyncServingRuntime`). The core holds
every scheduling decision — ``tick()`` selects one admission batch and
executes it, synchronously, with an injectable clock — so correctness
(and every test) needs no threads and no event loop. The wrapper only
drives ticks from an asyncio task and adapts tickets/streams to
futures/async-iterators.

Scheduling policy, in order:

  1. **Effective priority class.** Each entry carries a small-int priority
     (0 = most urgent). Waiting *ages* an entry one class per ``aging_s``
     seconds, so a flood of urgent work can delay — but never starve — a
     background query: its effective class eventually reaches 0 and EDF
     takes over (bounded-wait fairness).
  2. **EDF within a class.** Deadlines default to submission time plus a
     cost-proportional SLO derived from ``LazyVLMEngine.estimate_cost``
     pipeline totals (``default_slo_s + device_bytes / service_bytes_per_s``)
     — cheap queries get tight deadlines, heavy ones realistic slack.
  3. **Budgeted admission.** The batch fills in that order under the shared
     :class:`CostBasedAdmission` budget (the same pipeline-cost currency
     interactive queries and subscription refreshes are both priced in);
     the head entry is always admitted, so no entry can livelock.

**Coalescing exactness.** All query entries selected in one tick run as
ONE ``query_batch`` against the engine's current ``store_version`` — they
share the plan cache, one fused embed per bank, fused per-stage launches,
and the cross-query VLM dedupe. The engine pins ``query_batch`` ≡
per-query ``query`` bit-for-bit (PR 1), so coalesced results are
bit-identical to executing each user's query alone; the runtime inherits
that guarantee for any arrival order, priority mix, and store version
(pinned again end-to-end in ``tests/test_runtime.py``).

**Backpressure.** ``submit`` on a full queue returns a structured
:class:`SubmitRejection` — carrying a ``retry_after_s`` derived from the
queued pipeline cost over the configured service rate — and never raises
from inside the engine and never silently drops. Ingest-driven
subscription refreshes are standing work and are not droppable: they
bypass the submit-side bound (a skipped refresh would only go stale and
be re-notified, so rejecting it buys nothing).
"""
from __future__ import annotations

import asyncio
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Union

from repro import obs
from repro.core.executor import LazyVLMEngine, QueryResult
from repro.core.fault import (DeviceLossError, ServiceUnavailable,
                              TransientFault)
from repro.core.streaming import RefreshDelta, Subscription, _result_delta
from repro.serving.frontend import QueryFailure, QueryTicket
from repro.serving.scheduler import (BatchBudget, CostBasedAdmission,
                                     SubscriptionDrain)
from repro.session import QueryLike, Session, SessionRegistry

# priority classes (smaller = more urgent); any small int works, these are
# the conventional names
PRIORITY_HIGH = 0
PRIORITY_NORMAL = 1
PRIORITY_LOW = 2


@dataclass(frozen=True)
class SubmitRejection:
    """Structured backpressure signal: the queue is full.

    ``retry_after_s`` is derived from the queue's admitted pipeline cost
    (queued device bytes over the runtime's modeled service rate), so a
    well-behaved client backing off by it arrives roughly when the current
    backlog has drained. ``rejected`` is always True — tickets expose the
    same attribute as False, so callers branch on ``out.rejected`` without
    isinstance checks."""

    reason: str
    retry_after_s: float
    queue_depth: int
    queue_device_bytes: int
    rejected: bool = True


class RuntimeOverloaded(RuntimeError):
    """Raised by the async wrapper when ``submit`` is rejected; carries the
    :class:`SubmitRejection` as ``.rejection``."""

    def __init__(self, rejection: SubmitRejection):
        super().__init__(f"serving runtime overloaded: {rejection.reason} "
                         f"(retry after {rejection.retry_after_s:.3f}s)")
        self.rejection = rejection


@dataclass
class RuntimeTicket(QueryTicket):
    """A :class:`QueryTicket` with the runtime's scheduling envelope."""

    session: str = ""
    priority: int = PRIORITY_NORMAL
    deadline: float = 0.0
    store_version_at_submit: int = 0
    est_device_bytes: int = 0
    coalesced_with: int = 0          # size of the batch it executed in
    rejected: bool = False           # attribute parity with SubmitRejection
    _callbacks: List[Callable[["RuntimeTicket"], None]] = field(
        default_factory=list, repr=False)

    def add_callback(self, fn: Callable[["RuntimeTicket"], None]) -> None:
        """Invoke ``fn(ticket)`` on completion (immediately if done)."""
        if self.done:
            fn(self)
        else:
            self._callbacks.append(fn)

    def _complete(self) -> None:
        for fn in self._callbacks:
            fn(self)
        self._callbacks.clear()


@dataclass
class RuntimeMetrics:
    """Lifetime counters (the benchmark reads latencies off tickets)."""

    submitted: int = 0
    rejected: int = 0
    completed: int = 0
    failed: int = 0                  # tickets completed with an engine error
    refreshes: int = 0
    refresh_failures: int = 0
    batches: int = 0
    coalesced_queries: int = 0       # queries that shared a >1-query batch
    peak_queue_depth: int = 0
    # -- fault tolerance ---------------------------------------------------
    requeued: int = 0                # transient failures re-entered the queue
    deadline_failures: int = 0       # tickets expired before execution
    retry_exhausted: int = 0         # tickets that outlived their retry budget
    quarantined: int = 0             # subscriptions quarantined as poisoned
    device_losses: int = 0           # DeviceLossError batches observed
    # -- tiered-storage maintenance (idle-tick background work) ------------
    compactions: int = 0             # background compaction passes applied
    compacted_segments: int = 0      # segments merged away by those passes
    compaction_bytes: int = 0        # modeled bytes the passes were priced at
    demotions: int = 0               # segments demoted to the cold tier
    # -- adaptive re-optimization (mirrors engine.adapt; zero when off) -----
    adaptations: int = 0             # corrections that changed compile output
    reorders: int = 0                # mid-pipeline (probe) filter re-sorts
    budget_changes: int = 0          # auto-tuned verify-budget moves


@dataclass
class _Entry:
    """One unit of schedulable work: an interactive query OR a refresh."""

    seq: int
    kind: str                        # "query" | "refresh"
    priority: int
    deadline: float
    submitted_at: float
    est_device_bytes: int
    est_rows: int
    ticket: Optional[RuntimeTicket] = None     # kind == "query"
    sub: Optional[Subscription] = None         # kind == "refresh"
    attempts: int = 0                # transient failures survived so far
    not_before: float = 0.0          # backoff gate: ineligible until then


class StreamHandle:
    """Pull-based view of one ``follow=true`` subscription's delta stream.

    Deltas are buffered in arrival order; ``poll()`` drains the buffer.
    Setting ``on_delta`` (the async wrapper does) reroutes future deltas
    to the callback instead of the buffer. ``result`` is the
    subscription's current (bit-exact) state at any time."""

    def __init__(self, sub: Subscription, session: str):
        self.sub = sub
        self.session = session
        self.closed = False
        self.on_delta: Optional[Callable[[RefreshDelta], None]] = None
        self._deltas: Deque[RefreshDelta] = deque()

    def _push(self, delta: RefreshDelta) -> None:
        if self.closed:
            return
        if self.on_delta is not None:
            self.on_delta(delta)
        else:
            self._deltas.append(delta)

    def poll(self) -> List[RefreshDelta]:
        """Drain and return the buffered deltas (possibly empty)."""
        out = list(self._deltas)
        self._deltas.clear()
        return out

    def __len__(self) -> int:
        return len(self._deltas)

    @property
    def result(self) -> Optional[QueryResult]:
        return self.sub.result

    def close(self) -> None:
        """Stop receiving deltas (the subscription itself keeps refreshing
        for other listeners / direct ``sub.result`` readers)."""
        if not self.closed:
            self.closed = True
            self.sub.remove_listener(self._push)


class ServingRuntime:
    """Deterministic tick-driven core of the multi-tenant serving daemon.

    ``sessions`` may be a :class:`SessionRegistry`, a single
    :class:`Session` (adopted as ``"default"``), or a bare engine. All
    sessions share the engine — that is the point: shared stores, shared
    plan/embed caches, and cross-user coalescing.

    ``clock`` is injectable for deterministic scheduling tests; only
    monotonicity is assumed.
    """

    def __init__(self, sessions: Union[SessionRegistry, Session,
                                       LazyVLMEngine], *,
                 admission: Optional[CostBasedAdmission] = None,
                 budget: Optional[BatchBudget] = None,
                 max_queue: int = 256,
                 max_queue_device_bytes: Optional[int] = None,
                 aging_s: float = 0.25,
                 refresh_priority: int = PRIORITY_NORMAL,
                 default_slo_s: float = 0.05,
                 service_bytes_per_s: float = 2e9,
                 clock: Callable[[], float] = time.perf_counter,
                 enforce_deadlines: bool = False,
                 max_ticket_retries: int = 3,
                 retry_backoff_s: float = 0.05,
                 retry_jitter: Optional[Callable[[int], float]] = None,
                 max_refresh_failures: int = 3,
                 compaction: Optional["CompactionPolicy"] = None,
                 demote_after: Optional[int] = None):
        if isinstance(sessions, SessionRegistry):
            self.registry = sessions
        elif isinstance(sessions, Session):
            self.registry = SessionRegistry(sessions.engine)
            sessions.name = sessions.name or "default"
            self.registry._sessions[sessions.name] = sessions
        else:
            self.registry = SessionRegistry(sessions)
        self.engine = self.registry.engine
        if admission is None:
            admission = CostBasedAdmission(
                self.engine, budget or BatchBudget(max_queries=8))
        self.admission = admission
        self.max_queue = max_queue
        self.max_queue_device_bytes = max_queue_device_bytes
        self.aging_s = aging_s
        self.refresh_priority = refresh_priority
        self.default_slo_s = default_slo_s
        self.service_bytes_per_s = service_bytes_per_s
        self.clock = clock
        # -- fault-tolerance knobs ------------------------------------------
        # deadline enforcement is opt-in: the default SLOs are tight enough
        # that a flood test driving the real clock would expire its tail
        self.enforce_deadlines = enforce_deadlines
        self.max_ticket_retries = max_ticket_retries
        self.retry_backoff_s = retry_backoff_s
        # attempt -> fraction in [0, 1) (fault.seeded_jitter for tests)
        self.retry_jitter = retry_jitter
        self.max_refresh_failures = max_refresh_failures
        # -- tiered-storage maintenance knobs --------------------------------
        # compaction: merge adjacent sealed segments on idle ticks, priced
        # in the admission budget's device-bytes currency so maintenance
        # never preempts interactive work. demote_after: sealed segments
        # untouched this many store versions drop to the int4 cold tier.
        # Both default off — existing runtimes behave exactly as before.
        self.compaction = compaction
        self.demote_after = demote_after
        self.metrics = RuntimeMetrics()
        self.last_refresh_error: Optional[Exception] = None
        self._queue: List[_Entry] = []
        self._queued_bytes = 0
        self._queued_subs: set = set()           # id(sub) already enqueued
        self._drains: Dict[str, SubscriptionDrain] = {}
        self._refresh_failures: Dict[int, int] = {}   # id(sub) -> consecutive
        self._quarantined: Dict[int, Subscription] = {}
        self._next_qid = 0
        self._next_seq = 0

    # -- introspection -----------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def queued_device_bytes(self) -> int:
        """Estimated pipeline bytes of everything waiting (the retry-after
        currency)."""
        return self._queued_bytes

    def retry_after(self) -> float:
        """Backoff hint: the time the modeled service rate needs to drain
        the current backlog (floored at 1 ms so it is never zero)."""
        return max(1e-3, self._queued_bytes / self.service_bytes_per_s)

    # -- submission --------------------------------------------------------
    def submit(self, query: QueryLike, *, session: str = "default",
               priority: int = PRIORITY_NORMAL,
               deadline_s: Optional[float] = None
               ) -> Union[RuntimeTicket, SubmitRejection]:
        """Enqueue one interactive query for the named session.

        Parses/validates at submission (a malformed query fails its own
        submitter immediately, like ``QueryFrontend.submit``), prices the
        pipeline through the plan cache, and applies backpressure: a full
        queue returns a :class:`SubmitRejection` — a structured value, not
        an exception from deep in the engine — and drops nothing silently.
        The call is one ``lazyvlm.runtime.submit`` span.
        """
        with obs.span("runtime.submit"):
            return self._submit(query, session, priority, deadline_s)

    def _submit(self, query: QueryLike, session: str, priority: int,
                deadline_s: Optional[float]
                ) -> Union[RuntimeTicket, SubmitRejection]:
        sess = self.registry.open(session)
        q = sess.resolve(query)
        q.validate()
        est = self.admission.cost_of(q)
        if len(self._queue) >= self.max_queue:
            return self._reject(f"queue full ({self.max_queue} entries)")
        if (self.max_queue_device_bytes is not None
                and self._queued_bytes + est.device_bytes
                > self.max_queue_device_bytes):
            return self._reject(
                f"queue cost budget full "
                f"({self.max_queue_device_bytes} device bytes)")
        now = self.clock()
        if deadline_s is None:
            deadline_s = (self.default_slo_s
                          + est.device_bytes / self.service_bytes_per_s)
        ticket = RuntimeTicket(
            self._next_qid, q, now, session=session, priority=priority,
            deadline=now + deadline_s,
            store_version_at_submit=self.engine.store_version,
            est_device_bytes=est.device_bytes)
        self._next_qid += 1
        self._push(_Entry(self._next_seq, "query", priority, ticket.deadline,
                          now, est.device_bytes, est.rows, ticket=ticket))
        self.metrics.submitted += 1
        return ticket

    def _reject(self, reason: str) -> SubmitRejection:
        self.metrics.rejected += 1
        return SubmitRejection(reason=reason,
                               retry_after_s=self.retry_after(),
                               queue_depth=len(self._queue),
                               queue_device_bytes=self._queued_bytes)

    def _push(self, entry: _Entry) -> None:
        self._next_seq += 1
        self._queue.append(entry)
        self._queued_bytes += entry.est_device_bytes
        self.metrics.peak_queue_depth = max(self.metrics.peak_queue_depth,
                                            len(self._queue))

    # -- continuous queries ------------------------------------------------
    def follow(self, query: QueryLike, *, session: str = "default"
               ) -> StreamHandle:
        """Register a ``follow=true`` subscription and stream its deltas.

        The initial snapshot evaluates inline at registration (it is the
        subscriber's own cold query) and arrives as the stream's first
        delta; every later ingest batch produces one scheduled refresh
        whose :class:`RefreshDelta` lands on the handle — fed by the
        ``Subscription.add_listener`` hook, interleaved with interactive
        queries under the shared admission budget by :meth:`tick`."""
        sess = self.registry.open(session)
        sub = sess.subscribe(query)
        handle = StreamHandle(sub, session)
        sub.add_listener(handle._push)
        # the registration refresh ran before the listener attached; its
        # snapshot is the stream's first delta
        handle._push(_result_delta(None, sub.result,
                                   store_version=sub.version or 0,
                                   refresh_index=sub.stats.refreshes))
        return handle

    def update_stores(self, stores) -> int:
        """Point every session at the updated stores and enqueue refresh
        work for the now-stale subscriptions. Returns how many refresh
        entries were enqueued (dedup: a subscription already queued is not
        queued again — its refresh will see the newest version anyway)."""
        self.registry.update_stores(stores, refresh=False)
        return self.notify_ingest()

    def notify_ingest(self) -> int:
        """Collect stale subscriptions into the scheduling queue, fed
        session-by-session through a :class:`SubscriptionDrain` (its
        ``notify`` owns staleness bookkeeping)."""
        queued = 0
        now = self.clock()
        for sess in self.registry:
            name = sess.name or "default"
            drain = self._drains.get(name)
            if drain is None:
                drain = self._drains[name] = SubscriptionDrain(
                    sess, admission=self.admission)
            drain.notify()
            while drain.waiting:
                t = drain.waiting.popleft()
                if (id(t.sub) in self._queued_subs
                        or id(t.sub) in self._quarantined):
                    continue
                est = self.admission.cost_of(t.query)
                deadline = now + (self.default_slo_s + est.device_bytes
                                  / self.service_bytes_per_s)
                self._queued_subs.add(id(t.sub))
                self._push(_Entry(self._next_seq, "refresh",
                                  self.refresh_priority, deadline, now,
                                  est.device_bytes, est.rows, sub=t.sub))
                queued += 1
        return queued

    # -- background storage maintenance ------------------------------------
    def run_maintenance(self, now: Optional[float] = None) -> int:
        """One budgeted tiered-storage maintenance pass — idle ticks only
        (``tick`` calls this when the queue is empty, so interactive work
        always wins the round).

        Demotion (``demote_after``) drops long-untouched sealed segments
        to the int4 cold tier; compaction (``compaction``, a
        :class:`~repro.core.compact.CompactionPolicy`) merges adjacent
        sealed segments, admitting runs under the admission budget's
        ``max_device_bytes`` in the same currency queries are priced in
        (:func:`~repro.core.compact.compaction_cost_bytes`; the head run
        is always admitted, so a large backlog still drains one run per
        idle tick). Either action re-points every session through
        :meth:`update_stores`, queueing refreshes for stale
        subscriptions — which stay bit-identical: both passes are
        metadata-only and every scan mode is exact. Returns the number of
        maintenance actions applied (0 = idle and nothing to do)."""
        if self.compaction is None and self.demote_after is None:
            return 0
        from repro.core.compact import (compact_stores,
                                        compaction_cost_bytes,
                                        plan_compaction)
        from repro.core.stores import demote_cold_segments
        stores = self.engine.stores
        actions = 0
        if self.demote_after is not None:
            demoted = demote_cold_segments(stores,
                                           demote_after=self.demote_after)
            if demoted is not stores:
                self.metrics.demotions += sum(
                    1 for a, b in zip(stores.segments, demoted.segments)
                    if a.tier != b.tier)
                stores = demoted
                actions += 1
        if self.compaction is not None:
            runs = plan_compaction(stores, self.compaction)
            if runs:
                cap = self.admission.budget.max_device_bytes
                picked, total = [], 0
                for run in runs:
                    cost = compaction_cost_bytes(stores, (run,))
                    if picked and cap is not None and total + cost > cap:
                        break
                    picked.append(run)
                    total += cost
                merged_away = sum(hi - lo - 1 for lo, hi in picked)
                stores = compact_stores(stores, plan=tuple(picked))
                self.metrics.compactions += 1
                self.metrics.compacted_segments += merged_away
                self.metrics.compaction_bytes += total
                actions += 1
        if actions:
            self.update_stores(stores)
        return actions

    def release_quarantine(self, sub: Optional[Subscription] = None) -> int:
        """Lift the quarantine (one subscription, or all of them) and
        re-derive staleness through :meth:`notify_ingest` — a released
        subscription that is still behind the store version re-enters the
        queue immediately; an up-to-date one simply resumes on the next
        ingest. Returns how many refresh entries were enqueued."""
        if sub is None:
            for s in self._quarantined.values():
                s.tuning = True
            self._quarantined.clear()
            self._refresh_failures.clear()
        else:
            sub.tuning = True
            self._quarantined.pop(id(sub), None)
            self._refresh_failures.pop(id(sub), None)
        return self.notify_ingest()

    @property
    def quarantined_subscriptions(self) -> List[Subscription]:
        return list(self._quarantined.values())

    # -- scheduling --------------------------------------------------------
    def _effective_priority(self, entry: _Entry, now: float) -> int:
        """Priority class after aging: one class of boost per ``aging_s``
        waited, floored at 0 — the starvation-freedom mechanism."""
        if not self.aging_s:
            return entry.priority
        boost = int((now - entry.submitted_at) / self.aging_s)
        return max(0, entry.priority - boost)

    def _schedule_key(self, entry: _Entry, now: float):
        # EDF inside the effective class; seq breaks deadline ties FIFO
        return (self._effective_priority(entry, now), entry.deadline,
                entry.seq)

    def _select_batch(self, now: float) -> List[_Entry]:
        """Admission under the shared cost budget, in scheduling order.

        The head of the order is always admitted (no livelock); selection
        stops at the first entry that would overflow the budget rather
        than skipping past it, so a large high-priority query cannot be
        bypassed indefinitely by smaller late arrivals.

        Entries inside a retry-backoff window (``not_before``) are not
        eligible this round — they stay queued and become schedulable when
        the clock passes their gate."""
        order = sorted((e for e in self._queue if e.not_before <= now),
                       key=lambda e: self._schedule_key(e, now))
        b = self.admission.budget
        batch: List[_Entry] = []
        bytes_total = rows_total = 0
        for e in order:
            if batch and (
                    (b.max_device_bytes is not None
                     and bytes_total + e.est_device_bytes
                     > b.max_device_bytes)
                    or (b.max_rows is not None
                        and rows_total + e.est_rows > b.max_rows)
                    or (b.max_queries is not None
                        and len(batch) + 1 > b.max_queries)):
                break
            batch.append(e)
            bytes_total += e.est_device_bytes
            rows_total += e.est_rows
        taken = {e.seq for e in batch}
        self._queue = [e for e in self._queue if e.seq not in taken]
        self._queued_bytes -= bytes_total
        return batch

    def tick(self, now: Optional[float] = None) -> int:
        """One scheduling round: select a batch, execute it. Returns the
        number of work items processed (0 = idle).

        Query entries in the batch are **coalesced** into one
        ``query_batch`` call against the engine's current store version;
        refresh entries run their subscription's incremental refresh.

        Failure semantics (the daemon loop never dies on one bad batch):
        *transient* engine failures (:class:`TransientFault`,
        :class:`ServiceUnavailable`, :class:`DeviceLossError` — the last
        also triggers sticky re-placement) re-queue their entries with
        exponential backoff until ``max_ticket_retries``, then complete the
        ticket with a structured, cause-chained :class:`QueryFailure`;
        non-transient failures complete the batch's tickets immediately
        with the raw error attached. A refresh that keeps failing is
        retried with the same backoff and **quarantined** after
        ``max_refresh_failures`` consecutive failures instead of wedging
        the drain (see :meth:`release_quarantine`).

        **Idle ticks do storage maintenance**: with a
        :class:`~repro.core.compact.CompactionPolicy` configured, an empty
        queue runs one budgeted compaction/demotion pass instead of
        returning immediately (see :meth:`run_maintenance`) — interactive
        work always wins the tick.

        The round is one ``lazyvlm.runtime.tick`` span; inside it,
        ``lazyvlm.runtime.admit`` brackets the choice of the batch and
        ``lazyvlm.runtime.execute`` the engine call."""
        with obs.span("runtime.tick"):
            return self._tick(now)

    def _tick(self, now: Optional[float]) -> int:
        if not self._queue:
            n = self.run_maintenance(now)
            self._sync_adapt_metrics()
            return n
        if now is None:
            now = self.clock()
        with obs.span("runtime.admit"):
            self._expire_deadlines(now)
            batch = self._select_batch(now)
        if not batch:          # everything eligible is inside a backoff gate
            return 0
        queries = [e for e in batch if e.kind == "query"]
        refreshes = [e for e in batch if e.kind == "refresh"]
        if queries:
            self._execute_queries(queries)
        for e in refreshes:
            self._queued_subs.discard(id(e.sub))
            try:
                e.sub.refresh()
                self.metrics.refreshes += 1
                self._refresh_failures.pop(id(e.sub), None)
            except Exception as exc:              # keep serving
                self.metrics.refresh_failures += 1
                self.last_refresh_error = exc
                self._refresh_failed(e, exc, now)
        self.metrics.batches += 1
        self.admission.batches_admitted += 1
        self._sync_adapt_metrics()
        return len(batch)

    def _sync_adapt_metrics(self) -> None:
        """Mirror the engine's adaptation counters into the runtime's
        lifetime metrics (absolute copies: the engine's AdaptiveStats is
        the source of truth; with adaptation off they stay zero)."""
        adapt = getattr(self.engine, "adapt", None)
        if adapt is None:
            return
        self.metrics.adaptations = adapt.adaptations
        self.metrics.reorders = adapt.reorders
        self.metrics.budget_changes = adapt.budget_changes

    def _expire_deadlines(self, now: float) -> None:
        """Fail query entries whose EDF deadline already passed (opt-in via
        ``enforce_deadlines``): they complete with a structured
        ``kind="deadline"`` :class:`QueryFailure` instead of consuming a
        batch slot they can no longer use."""
        if not self.enforce_deadlines:
            return
        expired = [e for e in self._queue
                   if e.kind == "query" and e.deadline < now]
        if not expired:
            return
        taken = {e.seq for e in expired}
        self._queue = [e for e in self._queue if e.seq not in taken]
        for e in expired:
            self._queued_bytes -= e.est_device_bytes
            t = e.ticket
            t.error = QueryFailure(
                f"deadline missed by {now - e.deadline:.3f}s",
                kind="deadline", attempts=e.attempts,
                elapsed_s=now - t.submitted_at, deadline=e.deadline)
            t.done = True
            t.completed_at = now
            self.metrics.failed += 1
            self.metrics.deadline_failures += 1
            t._complete()

    def _backoff_gate(self, attempt: int, now: float) -> float:
        """Eligibility time for retry number ``attempt`` (1-based):
        exponential backoff scaled up by the injectable jitter."""
        frac = self.retry_jitter(attempt) if self.retry_jitter else 0.0
        return now + (self.retry_backoff_s * 2 ** max(0, attempt - 1)
                      * (1.0 + frac))

    def _requeue(self, e: _Entry) -> None:
        """Put a transiently-failed entry back (original ``seq`` — its
        FIFO tie-break and aging baseline survive the retry)."""
        self._queue.append(e)
        self._queued_bytes += e.est_device_bytes
        self.metrics.requeued += 1
        self.metrics.peak_queue_depth = max(self.metrics.peak_queue_depth,
                                            len(self._queue))

    def _refresh_failed(self, e: _Entry, exc: Exception, now: float) -> None:
        n = self._refresh_failures.get(id(e.sub), 0) + 1
        self._refresh_failures[id(e.sub)] = n
        if n >= self.max_refresh_failures:
            # poisoned: stop retrying so it cannot wedge the drain; the
            # subscription's state is untouched (refresh commits only on
            # success) and release_quarantine resumes it exactly. Its
            # budget-tuner feed stops with it — a failing subscription
            # must not keep steering the engine's shared tuner
            self._quarantined[id(e.sub)] = e.sub
            e.sub.tuning = False
            self.metrics.quarantined += 1
            return
        e.attempts += 1
        e.not_before = self._backoff_gate(n, now)
        self._queued_subs.add(id(e.sub))
        self._requeue(e)

    def _execute_queries(self, entries: List[_Entry]) -> None:
        tickets = [e.ticket for e in entries]
        admitted = self.clock()
        for t in tickets:
            t.admitted_at = admitted
        started = self.clock()
        for t in tickets:
            t.execute_started_at = started
            t.coalesced_with = len(tickets)
        try:
            # metadata ties the batch's spans to its tickets: the runtime's
            # batch counter and the ticket ids, space-separated (the
            # profiler's metadata encoding reserves ",")
            with obs.span("runtime.execute", batch=self.metrics.batches,
                          qids=" ".join(str(t.qid) for t in tickets)):
                results = self.engine.query_batch(
                    [t.query for t in tickets])
            error = None
        except Exception as exc:
            if self._handle_query_failure(entries, exc):
                return           # transient: re-queued / structured-failed
            results = [None] * len(tickets)
            error = exc
        done = self.clock()
        for t, r in zip(tickets, results):
            t.result = r
            t.error = error
            t.done = True
            t.completed_at = done
            if error is None:
                self.metrics.completed += 1
            else:
                self.metrics.failed += 1
            t._complete()
        if len(tickets) > 1:
            self.metrics.coalesced_queries += len(tickets)

    def _handle_query_failure(self, entries: List[_Entry],
                              exc: Exception) -> bool:
        """Classify one batch failure. Transient classes — the fault
        layer's :class:`TransientFault` / :class:`ServiceUnavailable`, and
        :class:`DeviceLossError` (which additionally triggers the engine's
        sticky re-placement) — re-queue each entry with exponential
        backoff while its retry budget lasts, then complete its ticket
        with a ``kind="retries_exhausted"`` :class:`QueryFailure` chaining
        the cause. Returns True when the failure was handled here;
        non-transient errors return False and take the raw-error path
        (unchanged pre-fault-layer behavior)."""
        now = self.clock()
        if isinstance(exc, DeviceLossError):
            self.metrics.device_losses += 1
            if hasattr(self.engine, "mark_device_lost"):
                self.engine.mark_device_lost(exc.ordinal)
        elif not isinstance(exc, (TransientFault, ServiceUnavailable)):
            return False
        for e in entries:
            if e.attempts < self.max_ticket_retries:
                e.attempts += 1
                e.not_before = self._backoff_gate(e.attempts, now)
                self._requeue(e)                 # ticket stays pending
                continue
            t = e.ticket
            t.error = QueryFailure(
                f"transient failures outlived {e.attempts} retries: {exc}",
                kind="retries_exhausted", attempts=e.attempts + 1,
                elapsed_s=now - t.submitted_at, cause=exc)
            t.done = True
            t.completed_at = now
            self.metrics.failed += 1
            self.metrics.retry_exhausted += 1
            t._complete()
        return True

    def run_until_idle(self, max_ticks: int = 10_000) -> int:
        """Drive ticks until nothing is schedulable; returns items
        processed. Entries still inside a retry-backoff gate remain queued
        — re-invoke once the clock passes their ``not_before`` (tests
        advance the injected clock; the async driver simply keeps
        ticking)."""
        done = 0
        for _ in range(max_ticks):
            n = self.tick()
            if n == 0:
                return done
            done += n
        return done


# ---------------------------------------------------------------------------
# asyncio wrapper
# ---------------------------------------------------------------------------
class AsyncStream:
    """Async-iterator adapter over a :class:`StreamHandle`."""

    def __init__(self, handle: StreamHandle):
        self.handle = handle
        self._q: asyncio.Queue = asyncio.Queue()
        for d in handle.poll():                  # already-buffered deltas
            self._q.put_nowait(d)
        handle.on_delta = self._q.put_nowait     # future deltas go straight in

    def __aiter__(self) -> "AsyncStream":
        return self

    async def __anext__(self) -> RefreshDelta:
        if self.handle.closed and self._q.empty():
            raise StopAsyncIteration
        return await self._q.get()

    @property
    def result(self) -> Optional[QueryResult]:
        return self.handle.result

    def close(self) -> None:
        self.handle.close()


class AsyncServingRuntime:
    """asyncio facade over :class:`ServingRuntime`.

    No threads: ``start()`` spawns one event-loop task that calls
    ``tick()`` whenever there is work (yielding between ticks), so every
    scheduling decision still happens in the deterministic core.
    ``submit`` awaits the ticket's result (raising
    :class:`RuntimeOverloaded` on backpressure, or the engine's error if
    the batch failed); ``follow`` returns an async iterator of
    :class:`RefreshDelta`. Usable as an async context manager."""

    def __init__(self, runtime: ServingRuntime, *,
                 idle_sleep_s: float = 0.002):
        self.runtime = runtime
        self.idle_sleep_s = idle_sleep_s
        self._task: Optional[asyncio.Task] = None
        self._running = False

    def start(self) -> None:
        if self._task is None:
            self._running = True
            self._task = asyncio.get_running_loop().create_task(
                self._drive())

    async def stop(self) -> None:
        self._running = False
        if self._task is not None:
            await self._task
            self._task = None

    async def __aenter__(self) -> "AsyncServingRuntime":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    async def _drive(self) -> None:
        while self._running:
            n = self.runtime.tick()
            # yield to submitters between ticks; nap when idle
            await asyncio.sleep(0.0 if n else self.idle_sleep_s)

    async def submit(self, query: QueryLike, *, session: str = "default",
                     priority: int = PRIORITY_NORMAL,
                     deadline_s: Optional[float] = None) -> QueryResult:
        out = self.runtime.submit(query, session=session, priority=priority,
                                  deadline_s=deadline_s)
        if isinstance(out, SubmitRejection):
            raise RuntimeOverloaded(out)
        fut = asyncio.get_running_loop().create_future()

        def _done(t: RuntimeTicket) -> None:
            if fut.done():
                return
            if t.error is not None:
                fut.set_exception(t.error)
            else:
                fut.set_result(t.result)

        out.add_callback(_done)
        return await fut

    async def follow(self, query: QueryLike, *, session: str = "default"
                     ) -> AsyncStream:
        return AsyncStream(self.runtime.follow(query, session=session))

    def update_stores(self, stores) -> int:
        """Synchronous by design: ingest is the producer side's call."""
        return self.runtime.update_stores(stores)
