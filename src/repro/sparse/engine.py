"""Continuous-batching async serving engine over persistent plans.

``repro.sparse.stream`` replays one plan synchronously: the caller owns
the loop, every ``execute`` serves exactly one right-hand side, and the
host blocks per call.  Production traffic is many concurrent streams with
mixed widths and deadlines — the regime this module serves:

    engine = ServingEngine(max_queue=256, policy="wait")
    engine.register("moe", sparse.plan(m, BSpec(d=64, reuse=4096)))
    engine.start()                        # worker thread
    t = engine.submit("moe", b)           # any thread; bounded queue
    c = t.result()                        # per-request future
    print(engine.summary())               # batches, latency, goodput

The serving loop is four stages, each inspectable in :meth:`ServingEngine
.stats`:

1. **Admission.**  ``submit`` tags each request ``(operator, d,
   deadline)`` and appends it to a bounded queue.  A full queue applies
   the backpressure policy: ``"wait"`` blocks the submitter (optionally
   up to a timeout), ``"shed"`` rejects immediately with
   :class:`ShedError` — load-shedding at admission, before any work is
   sunk into the request.

2. **Micro-batch coalescing.**  The drafting step takes the queue head
   and every other queued request for the *same operator* (FIFO within
   the operator) until the plan's column budget is reached, concatenates
   their right-hand sides column-wise, and replays the whole batch
   through one ``execute_wide`` call.  Columns of B are independent in
   SpMM, so coalescing is exact — and it is itself a bandwidth
   optimization: one launch reads A once for the whole batch where
   per-request replay re-reads it per request (the propagation-blocking
   argument, arXiv 2002.11302, applied at the serving layer).  Batches
   never mix plans, and the per-launch width respects the plan's
   ``coalesce_block_d`` (pallas layouts replay at the planned width their
   B-slab was packed for; jax kernels take the whole batch in one call).

3. **Double-buffered staging.**  Dispatch is asynchronous
   (``KernelSpec.async_dispatch``), so after enqueueing batch *i* the
   engine drafts and stages batch *i+1* — host-side concatenation plus
   ``jax.device_put`` — before blocking on *i*: host transfer overlaps
   device compute.  ``KernelSpec.donate_b`` governs when the staged
   buffer may be dropped (at dispatch when the launch consumes it, at
   materialization otherwise).

4. **Completion + plan swap.**  One ``block_until_ready`` per batch (not
   per request), result columns sliced back per ticket, latencies
   recorded.  Between batches the engine polls
   ``plan.maybe_replan()`` — when a stream has outlived its planned reuse
   horizon the plan is rebuilt at the observed horizon and swapped
   atomically under the queue lock; in-flight batches keep the plan they
   were drafted against.

Each stage is timed on the worker thread as a ``repro.engine.*`` span
(:mod:`repro.obs`): ``draft``, ``stage``, ``dispatch`` (the enqueue of
the launch), ``wait`` (``block_until_ready``), ``fetch`` (the copy to the
host) and ``complete``, each with attr ``batch`` (``BatchRecord.seq``),
``idle`` while the worker waits for work, and ``submit`` on the caller's
thread with attr ``request`` (the ticket id).

Latency accounting (the numbers ``stats`` reports): a request's latency
is measured from the ``submit`` call's entry (so backpressure wait is
*included* — it is part of what the client observes) to the completion of
``block_until_ready`` on its batch.  p50/p99 are percentiles over served
requests; goodput counts only requests that met their deadline (all
served requests when no deadline was given), divided by the span from
first admission to last completion.  ``docs/serving_engine.md`` walks
through the methodology.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.precision import as_precision
from repro.sparse.stream import StreamPlan

#: Default cap on the staged host->device buffer per micro-batch, in
#: bytes.  Two batches are in flight under double buffering, so the
#: engine's staging footprint is at most twice this.
DEFAULT_STAGE_BYTES: int = 8 * 2 ** 20

#: Default bounded-queue depth (requests).
DEFAULT_MAX_QUEUE: int = 256


class ShedError(RuntimeError):
    """A request was refused at admission (queue full under ``"shed"``,
    or the ``"wait"`` timeout expired before space opened up)."""


def _stage_dtype(plan: StreamPlan):
    """The dtype batches are staged (and executed) at for ``plan``.

    A reduced-precision plan's kernels would cast B on device anyway, so
    the engine casts at staging instead — halving the host->device bytes
    the double buffering has to hide.  Full-precision plans stage at the
    stream's declared dtype.
    """
    prec = as_precision(plan.dispatch.precision)
    return prec.value_jnp if prec.reduced else plan.spec.dtype


def coalesce_budget(plan: StreamPlan, *,
                    stage_bytes: int = DEFAULT_STAGE_BYTES) -> int:
    """Max total RHS columns one micro-batch may carry for ``plan``.

    Two constraints meet here:

    * the staged operand — ``[n, cols]`` at the plan's staging dtype
      (the reduced value dtype for a bf16 plan, else the stream dtype),
      concatenated on the host and moved in one ``device_put`` — must
      fit the staging budget (double buffering keeps two of these
      alive);
    * the batch replays through ``execute_wide`` at the plan's
      ``coalesce_block_d``, so per-launch kernel tiling (including the
      binned B slab packed for ``plan_d``) is unchanged by coalescing — the
      budget never needs to model VMEM, only host staging.

    The result is floored at the planned width (a planned-width request
    must always be servable) and rounded down to a multiple of it when
    possible, so batches split evenly into planned-width launches.

    Args:
        plan: the bound :class:`~repro.sparse.stream.StreamPlan`.
        stage_bytes: staging-buffer budget in bytes.

    Returns:
        The column budget (>= ``plan.spec.d``).
    """
    itemsize = np.dtype(_stage_dtype(plan)).itemsize
    cap = max(int(stage_bytes) // (plan.n * itemsize), 1)
    d = max(plan.spec.d, 1)
    return max(d, (cap // d) * d)


@dataclasses.dataclass
class Ticket:
    """Per-request handle: the future plus the request's audit record.

    Attributes:
        id: admission sequence number (unique per engine).
        operator: the registered plan the request was tagged with.
        d: the request's RHS width (requests of mixed widths coalesce).
        deadline_s: absolute deadline on the engine clock, or None.
        submitted_s: clock at ``submit`` entry (latency starts here —
            backpressure wait counts against the request).
        batched_s: clock when the request was drafted into a micro-batch.
        done_s: clock when its batch finished materializing.
        batch_seq: sequence number of the batch that served it.
    """

    id: int
    operator: str
    d: int
    deadline_s: Optional[float] = None
    submitted_s: float = 0.0
    batched_s: Optional[float] = None
    done_s: Optional[float] = None
    batch_seq: Optional[int] = None
    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)
    _result: Optional[jnp.ndarray] = dataclasses.field(
        default=None, repr=False)
    _error: Optional[BaseException] = dataclasses.field(
        default=None, repr=False)

    def done(self) -> bool:
        """Whether the request finished (result or error is available)."""
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until served and return this request's ``[n, d]`` result.

        The value is a host-side array (a view into its batch's
        materialized output): a serving engine's responses leave the
        device anyway, and host slicing is what keeps mixed-width
        batches from paying one compiled-slice program per ticket.

        Args:
            timeout: seconds to wait; None waits forever.

        Raises:
            TimeoutError: the request did not complete in time.
            BaseException: whatever the execution raised, re-raised here.
        """
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.id} ({self.operator}, d={self.d}) not "
                f"served within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    @property
    def latency_s(self) -> Optional[float]:
        """submit-to-completion latency; None until served."""
        if self.done_s is None:
            return None
        return self.done_s - self.submitted_s

    @property
    def met_deadline(self) -> Optional[bool]:
        """Whether completion beat the deadline (None = no deadline)."""
        if self.deadline_s is None or self.done_s is None:
            return None
        return self.done_s <= self.deadline_s


@dataclasses.dataclass
class _Request:
    """A queued request: the ticket plus its host-side operand."""

    ticket: Ticket
    b: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class BatchRecord:
    """One executed micro-batch's audit row (``ServingEngine.batch_log``).

    The serving loop's per-batch decisions stay inspectable the way
    ``DispatchPlan.summary()`` keeps dispatch decisions inspectable:
    which operator, which requests, how wide, how long.
    """

    seq: int                      # batch number, in draft order
    operator: str
    chosen: str                   # format the plan executed
    request_ids: Tuple[int, ...]
    widths: Tuple[int, ...]       # per-request d
    cols: int                     # total columns incl. padding
    block_d: int                  # per-launch width the batch replayed at
    queued_s: float               # oldest member's admission->draft wait
    exec_s: float                 # draft -> materialized


@dataclasses.dataclass
class _Staged:
    """A drafted batch staged on device, awaiting dispatch."""

    seq: int                      # the batch's number, from its draft
    plan: StreamPlan
    requests: List[_Request]
    b_dev: jnp.ndarray
    block_d: int
    cols: int


class ServingEngine:
    """Request-queue serving loop over registered persistent plans.

    Deterministic core + optional worker thread: :meth:`submit` /
    :meth:`step` / :meth:`drain` are a single-threaded API (tests drive
    it with an injected fake clock); :meth:`start` runs the same loop on
    a daemon thread so ``submit`` becomes fire-and-forget from any
    thread.

    Args:
        max_queue: bounded-queue depth; admission beyond it applies the
            backpressure policy.
        policy: ``"wait"`` (block the submitter until space) or
            ``"shed"`` (raise :class:`ShedError` immediately).
        max_batch_cols: column budget per micro-batch; None derives it
            per plan from the staging budget (:func:`coalesce_budget`).
        stage_bytes: staging-buffer budget behind the derived column
            budget.
        clock: monotonic-seconds callable; injectable for deterministic
            latency tests (default ``time.monotonic``).
        double_buffer: stage the next batch between dispatching and
            blocking on the current one (disabled automatically when the
            plan's kernel reports ``async_dispatch=False`` — without
            async dispatch there is no compute to overlap with).
        auto_replan: poll ``plan.maybe_replan()`` after each batch and
            swap the fresh plan in atomically when the reuse audit fires.
        batch_log_depth: how many :class:`BatchRecord` rows to retain.
    """

    def __init__(self, *, max_queue: int = DEFAULT_MAX_QUEUE,
                 policy: str = "wait",
                 max_batch_cols: Optional[int] = None,
                 stage_bytes: int = DEFAULT_STAGE_BYTES,
                 clock: Callable[[], float] = time.monotonic,
                 double_buffer: bool = True,
                 auto_replan: bool = True,
                 batch_log_depth: int = 64):
        if policy not in ("wait", "shed"):
            raise ValueError(
                f"policy must be 'wait' or 'shed', got {policy!r}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._plans: Dict[str, StreamPlan] = {}
        self._queue: Deque[_Request] = collections.deque()
        self._lock = threading.RLock()
        self._space = threading.Condition(self._lock)   # waiters on a full q
        self._work = threading.Condition(self._lock)    # worker wake-up
        self.max_queue = max_queue
        self.policy = policy
        self.max_batch_cols = max_batch_cols
        self.stage_bytes = stage_bytes
        self.clock = clock
        self.double_buffer = double_buffer
        self.auto_replan = auto_replan
        self.batch_log: Deque[BatchRecord] = collections.deque(
            maxlen=batch_log_depth)
        self._staged: Optional[_Staged] = None
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._seq = 0
        self._batch_seq = 0
        self._latencies: List[float] = []
        self._counts = {"admitted": 0, "served": 0, "shed": 0,
                        "batches": 0, "coalesced": 0, "replans": 0,
                        "deadline_miss": 0}
        self._first_submit_s: Optional[float] = None
        self._last_done_s: Optional[float] = None

    # ------------------------------------------------------------- #
    # Operators
    # ------------------------------------------------------------- #

    def register(self, name: str, plan: StreamPlan) -> StreamPlan:
        """Register ``plan`` as operator ``name``; returns the plan.

        A sharded plan (``sparse.plan(m, spec, mesh=...)``) registers the
        same way — the engine consults its ``exec_hints`` /
        ``coalesce_block_d`` overrides and otherwise treats it as any
        other plan.
        """
        with self._lock:
            self._plans[name] = plan
        return plan

    def plan_for(self, name: str) -> StreamPlan:
        """The plan currently serving operator ``name`` (post any swaps)."""
        with self._lock:
            return self._plans[name]

    def budget_for(self, name: str) -> int:
        """The micro-batch column budget applied to operator ``name``."""
        plan = self.plan_for(name)
        if self.max_batch_cols is not None:
            return max(self.max_batch_cols, plan.spec.d)
        return coalesce_budget(plan, stage_bytes=self.stage_bytes)

    def warmup(self, name: str, *, max_cols: Optional[int] = None) -> int:
        """Prime the compiled-launch cache for operator ``name``.

        Coalesced batches replay at quantized widths
        (``plan.coalesce_block_d``), and each distinct width jit-compiles
        once; serving traffic through cold size classes puts those
        compiles inside request latencies.  This runs one zero-operand
        ``execute_wide`` per size class up to the column budget (or
        ``max_cols``), then resets the plan's execution counter so the
        warm-up doesn't skew its reuse audit.

        Args:
            name: a registered operator.
            max_cols: cap on the largest class to warm; defaults to the
                operator's coalescing budget.

        Returns:
            Number of distinct launch widths warmed.
        """
        plan = self.plan_for(name)
        cap = self.budget_for(name) if max_cols is None else max(
            int(max_cols), plan.spec.d)
        classes = []
        cols = plan.spec.d
        while True:
            block = plan.coalesce_block_d(cols)
            if block not in classes:
                classes.append(block)
            if cols >= cap:
                break
            cols = min(cols * 2, cap)
        for block in classes:
            b = jnp.zeros((plan.n, block), _stage_dtype(plan))
            jax.block_until_ready(plan.execute_wide(b, block_d=block))
        plan.reset_stats()
        return len(classes)

    def reset_stats(self) -> None:
        """Zero latency/counter accounting (e.g. after a warm-up wave).

        Registered plans, queue contents, and ticket-id numbering are
        untouched; only the served-request accounting (latencies,
        counters, batch log, goodput span) restarts.
        """
        with self._lock:
            self._latencies.clear()
            self.batch_log.clear()
            for k in self._counts:
                self._counts[k] = 0
            self._first_submit_s = None
            self._last_done_s = None

    # ------------------------------------------------------------- #
    # Admission (stage 1)
    # ------------------------------------------------------------- #

    def submit(self, operator: str, b: jnp.ndarray, *,
               deadline_s: Optional[float] = None,
               timeout: Optional[float] = None) -> Ticket:
        """Admit one request; returns its :class:`Ticket`.

        Args:
            operator: a name previously :meth:`register`-ed.
            b: dense right-hand side ``[n, d]`` (any width; requests of
                mixed widths coalesce into shared batches).
            deadline_s: optional deadline in seconds *from admission*;
                missed deadlines are counted (and excluded from goodput)
                but the request is still served.
            timeout: under ``policy="wait"``, how long to block for queue
                space before shedding anyway; None waits forever.

        Raises:
            KeyError: unknown operator.
            ValueError: operand shape incompatible with the plan.
            ShedError: queue full under ``"shed"``, or wait timed out.
        """
        with obs.span("repro.engine.submit") as attrs:
            ticket = self._admit(operator, b, deadline_s, timeout)
            attrs["request"] = ticket.id
        return ticket

    def _admit(self, operator: str, b: jnp.ndarray,
               deadline_s: Optional[float],
               timeout: Optional[float]) -> Ticket:
        t0 = self.clock()
        with self._lock:
            plan = self._plans[operator]        # KeyError = unknown operator
        if getattr(b, "ndim", 0) != 2 or b.shape[0] != plan.n:
            raise ValueError(
                f"operand shape {tuple(getattr(b, 'shape', ()))} "
                f"incompatible with operator {operator!r} for "
                f"[{plan.n}, {plan.n}] matrix; expected [{plan.n}, d]")
        ticket = Ticket(
            id=-1, operator=operator, d=int(b.shape[1]),
            deadline_s=None if deadline_s is None else t0 + deadline_s,
            submitted_s=t0)
        with self._space:
            while len(self._queue) >= self.max_queue:
                if self.policy == "shed":
                    self._counts["shed"] += 1
                    raise ShedError(
                        f"queue full ({self.max_queue}); request for "
                        f"{operator!r} shed at admission")
                if not self._space.wait(timeout):
                    self._counts["shed"] += 1
                    raise ShedError(
                        f"queue full ({self.max_queue}) for {timeout}s; "
                        f"request for {operator!r} shed after waiting")
            ticket.id = self._seq
            self._seq += 1
            self._counts["admitted"] += 1
            if self._first_submit_s is None:
                self._first_submit_s = t0
            self._queue.append(_Request(ticket=ticket, b=b))
            self._work.notify_all()
        return ticket

    def pending(self) -> int:
        """Requests admitted but not yet drafted into a batch."""
        with self._lock:
            return len(self._queue)

    # ------------------------------------------------------------- #
    # Coalescing + staging (stages 2-3)
    # ------------------------------------------------------------- #

    def _draft(self) -> Optional[Tuple[StreamPlan, List[_Request], int]]:
        """Pop the next micro-batch and number it (stage 2, under lock).

        The queue head anchors the batch; every other queued request for
        the same operator joins in FIFO order until the column budget is
        hit.  Requests for other operators keep their relative order and
        wait for a later batch — the head is always served, so no
        operator starves.
        """
        with self._lock:
            if not self._queue:
                return None
            seq = self._batch_seq
            self._batch_seq += 1
            with obs.span("repro.engine.draft", batch=seq):
                head = self._queue.popleft()
                op = head.ticket.operator
                plan = self._plans[op]
                budget = (max(self.max_batch_cols, plan.spec.d)
                          if self.max_batch_cols is not None
                          else coalesce_budget(plan,
                                               stage_bytes=self.stage_bytes))
                batch = [head]
                cols = head.ticket.d
                rest: List[_Request] = []
                while self._queue:
                    req = self._queue.popleft()
                    if (req.ticket.operator == op
                            and cols + req.ticket.d <= budget):
                        batch.append(req)
                        cols += req.ticket.d
                    else:
                        rest.append(req)
                self._queue.extend(rest)
                self._space.notify_all()
                return plan, batch, seq

    def _stage(self) -> Optional[_Staged]:
        """Draft the next batch and move its operand to device (stage 3).

        Host-side work only — column concatenation, padding to a multiple
        of the plan's ``coalesce_block_d``, and an asynchronous
        ``device_put`` — so calling this between dispatching and blocking
        on the previous batch overlaps the transfer with device compute.
        """
        drafted = self._draft()
        if drafted is None:
            return None
        plan, batch, seq = drafted
        with obs.span("repro.engine.stage", batch=seq):
            t_batch = self.clock()
            for req in batch:
                req.ticket.batched_s = t_batch
            cols = sum(r.ticket.d for r in batch)
            block_d = plan.coalesce_block_d(cols)
            pad = (-cols) % block_d
            # Concatenate on the host (NumPy), not with jnp: an eager
            # jnp.concatenate compiles one XLA program per distinct
            # width-combination, and arrival timing makes nearly every batch
            # a new combination — recompiles would dominate the batch.  One
            # memcpy-shaped concat plus a single device_put is the staging
            # transfer the double buffering exists to overlap.  Staging casts
            # to the plan's precision dtype here, on the host, so a bf16 plan
            # moves half the bytes per batch.
            stage_dt = np.dtype(_stage_dtype(plan))
            parts = [np.asarray(r.b, dtype=stage_dt) for r in batch]
            if pad:
                parts.append(np.zeros((plan.n, pad), stage_dt))
            wide = parts[0] if len(parts) == 1 else np.concatenate(
                parts, axis=1)
            return _Staged(seq=seq, plan=plan, requests=batch,
                           b_dev=jax.device_put(wide), block_d=block_d,
                           cols=cols + pad)

    # ------------------------------------------------------------- #
    # Execution (stage 4)
    # ------------------------------------------------------------- #

    def step(self) -> int:
        """Execute one micro-batch; returns the number of requests served.

        Consumes the staged batch if double buffering left one, else
        drafts fresh; dispatches its single ``execute_wide`` call; stages
        the *next* batch while the device computes (when the plan's
        kernel dispatches asynchronously — ``exec_hints``); blocks once;
        then slices per-request results out and completes the tickets.
        Returns 0 when the queue is idle.
        """
        staged = self._staged
        self._staged = None
        if staged is None:
            staged = self._stage()
        if staged is None:
            return 0
        plan, batch, seq = staged.plan, staged.requests, staged.seq
        hints = plan.exec_hints()
        try:
            with obs.span("repro.engine.dispatch", batch=seq):
                out = plan.execute_wide(staged.b_dev,
                                        block_d=staged.block_d)
            if hints.get("donate_b"):
                # The launch consumed the staged buffer; drop our alias
                # now rather than at materialization.
                staged.b_dev = None
            if self.double_buffer and hints.get("async_dispatch", True):
                self._staged = self._stage()    # overlaps device compute
            with obs.span("repro.engine.wait", batch=seq):
                jax.block_until_ready(out)
        except Exception as exc:               # noqa: BLE001 - delivered
            t_done = self.clock()
            for req in batch:
                req.ticket._error = exc
                req.ticket.done_s = t_done
                req.ticket._event.set()
            raise
        # Slice per-request results from the materialized host array:
        # eager jnp slices compile per (offset, width) pair, so a mixed
        # batch would pay a compile per ticket; NumPy views are free and
        # the batch is already synced.
        with obs.span("repro.engine.fetch", batch=seq):
            host = np.asarray(out)
        with obs.span("repro.engine.complete", batch=seq):
            self._complete(staged, host)
        return len(batch)

    def _complete(self, staged: _Staged, host: np.ndarray) -> None:
        """Hand each ticket its columns of ``host``, account for the
        batch, and poll for a plan swap."""
        batch = staged.requests
        t_done = self.clock()
        lo = 0
        for req in batch:
            tk = req.ticket
            tk._result = host[:, lo:lo + tk.d]
            lo += tk.d
            tk.done_s = t_done
            tk.batch_seq = staged.seq
            tk._event.set()
        with self._lock:
            self._counts["batches"] += 1
            self._counts["served"] += len(batch)
            if len(batch) > 1:
                self._counts["coalesced"] += len(batch)
            self._counts["deadline_miss"] += sum(
                1 for r in batch if r.ticket.met_deadline is False)
            self._latencies.extend(r.ticket.latency_s for r in batch)
            self._last_done_s = t_done
            oldest = min(r.ticket.submitted_s for r in batch)
            self.batch_log.append(BatchRecord(
                seq=staged.seq, operator=batch[0].ticket.operator,
                chosen=staged.plan.chosen,
                request_ids=tuple(r.ticket.id for r in batch),
                widths=tuple(r.ticket.d for r in batch),
                cols=staged.cols, block_d=staged.block_d,
                queued_s=batch[0].ticket.batched_s - oldest,
                exec_s=t_done - batch[0].ticket.batched_s))
        if self.auto_replan:
            self._maybe_swap(batch[0].ticket.operator)

    def _maybe_swap(self, operator: str) -> None:
        """Atomic mid-stream plan swap when the reuse audit fired.

        ``maybe_replan`` rebuilds (and fully binds) the plan *outside*
        the serving lock; only the reference swap happens under it, so
        admission never stalls behind a re-plan.  Batches already staged
        against the old plan run to completion on it.
        """
        with self._lock:
            plan = self._plans.get(operator)
        if plan is None:
            return
        fresh = plan.maybe_replan()
        if fresh is None:
            return
        with self._lock:
            # Swap only if nobody else swapped meanwhile.
            if self._plans.get(operator) is plan:
                self._plans[operator] = fresh
                self._counts["replans"] += 1

    def drain(self) -> int:
        """Serve until the queue (and any staged batch) is empty.

        Returns:
            Total requests served by this call.
        """
        total = 0
        while True:
            served = self.step()
            if served == 0 and self._staged is None:
                with self._lock:
                    if not self._queue:
                        return total
            total += served

    # ------------------------------------------------------------- #
    # Worker thread
    # ------------------------------------------------------------- #

    def start(self) -> None:
        """Spawn the worker thread consuming the queue (idempotent)."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stopping = False
            self._thread = threading.Thread(
                target=self._worker, name="serving-engine", daemon=True)
            self._thread.start()

    def stop(self, *, drain: bool = True,
             timeout: Optional[float] = None) -> None:
        """Stop the worker thread.

        Args:
            drain: serve everything already admitted before exiting;
                False abandons queued requests (their tickets never
                complete — callers using ``result(timeout=...)`` see a
                ``TimeoutError``).
            timeout: join timeout in seconds.
        """
        with self._lock:
            self._stopping = True
            self._drain_on_stop = drain
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def _nothing_to_do(self) -> bool:
        return not self._queue and self._staged is None \
            and not self._stopping

    def _worker(self) -> None:
        """Worker loop: wait for admissions, serve batches until stopped.

        The wake condition covers the staged batch too: double buffering
        can leave a drafted batch in ``self._staged`` after the queue
        empties, and waiting on admissions alone would strand it (and
        its requests) until the next submit.
        """
        while True:
            with self._work:
                if self._nothing_to_do():
                    with obs.span("repro.engine.idle"):
                        while self._nothing_to_do():
                            self._work.wait(0.1)
                if self._stopping and (
                        not getattr(self, "_drain_on_stop", True)
                        or not self._queue):
                    if self._staged is None:
                        return
            self.step()

    # ------------------------------------------------------------- #
    # Accounting
    # ------------------------------------------------------------- #

    def stats(self) -> dict:
        """Counters + latency percentiles + goodput, as one dict.

        Keys: ``admitted`` / ``served`` / ``shed`` / ``batches`` /
        ``coalesced`` (requests that shared a batch) / ``replans`` /
        ``deadline_miss`` / ``queue_depth`` / ``mean_batch_cols`` /
        ``p50_us`` / ``p99_us`` (percentiles over served requests'
        submit-to-completion latencies) / ``goodput_rps`` (deadline-
        meeting completions per second of serving wall time) /
        ``operators`` (each registered plan's own ``stats()``).
        """
        with self._lock:
            lats = list(self._latencies)
            counts = dict(self._counts)
            depth = len(self._queue)
            log = list(self.batch_log)
            span = ((self._last_done_s - self._first_submit_s)
                    if self._latencies and self._first_submit_s is not None
                    else 0.0)
            ops = {name: p.stats() for name, p in self._plans.items()}
        good = counts["served"] - counts["deadline_miss"]
        out = dict(counts)
        out.update({
            "queue_depth": depth,
            "mean_batch_cols": (float(np.mean([r.cols for r in log]))
                                if log else 0.0),
            "p50_us": float(np.percentile(lats, 50) * 1e6) if lats else 0.0,
            "p99_us": float(np.percentile(lats, 99) * 1e6) if lats else 0.0,
            "goodput_rps": good / span if span > 0 else 0.0,
            "operators": ops,
        })
        return out

    def summary(self) -> str:
        """Human-readable audit: counters plus the recent batch log."""
        s = self.stats()
        lines = [
            f"ServingEngine(policy={self.policy}, "
            f"max_queue={self.max_queue}): "
            f"admitted={s['admitted']} served={s['served']} "
            f"shed={s['shed']} batches={s['batches']} "
            f"coalesced={s['coalesced']} replans={s['replans']}",
            f"  latency p50={s['p50_us']:.0f}us p99={s['p99_us']:.0f}us  "
            f"goodput={s['goodput_rps']:.1f} req/s  "
            f"deadline_miss={s['deadline_miss']}",
        ]
        for rec in list(self.batch_log)[-8:]:
            lines.append(
                f"  batch {rec.seq:4d} {rec.operator:>12s}[{rec.chosen}] "
                f"x{len(rec.request_ids)} widths={list(rec.widths)} "
                f"cols={rec.cols} block_d={rec.block_d} "
                f"queued={rec.queued_s * 1e6:.0f}us "
                f"exec={rec.exec_s * 1e6:.0f}us")
        return "\n".join(lines)
