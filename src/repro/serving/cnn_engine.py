"""Bucketed dynamic-batching CNN serving engine over compiled overlay
programs.

PR-2's engine ran ONE fixed batch shape: a lone request paid the full
batch-8 latency and bursts queued behind a single executable — the
utilization cliff DYNAMAP's dynamic-mapping overlay exists to avoid (§3).
This engine compiles one overlay program per *batch bucket* (powers of two
up to ``batch_size``) and schedules ticks against a per-request latency
SLO:

* each bucket's executable is lowered under the ``(signature, bucket)``
  tuning winner (``compile_plan(..., tuning_batch=bucket)``) — the binding
  measured *at that batch size*, not the batch-1 winner;
* ``step()`` picks the smallest bucket covering the queue. While the
  oldest request still has deadline budget (``slo_s`` minus the bucket's
  estimated service time), the tick *waits* to fill a larger bucket;
  once the budget is nearly spent — or the largest bucket fills — it
  dispatches, zero-padding any empty tail slots;
* with ``slo_s=None`` every tick dispatches immediately through the
  smallest covering bucket (the latency-greedy policy; also the PR-2
  compatible default).

Staging buffers sized for the largest bucket are allocated once; bucket
dispatches slice their leading rows, and only stale slots left by a
previous larger tick are re-zeroed (never the whole buffer).

Pipelined execution (``pipeline_depth >= 2``) makes the tick loop
asynchronous: ``step()`` *launches* the bucket executable (JAX dispatch
is async — the call returns an in-flight array, not a result) and
records an ``InflightTick`` instead of blocking, so the host packs tick
N+1 while the device computes tick N. Completion — ``block_until_ready``
+ unpack + ``RequestTrace`` — happens lazily: at the start of the next
``step()`` for ticks whose results are already ready, when the pipeline
is full and the oldest tick's staging buffer must be reclaimed, on an
explicit ``drain()``, or when a requester ``poll()``s for its result.
Staging rotates across ``pipeline_depth`` host buffers so the buffer a
tick was packed from is never overwritten while that tick may still be
reading it (the JAX CPU backend can alias host memory). Bucket
executables are compiled with ``donate=True`` so each tick's device
input buffer is reused across ticks instead of growing the live set.
``pipeline_depth=1`` (default) is the fully synchronous engine with
byte-for-byte identical scheduling, accounting and trace semantics.

Robustness (overload + faults) — every request ends in exactly one
``RequestOutcome``, and the four counters conserve
(``completed + rejected_full + shed_deadline + failed + pending ==
submitted``):

* **bounded admission** — ``max_queue=N`` rejects at ``submit()`` once
  the queue holds N requests (outcome ``rejected_full``) instead of
  growing without limit;
* **deadline shedding** — ``shed_deadline=True`` (with an ``slo_s``)
  drops queued requests whose deadline is already unmeetable *even by
  the cheapest bucket's measured service estimate* before they occupy a
  bucket slot (outcome ``shed_deadline``);
* **fault-injected tick retry** — a ``distributed.fault.FaultPlan``
  fails or delays planned ticks (dispatch- or completion-surfaced,
  emulating async device faults/stragglers on this CPU-only host);
  dispatch wraps in a bounded retry-with-backoff loop (``max_retries``,
  ``retry_backoff_s``) replaying from the tick's pinned staging buffer,
  and a tick that exhausts retries fails its requests cleanly (outcome
  ``failed``; pipeline slot and staging buffer reclaimed, service EMAs
  untouched, later ticks unaffected — including in-flight ticks at
  ``pipeline_depth >= 2``);
* **graceful degradation** — ``degrade=DegradeConfig(...)`` arms a
  hysteresis controller: sustained queue pressure or consecutive
  service-time spikes (``distributed.fault.robust_zscore`` over the
  recent tick history) switch the scheduler to dispatch-immediately
  smallest-bucket mode; SLO batching is restored only after the queue
  stays below the exit watermark for ``exit_ticks`` consecutive ticks.

All four knobs default OFF, in which case scheduling, outputs and
accounting are bit-for-bit the pre-robustness engine.
``stats()["robustness"]`` reports outcome counters, retries, failed
ticks, degrade transitions and the queue high-water mark either way.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from contextlib import nullcontext
from typing import Callable, Deque, Dict, List, Optional, Sequence, Set

import jax
import numpy as np

from repro.cnn.executor import compile_plan
from repro.core.algorithms import Algorithm, IM2COL
from repro.core.graph import Graph
from repro.core.mapper import ExecutionPlan
from repro.distributed.fault import DeviceFault, FaultPlan, robust_zscore
from repro.serving.spans import HostSpans

# The four terminal request outcomes (RequestTrace.outcome). Exactly one
# per submitted request; the engine's conservation invariant is
#   completed + rejected_full + shed_deadline + failed + pending
#     == submitted.
OUTCOME_COMPLETED = "completed"
OUTCOME_REJECTED = "rejected_full"
OUTCOME_SHED = "shed_deadline"
OUTCOME_FAILED = "failed"

_NO_SPAN = nullcontext()


def _no_span(name: str):
    return _NO_SPAN


def batch_buckets(max_batch: int, shard: int = 1) -> List[int]:
    """Power-of-two bucket ladder up to ``max_batch`` (inclusive — a
    non-power-of-two cap becomes the top bucket). ``shard`` > 1 builds the
    mesh-sharded ladder: every bucket is a multiple of the data-shard
    count (``shard``, ``2*shard``, ``4*shard``, ...), so each bucket's
    padded batch splits evenly across the mesh's data axes — jit input
    shardings reject uneven partitions, and a bucket a mesh cannot place
    would be a compile-time landmine. The cap itself must divide."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if shard < 1:
        raise ValueError(f"shard must be >= 1, got {shard}")
    if max_batch % shard:
        raise ValueError(
            f"max_batch {max_batch} is not a multiple of the data-shard "
            f"count {shard}; the top bucket could not be placed on the mesh")
    out = []
    b = shard
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


@dataclasses.dataclass
class CNNRequest:
    rid: int
    image: np.ndarray                  # (H, W, C)
    # Stamped at submit() (engine clock) unless the caller provides it —
    # trace replays inject virtual arrival times here.
    t_submit: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class RequestTrace:
    """Per-request lifecycle accounting (engine-clock timestamps; the
    service leg is the tick's measured wall time, so with a virtual clock
    latency still combines simulated queueing with real service time —
    the same accounting the bench replay harness uses). ``outcome`` is
    the request's terminal state: ``completed`` requests carry the full
    submit→dispatch→done timeline; ``rejected_full`` / ``shed_deadline``
    / ``failed`` records stamp the decision time into ``t_dispatch`` /
    ``t_done`` with ``service_s == 0`` (no device work was billed to
    them) and ``bucket`` the tick's bucket for failures, 0 otherwise.
    ``tick`` is the index of the tick that served or failed the request
    (the engine's dispatch index since construction or ``reset()``), None
    for requests no tick took: the ``tick``-th ``engine.stage`` and
    ``engine.launch`` spans of the engine's recorder are that tick's."""
    rid: int
    t_submit: float
    t_dispatch: float
    t_done: float
    bucket: int
    queue_s: float
    service_s: float
    latency_s: float
    slo_ok: bool
    outcome: str = OUTCOME_COMPLETED
    tick: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class DegradeConfig:
    """Hysteresis thresholds for the overload degrade mode.

    Enter when the queue reaches ``enter_queue`` (default: 3× the top
    bucket) OR the last ``straggler_patience`` completed ticks were all
    service-time spikes (``robust_zscore`` over the trailing ``window``
    tick history exceeding ``straggler_k`` — the same median/MAD
    statistic ``StragglerMonitor`` applies across hosts). While active,
    ``step()`` dispatches immediately through the smallest covering
    bucket (no SLO waiting — under sustained overload, batching up
    latency-optimal buckets only deepens the backlog). Exit after the
    queue has stayed at or below ``exit_queue`` (default: the top
    bucket) with no fresh spike for ``exit_ticks`` consecutive ticks —
    entry and exit thresholds are deliberately separated so the mode
    cannot flap around a single watermark."""
    enter_queue: Optional[int] = None
    exit_queue: Optional[int] = None
    exit_ticks: int = 3
    straggler_k: float = 4.0
    straggler_patience: int = 2
    window: int = 32


@dataclasses.dataclass
class InflightTick:
    """One dispatched-but-not-retired tick: the in-flight device output
    plus everything completion needs to unpack it and write traces. The
    staging buffer index pins which rotating host buffer this tick was
    packed from — that buffer is not reused until this tick retires.
    ``run`` pins the bucket executable the tick was dispatched on: a plan
    hot-swap between dispatch and retirement must not change what an
    in-flight tick computes, so completion-surfaced fault replays re-run
    THIS callable, never the (possibly swapped) current ladder's."""
    bucket: int
    reqs: List[CNNRequest]
    out: object                        # in-flight jax.Array
    t_dispatch: float                  # engine clock at dispatch
    t_launch_pc: float                 # perf_counter at dispatch
    t_launched_pc: float               # perf_counter after dispatch returned
    ready_at_pc: float                 # t_launch_pc + injected device delay
    buf_index: int
    tick_idx: int = 0                  # global dispatch index (FaultPlan key)
    fault: object = None               # planned TickFault for this tick
    attempt: int = 0                   # dispatch attempts already burned
    run: object = None                 # executable the tick dispatched on


class CNNServingEngine:
    """Batches single-image requests through per-bucket compiled plans.

    ``batch_size`` caps the largest bucket; ``buckets`` overrides the
    power-of-two ladder (must be ascending, e.g. ``(2, 8)`` to forbid
    singleton dispatches). ``slo_s`` is the per-request latency objective
    driving the tick scheduler; ``clock`` injects a time source (tests and
    trace replays pass a virtual clock). ``warmup=True`` runs one padded
    tick per bucket at construction, pre-compiling every executable and
    priming the per-bucket service-time estimates the scheduler uses.
    ``trace_window`` bounds the per-request ``RequestTrace`` log backing
    the ``stats()`` latency aggregates (totals and SLO-violation counters
    keep counting past the window).

    ``mesh`` (a ``jax.sharding.Mesh``, e.g. ``launch.mesh.make_data_mesh``)
    turns on data-parallel multi-chip serving: every bucket executable is
    compiled with its batch dimension sharded across the mesh's data axes
    and params replicated (placed once, at construction). The bucket
    ladder is then built in multiples of the data-shard count so every
    padded dispatch splits evenly across chips, and tuning-record lookups
    key off the *per-chip* batch (``bucket // data_shards``) — a winner
    measured at per-chip batch N on one chip is exactly the workload each
    chip runs in a sharded bucket of ``N * data_shards``, so existing
    single-device records transfer unchanged.

    ``pipeline_depth`` >= 2 turns on asynchronous, double-buffered ticks:
    up to ``pipeline_depth`` dispatches stay in flight, staging rotates
    across that many host buffers, executables donate their batched input
    (device memory reused tick to tick), and results land in ``done``
    lazily — on later ``step()`` calls, on ``drain()``, or via
    ``poll(rid)``. Depth 1 (default) is the synchronous engine unchanged.
    ``device_delay_s`` injects a per-tick device-side delay (a tick is not
    considered ready until that long after its dispatch) — a test/bench
    hook that emulates a slower real accelerator on fast-host/slow-device
    ratios CPU CI cannot otherwise produce.

    Robustness knobs (all default OFF — see the module docstring for the
    outcome/conservation model): ``max_queue`` bounds admission,
    ``shed_deadline`` drops already-hopeless queued requests,
    ``fault_plan`` injects deterministic per-tick faults/delays with
    ``max_retries`` bounded re-dispatches (``retry_backoff_s`` base
    backoff, doubling per attempt) and ``degrade`` arms the overload
    degrade controller. ``submit()`` returns the admission verdict
    (``"queued"`` or ``"rejected_full"``) and raises ``ValueError`` on a
    duplicate ``rid`` — a reused rid would silently overwrite the
    earlier result in ``done`` and corrupt ``poll()``/``drain()``
    accounting.

    ``cache`` (an ``ExecutableCache``) shares compiled bucket executables
    across engines: tenants of the multi-model engine whose graphs hash
    equal reuse one jitted program per ``(graph, plan, bucket, mesh)``
    instead of recompiling. Safe because compiled programs take params as
    call arguments (nothing model-specific is closed over); per-engine
    fault hooks wrap *outside* the cached callable.

    ``spans`` (a ``serving.spans.HostSpans``) records the host phases of
    every tick: ``engine.stage`` (packing the images into the staging
    buffer), ``engine.launch`` (the bucket executable's call, retries
    included: argument handling, the batch's host-to-device copy, enqueue),
    ``engine.block`` (the host waiting on the device, completion-fault
    replays included) and ``engine.unpack`` (the logits' device-to-host
    copy, ``done``, the service EMA and the ``RequestTrace`` records). They
    nest inside whatever span the caller wraps ``step()`` in; under
    ``pipeline_depth >= 2`` a tick's block and unpack run inside a later
    step, still in dispatch order. ``None`` (default) records nothing.
    """

    def __init__(self, graph: Graph, params, plan: Optional[ExecutionPlan],
                 batch_size: int = 8,
                 buckets: Optional[Sequence[int]] = None,
                 slo_s: Optional[float] = None,
                 default_algo: Algorithm = IM2COL,
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 dtype=np.float32,
                 epilogue: str = "bias_relu",
                 tuning=None,
                 clock: Callable[[], float] = time.monotonic,
                 warmup: bool = False,
                 trace_window: int = 2048,
                 mesh=None,
                 pipeline_depth: int = 1,
                 device_delay_s: float = 0.0,
                 max_queue: Optional[int] = None,
                 shed_deadline: bool = False,
                 fault_plan: Optional[FaultPlan] = None,
                 max_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 degrade: Optional[DegradeConfig] = None,
                 cache=None,
                 act_scales: Optional[Dict[int, float]] = None,
                 spans: Optional[HostSpans] = None) -> None:
        self.graph = graph
        self.spans = spans
        self._span = spans if spans is not None else _no_span
        self.mesh = mesh
        self.cache = cache
        # Per-layer precision map of the served plan (bf16 when the plan
        # carries none) — surfaced by stats()["precision"]; act_scales
        # feed every bucket executable's int8 layers and key the shared
        # executable cache (see compile_plan).
        self.act_scales = act_scales
        self.precisions = dict(getattr(plan, "precisions", None) or {}) \
            if plan is not None else {}
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        self.pipeline_depth = int(pipeline_depth)
        self.device_delay_s = float(device_delay_s)
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.max_queue = max_queue
        self.shed_deadline = bool(shed_deadline)
        self.fault_plan = fault_plan
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        if mesh is not None:
            from repro.distributed.sharding import (data_shard_count,
                                                    replicated)
            self.data_shards = data_shard_count(mesh)
            # Replicate params across the mesh ONCE — jit would otherwise
            # re-transfer them to every chip on every tick.
            params = jax.device_put(params, replicated(mesh))
        else:
            self.data_shards = 1
        self.params = params
        self.buckets = (sorted(set(int(b) for b in buckets)) if buckets
                        else batch_buckets(batch_size, self.data_shards))
        if self.buckets[0] < 1:
            raise ValueError(f"buckets must be >= 1, got {self.buckets}")
        bad = [b for b in self.buckets if b % self.data_shards]
        if bad:
            raise ValueError(
                f"buckets {bad} are not multiples of the mesh's data-shard "
                f"count {self.data_shards} — their padded batches could "
                "not be placed")
        self.b = self.buckets[-1]              # largest bucket (PR-2 name)
        self.slo_s = slo_s
        self.dtype = np.dtype(dtype)
        self.queue: List[CNNRequest] = []
        self.done: Dict[int, np.ndarray] = {}
        self._clock = clock
        # The graph's input node pins the only image shape the compiled
        # programs can accept — validate against it, never against traffic.
        src = graph.nodes[graph.source()]
        self._shape = tuple(int(d) for d in src.attrs["out_shape"])
        # One executable per bucket: the bucket's tuning winner (measured
        # at that batch size) binds its lowering, so executables genuinely
        # differ — this is the multi-executable cache the fixed-batch
        # engine could not have. Under a mesh, each chip runs a per-chip
        # slice of the bucket, so the tuning lookup keys off that per-chip
        # batch — the workload a chip actually executes. Pipelined engines
        # donate the batched input: ticks are re-staged from host buffers
        # every dispatch, so the device-side input buffer of tick N is
        # dead the moment N's outputs exist and XLA may reuse it.
        # Fault-plan engines thread a dispatch hook through every bucket
        # executable (fault_plan=None threads nothing — the executables
        # are the exact unhooked callables). The hook reads the
        # (tick index, attempt) context the dispatch path sets around
        # each invocation; warmup never sets one, so warmup ticks can
        # neither consume nor trip planned faults.
        self._fault_ctx: tuple = (None, 0)
        # The deployed plan plus everything needed to rebuild the ladder
        # for a DIFFERENT plan with identical compile options — the
        # hot-swap path (``compile_ladder``/``swap_plan``) recompiles with
        # exactly these, so a swapped engine differs from a fresh one only
        # in the plan.
        self.plan = plan
        self.tuning = tuning
        self._compile_kw = dict(default_algo=default_algo,
                                use_pallas=use_pallas, interpret=interpret,
                                epilogue=epilogue, tuning=tuning)
        self.plan_swaps = 0
        self.plan_rollbacks = 0
        self._runs = self.compile_ladder(plan, act_scales=act_scales,
                                         warm=False)
        # Rotating staging buffers sized for the largest bucket, allocated
        # ONCE (one per pipeline slot; the synchronous engine keeps the
        # single PR-3 buffer). _filled tracks, per buffer, how many leading
        # slots hold stale images from the tick that last used it, so only
        # slots a dispatch would leak are re-zeroed.
        self._batch_bufs = [np.zeros((self.b,) + self._shape, self.dtype)
                            for _ in range(self.pipeline_depth)]
        self._filled = [0] * self.pipeline_depth
        self._buf_cursor = 0
        # In-flight dispatches, oldest first (completion is FIFO: the
        # device executes ticks in dispatch order).
        self._inflight: Deque[InflightTick] = collections.deque()
        # Serial-device completion model: a tick's service time is its
        # completion minus max(its launch, the previous completion) — the
        # device-occupancy time, NOT the host-blocking wall time, which
        # under pipelining would double-count time spent queued behind the
        # previous tick.
        self._last_ready_pc = float("-inf")
        self._last_done = float("-inf")        # engine-clock completion
        # Overlap accounting: how much device-busy time elapsed while the
        # host was NOT blocked waiting on it (stats()["pipeline"]).
        self._overlap_s = 0.0
        self._device_busy_s = 0.0
        self._dispatched_ticks = 0
        self._completed_ticks = 0
        # Measured per-bucket service time (EMA) — the scheduler's estimate
        # of how much deadline budget a dispatch will consume.
        self._svc: Dict[int, Optional[float]] = {b: None for b in self.buckets}
        self.dispatches: Dict[int, int] = {b: 0 for b in self.buckets}
        self.last_tick: Optional[Dict[str, object]] = None
        # --- observability (ROADMAP item): per-request lifecycle records
        # in a bounded window plus running totals, surfaced by stats().
        self.request_log: Deque[RequestTrace] = \
            collections.deque(maxlen=trace_window)
        self.submitted_total = 0
        self.served_total = 0
        self.slo_violations = 0
        # --- robustness accounting (outcome conservation + retry/degrade
        # bookkeeping; all zero and inert when the knobs are off).
        self.rejected_total = 0
        self.shed_total = 0
        self.failed_total = 0
        self.retries_total = 0
        self.failed_ticks = 0
        self.queue_high_water = 0
        self.failed: Dict[int, int] = {}       # rid -> faulted tick index
        self.shed_rids: Set[int] = set()
        self._pending_rids: Set[int] = set()   # queued, not yet dispatched
        self._inflight_rids: Set[int] = set()  # dispatched, not retired
        # Global dispatch index (FaultPlan key): every tick that consumes
        # requests burns one, whether or not its launch ever succeeds —
        # fault schedules must stay aligned with the dispatch sequence.
        self._tick_seq = 0
        # --- degrade controller (armed only when a config is passed).
        self._degrade_cfg = degrade
        self._degrade_active = False
        self._degrade_entries = 0
        self._degrade_exits = 0
        self._degrade_calm = 0                 # consecutive calm ticks
        self._spikes_total = 0
        self._spike_streak = 0
        if degrade is not None:
            self._enter_q = (degrade.enter_queue
                             if degrade.enter_queue is not None
                             else 3 * self.b)
            self._exit_q = (degrade.exit_queue
                            if degrade.exit_queue is not None else self.b)
            if self._exit_q >= self._enter_q:
                raise ValueError(
                    f"degrade exit_queue {self._exit_q} must be below "
                    f"enter_queue {self._enter_q} (hysteresis)")
            self._svc_hist: Deque[float] = \
                collections.deque(maxlen=degrade.window)
        if warmup:
            self._warmup()

    @property
    def _batch_buf(self) -> np.ndarray:
        """The synchronous engine's single staging buffer (buffer 0) —
        kept as the PR-3 name for tests and tooling."""
        return self._batch_bufs[0]

    # ------------------------------------------------------------ intake
    def submit(self, req: CNNRequest) -> str:
        """Enqueue one request; returns the admission verdict —
        ``"queued"``, or ``"rejected_full"`` when ``max_queue`` is set
        and already reached (the rejection is a first-class outcome:
        counted, traced, conserved — never a silent drop). Images are
        cast to the engine dtype and validated against the graph's
        (H, W, C) input shape here, so a bad request can never crash a
        tick or drag good requests down with it; a ``rid`` already live
        anywhere in the engine (queued, in flight, completed or failed)
        raises — a reused rid would overwrite the earlier result in
        ``done`` and corrupt ``poll()``/``drain()`` accounting."""
        img = np.asarray(req.image, dtype=self.dtype)
        if img.shape != self._shape:
            raise ValueError(
                f"request {req.rid}: image shape {img.shape} != "
                f"graph input shape {self._shape}")
        if (req.rid in self._pending_rids or req.rid in self._inflight_rids
                or req.rid in self.done or req.rid in self.failed):
            raise ValueError(
                f"request {req.rid}: duplicate rid — already "
                + ("queued" if req.rid in self._pending_rids else
                   "in flight" if req.rid in self._inflight_rids else
                   "completed" if req.rid in self.done else "failed"))
        req.image = img                # persist the validated array
        if req.t_submit is None:
            req.t_submit = self._clock()
        self.submitted_total += 1
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._record_rejection(req)
        self.queue.append(req)
        self._pending_rids.add(req.rid)
        self.queue_high_water = max(self.queue_high_water, len(self.queue))
        return "queued"

    def reject(self, req: CNNRequest) -> str:
        """Externally imposed admission rejection — the multi-model
        engine's *global* queue cap lands here: the request is counted
        as submitted and rejected in THIS engine's ledger (traced,
        conserved — a cap above the engine must not break the per-tenant
        conservation invariant), without entering the queue. Like a
        ``max_queue`` rejection, the rid never entered the engine and may
        be resubmitted."""
        if req.t_submit is None:
            req.t_submit = self._clock()
        self.submitted_total += 1
        return self._record_rejection(req)

    def _record_rejection(self, req: CNNRequest) -> str:
        """Stamp one rejection into the ledger (counter + trace): the
        shared tail of ``submit()``'s bounded-admission path and the
        external ``reject()`` path."""
        self.rejected_total += 1
        self.request_log.append(RequestTrace(
            rid=req.rid, t_submit=req.t_submit,
            t_dispatch=req.t_submit, t_done=req.t_submit,
            bucket=0, queue_s=0.0, service_s=0.0, latency_s=0.0,
            slo_ok=False, outcome=OUTCOME_REJECTED))
        return OUTCOME_REJECTED

    # --------------------------------------------------------- scheduling
    def covering_bucket(self, n: int) -> int:
        """Smallest bucket holding ``n`` requests (the largest bucket for
        any overflow — excess requests wait for the next tick)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.b

    def service_estimate(self, bucket: int) -> float:
        """Expected service time of one ``bucket`` dispatch. Unmeasured
        buckets borrow the largest measured smaller bucket's time (a lower
        bound — batched ticks only get slower), else 0: the scheduler then
        waits the full SLO before dispatching, which is the conservative
        larger-batch-favoring choice."""
        est = self._svc.get(bucket)
        if est is not None:
            return est
        known = [b for b in self._svc
                 if self._svc[b] is not None and b < bucket]
        return self._svc[max(known)] if known else 0.0

    def next_dispatch_at(self) -> Optional[float]:
        """Engine-clock time at which ``step()`` will dispatch without new
        arrivals — None when the queue is empty. Trace replays and serving
        loops use this as the next tick wake-up."""
        if not self.queue:
            return None
        oldest = self.queue[0]
        assert oldest.t_submit is not None
        if (self.slo_s is None or self._degrade_active
                or len(self.queue) >= self.b):
            return oldest.t_submit          # dispatch immediately
        bucket = self.covering_bucket(len(self.queue))
        wait = max(0.0, self.slo_s - self.service_estimate(bucket))
        return oldest.t_submit + wait

    def oldest_deadline(self) -> Optional[float]:
        """Deadline of the oldest queued request (``t_submit + slo_s``, or
        bare ``t_submit`` with no SLO) — None when the queue is empty. The
        multi-model scheduler orders due tenants by this: earliest
        deadline across models dispatches first."""
        if not self.queue:
            return None
        oldest = self.queue[0]
        assert oldest.t_submit is not None
        if self.slo_s is None:
            return oldest.t_submit
        return oldest.t_submit + self.slo_s

    def dispatch_due(self, now: float) -> bool:
        """True when ``step(now)`` would dispatch rather than wait: a full
        largest bucket, active degrade mode (batching for latency is
        pointless under overload), or the SLO wait budget of the oldest
        request is spent. The per-model policy predicate the joint
        multi-model scheduler consults without mutating engine state."""
        if not self.queue:
            return False
        if len(self.queue) >= self.b or self._degrade_active:
            return True
        at = self.next_dispatch_at()
        return at is None or now >= at

    # ------------------------------------------------------------- serve
    def step(self, now: Optional[float] = None, flush: bool = False) -> int:
        """One engine tick. Picks the smallest bucket covering the queue;
        under an SLO it *waits* (returns 0) while the oldest request still
        has deadline budget to fill a larger bucket, and dispatches early
        once that budget is nearly spent — ``flush=True`` dispatches
        unconditionally (drain/shutdown). Returns the number dispatched.

        Synchronous (depth 1) the dispatch blocks and results are in
        ``done`` on return; pipelined, the tick is launched asynchronously
        and retires lazily (any already-ready older ticks retire here
        first, and the oldest is force-retired when the pipeline is
        full). A tick whose planned fault exhausts ``max_retries`` still
        returns its batch size — its requests were consumed (outcome
        ``failed``), not left queued.

        Structured as housekeeping → wait policy (``dispatch_due``) →
        ``_dispatch_tick``; the multi-model engine reuses the same pieces
        but ranks tenants between the policy check and the dispatch."""
        if self._inflight:
            self._reap()                    # lazy completion of ready ticks
        if self._degrade_cfg is not None:
            self._degrade_update()
        if not self.queue:
            return 0
        if now is None:
            now = self._clock()
        if self.shed_deadline and self.slo_s is not None:
            self._shed_hopeless(now)
            if not self.queue:
                return 0
        if not flush and not self.dispatch_due(now):
            return 0                        # wait to fill a larger bucket
        return self._dispatch_tick(now)

    def _dispatch_tick(self, now: float) -> int:
        """The tick core: carve the covering bucket off the queue, stage,
        launch (with fault retry), and either complete synchronously or
        enqueue the in-flight tick. Callers are responsible for the wait
        policy — this always dispatches."""
        bucket = self.covering_bucket(len(self.queue))
        batch, self.queue = self.queue[:bucket], self.queue[bucket:]
        for req in batch:
            self._pending_rids.discard(req.rid)
            self._inflight_rids.add(req.rid)
        if len(self._inflight) >= self.pipeline_depth:
            # Pipeline full: the next staging buffer still belongs to the
            # oldest in-flight tick — retire it (blocking) to reclaim.
            self._complete(self._inflight.popleft())
        x = self._stage(batch)
        tick_idx = self._tick_seq
        self._tick_seq += 1
        fault = (self.fault_plan.get(tick_idx)
                 if self.fault_plan is not None else None)
        t_launch = time.perf_counter()
        out, attempt = self._launch(bucket, x, tick_idx, fault)
        t_launched = time.perf_counter()
        tick = InflightTick(bucket=bucket, reqs=batch, out=out,
                            t_dispatch=now, t_launch_pc=t_launch,
                            t_launched_pc=t_launched,
                            ready_at_pc=(t_launch + self.device_delay_s
                                         + (fault.delay_s if fault else 0.0)),
                            buf_index=self._last_buf_index,
                            tick_idx=tick_idx, fault=fault, attempt=attempt,
                            run=self._runs[bucket])
        if out is None:
            # Launch retries exhausted: fail cleanly — requests get their
            # terminal outcome, the staging buffer is simply left to the
            # normal stale-slot reclaim, and no pipeline slot was taken.
            self._fail_tick(tick)
            return len(batch)
        self.dispatches[bucket] += 1
        self._dispatched_ticks += 1
        if self.pipeline_depth == 1:
            self._complete(tick)            # synchronous: block right here
        else:
            self._inflight.append(tick)
        return len(batch)

    def _launch(self, bucket: int, x: np.ndarray, tick_idx: int,
                fault) -> tuple:
        """Invoke the bucket executable under the fault context, retrying
        dispatch-surfaced ``DeviceFault``s with bounded backoff. Returns
        ``(in-flight output, attempts burned)`` — ``(None, n)`` when
        retries are exhausted. Completion-surfaced faults never raise
        here; ``_complete`` replays them from the pinned staging
        buffer."""
        attempt = 0
        with self._span("engine.launch"):
            while True:
                try:
                    self._fault_ctx = (tick_idx, attempt)
                    return (self._runs[bucket](self.params, x[:bucket]),
                            attempt)
                except DeviceFault:
                    if attempt >= self.max_retries:
                        return None, attempt
                    self.retries_total += 1
                    self._backoff_sleep(attempt)
                    attempt += 1
                finally:
                    self._fault_ctx = (None, 0)

    def _fault_hook(self) -> None:
        """Per-invocation dispatch hook threaded through ``compile_plan``
        when a ``fault_plan`` is armed: raises for planned
        dispatch-surfaced failures of the current (tick, attempt)
        context. Delays do NOT sleep here — they ride ``ready_at_pc`` so
        a straggling device never blocks the dispatching host."""
        tick_idx, attempt = self._fault_ctx
        fault = self.fault_plan.get(tick_idx)
        if (fault is not None and fault.at_dispatch
                and attempt < fault.failures):
            raise DeviceFault(
                f"injected dispatch fault: tick {tick_idx} "
                f"attempt {attempt}")

    def _backoff_sleep(self, attempt: int) -> None:
        """Exponential backoff between retry attempts (base doubles per
        burned attempt; base 0.0 retries immediately)."""
        delay = self.retry_backoff_s * (2 ** attempt)
        if delay > 0:
            time.sleep(delay)

    def _shed_hopeless(self, now: float) -> None:
        """Drop queued requests whose SLO is already unmeetable even by
        an immediate smallest-bucket dispatch (the cheapest measured
        service estimate) — hopeless work must not occupy a bucket slot
        that a still-meetable request could use. Conservative by
        construction: with no measured estimate yet (0.0) nothing is
        ever shed."""
        floor = self.service_estimate(self.buckets[0])
        if floor <= 0.0:
            return
        keep: List[CNNRequest] = []
        for req in self.queue:
            assert req.t_submit is not None
            if (now - req.t_submit) + floor > self.slo_s:
                self.shed_total += 1
                self.shed_rids.add(req.rid)
                self._pending_rids.discard(req.rid)
                queue_s = max(0.0, now - req.t_submit)
                self.request_log.append(RequestTrace(
                    rid=req.rid, t_submit=req.t_submit, t_dispatch=now,
                    t_done=now, bucket=0, queue_s=queue_s, service_s=0.0,
                    latency_s=queue_s, slo_ok=False, outcome=OUTCOME_SHED))
            else:
                keep.append(req)
        if len(keep) != len(self.queue):
            self.queue = keep

    def _degrade_update(self) -> None:
        """Advance the degrade hysteresis one tick: enter on queue
        pressure or a sustained straggler-spike streak; exit only after
        ``exit_ticks`` consecutive calm ticks at or below the exit
        watermark."""
        cfg = self._degrade_cfg
        q = len(self.queue)
        if not self._degrade_active:
            if (q >= self._enter_q
                    or self._spike_streak >= cfg.straggler_patience):
                self._degrade_active = True
                self._degrade_entries += 1
                self._degrade_calm = 0
        else:
            if q <= self._exit_q and self._spike_streak == 0:
                self._degrade_calm += 1
                if self._degrade_calm >= cfg.exit_ticks:
                    self._degrade_active = False
                    self._degrade_exits += 1
                    self._degrade_calm = 0
            else:
                self._degrade_calm = 0

    # --------------------------------------------------- staging buffers
    def _stage(self, batch: List[CNNRequest]) -> np.ndarray:
        """Pack ``batch`` into the next rotating staging buffer, zeroing
        only slots still holding images a *previous* tick staged there — a
        smaller bucket after a larger one must not leak stale images into
        its padded tail. Rotation guarantees the buffer's previous tick
        has already retired (pipeline depth == buffer count)."""
        with self._span("engine.stage"):
            idx = self._buf_cursor
            self._buf_cursor = (idx + 1) % len(self._batch_bufs)
            self._last_buf_index = idx
            x = self._batch_bufs[idx]
            for i, req in enumerate(batch):
                x[i] = req.image
            if self._filled[idx] > len(batch):
                x[len(batch):self._filled[idx]] = 0
            self._filled[idx] = len(batch)
            return x

    # ------------------------------------------------------- completion
    def _reap(self) -> None:
        """Retire in-flight ticks whose results are already ready, without
        blocking (completion is FIFO — the device runs ticks in dispatch
        order, so a ready head implies nothing about later ticks)."""
        while self._inflight:
            head = self._inflight[0]
            if time.perf_counter() < head.ready_at_pc:
                break
            is_ready = getattr(head.out, "is_ready", None)
            if is_ready is None or not is_ready():
                break
            self._complete(self._inflight.popleft())

    def _complete(self, tick: InflightTick) -> None:
        """Blocking completion of one tick: wait for the device, unpack
        results into ``done``, update the bucket's service EMA from the
        *device-completion* time, and write ``RequestTrace`` records.
        Planned completion-surfaced faults are discovered here — the
        async result turns out bad when blocked on — and replayed from
        the tick's pinned staging buffer under the bounded retry budget;
        exhaustion fails the tick cleanly (slot and buffer reclaimed,
        EMAs untouched, later in-flight ticks unaffected)."""
        t_block = time.perf_counter()
        with self._span("engine.block"):
            out = jax.block_until_ready(tick.out)
            remaining = tick.ready_at_pc - time.perf_counter()
            if remaining > 0:
                time.sleep(remaining)       # emulated device still busy
            fault = tick.fault
            if fault is not None and not fault.at_dispatch:
                while tick.attempt < fault.failures:
                    if tick.attempt >= self.max_retries:
                        self._fail_tick(tick)
                        return
                    self.retries_total += 1
                    self._backoff_sleep(tick.attempt)
                    tick.attempt += 1
                    # Replay from the pinned staging buffer — rotation
                    # guarantees it still holds exactly this tick's images
                    # — on the tick's pinned executable: a hot-swap between
                    # dispatch and this replay must not change the math.
                    x = self._batch_bufs[tick.buf_index]
                    run = tick.run if tick.run is not None \
                        else self._runs[tick.bucket]
                    try:
                        self._fault_ctx = (tick.tick_idx, tick.attempt)
                        tick.out = run(self.params, x[:tick.bucket])
                    finally:
                        self._fault_ctx = (None, 0)
                    out = jax.block_until_ready(tick.out)
            t_ready = time.perf_counter()
        with self._span("engine.unpack"):
            self._unpack(tick, out, t_block, t_ready)

    def _unpack(self, tick: InflightTick, out, t_block: float,
                t_ready: float) -> None:
        """The host side of a tick's completion once its result is ready:
        service and overlap accounting, the logits' copy to the host into
        ``done``, and the ``RequestTrace`` records."""
        # Serial-device occupancy: this tick could only start once the
        # previous one finished, so its service time is completion minus
        # max(launch, previous completion) — under pipelining the naive
        # (completion - launch) would fold queueing behind older ticks
        # into the EMA and wreck the scheduler's deadline budgets.
        start = max(tick.t_launch_pc, self._last_ready_pc)
        service = max(t_ready - start, 1e-9)
        self._last_ready_pc = t_ready
        # Overlap = the part of this tick's device time that elapsed
        # between its dispatch call *returning* and the host blocking on
        # the result — i.e. device time during which the host was free to
        # pack/dispatch other ticks. Synchronous ticks block immediately
        # after dispatch, so their overlap is ~0; the dispatch call
        # itself (tracing, transfer) is host work and never counts.
        free_from = max(tick.t_launched_pc, start)
        self._overlap_s += min(max(t_block - free_from, 0.0), service)
        self._device_busy_s += service
        self._completed_ticks += 1
        arr = np.asarray(out)
        for i, req in enumerate(tick.reqs):
            self.done[req.rid] = arr[i]
            self._inflight_rids.discard(req.rid)
        prev = self._svc[tick.bucket]
        self._svc[tick.bucket] = (service if prev is None
                                  else 0.5 * prev + 0.5 * service)
        self.served_total += len(tick.reqs)
        if self._degrade_cfg is not None:
            self._observe_service(service)
        # Engine-clock completion: pipelined ticks finish no earlier than
        # the previous tick's completion (the serial device again), which
        # keeps t_done monotone across out-of-order drains. The
        # synchronous engine keeps the PR-4 stamp (dispatch + wall).
        if self.pipeline_depth > 1:
            t_done = max(tick.t_dispatch, self._last_done) + service
        else:
            t_done = tick.t_dispatch + service
        self._last_done = t_done
        for req in tick.reqs:
            assert req.t_submit is not None
            queue_s = max(0.0, tick.t_dispatch - req.t_submit)
            latency_s = queue_s + (t_done - tick.t_dispatch)
            slo_ok = self.slo_s is None or latency_s <= self.slo_s
            if not slo_ok:
                self.slo_violations += 1
            self.request_log.append(RequestTrace(
                rid=req.rid, t_submit=req.t_submit,
                t_dispatch=tick.t_dispatch, t_done=t_done,
                bucket=tick.bucket, queue_s=queue_s, service_s=service,
                latency_s=latency_s, slo_ok=slo_ok, tick=tick.tick_idx))
        self.last_tick = {"bucket": tick.bucket, "served": len(tick.reqs),
                          "wall_s": service, "now": tick.t_dispatch,
                          "per_chip_batch": tick.bucket // self.data_shards}

    def _observe_service(self, service: float) -> None:
        """Feed one completed tick's service time to the degrade
        controller's spike detector: robust z-score against the trailing
        history (``distributed.fault.robust_zscore`` — median/MAD, the
        ``StragglerMonitor`` statistic), streak-counted so only
        *consecutive* spikes trip the degrade entry."""
        cfg = self._degrade_cfg
        if len(self._svc_hist) >= 5:
            if robust_zscore(service, self._svc_hist) > cfg.straggler_k:
                self._spikes_total += 1
                self._spike_streak += 1
            else:
                self._spike_streak = 0
        self._svc_hist.append(service)

    def _fail_tick(self, tick: InflightTick) -> None:
        """Terminal failure of one tick after its retry budget is spent:
        every request gets outcome ``failed`` (traced, counted,
        conserved), the pipeline slot and staging buffer return to the
        pool, and — deliberately — the bucket's service EMA and the
        degrade spike history are NOT updated: a failed tick produced no
        service-time measurement, and polluting the scheduler's deadline
        budgets with fault wall time would punish the requests that
        follow."""
        self.failed_ticks += 1
        wall = max(time.perf_counter() - tick.t_launch_pc, 1e-9)
        if tick.out is not None:
            # The device was genuinely occupied by the doomed attempts:
            # later ticks' serial-device service accounting must not
            # back-date their start to before this tick ended.
            self._last_ready_pc = max(self._last_ready_pc,
                                      time.perf_counter())
        t_done = tick.t_dispatch
        for req in tick.reqs:
            self._inflight_rids.discard(req.rid)
            self.failed[req.rid] = tick.tick_idx
            assert req.t_submit is not None
            queue_s = max(0.0, tick.t_dispatch - req.t_submit)
            self.request_log.append(RequestTrace(
                rid=req.rid, t_submit=req.t_submit,
                t_dispatch=tick.t_dispatch, t_done=t_done,
                bucket=tick.bucket, queue_s=queue_s, service_s=0.0,
                latency_s=queue_s, slo_ok=False, outcome=OUTCOME_FAILED,
                tick=tick.tick_idx))
        self.failed_total += len(tick.reqs)
        self.last_tick = {"bucket": tick.bucket, "served": 0,
                          "wall_s": wall, "now": tick.t_dispatch,
                          "per_chip_batch": tick.bucket // self.data_shards,
                          "failed": True}

    def drain(self) -> Dict[int, np.ndarray]:
        """Retire every in-flight tick (blocking, in dispatch order) so
        ``done`` holds all dispatched results. No-op when synchronous or
        idle; never dispatches — pair with ``step(flush=True)`` /
        ``run_until_done()`` to also empty the queue."""
        while self._inflight:
            self._complete(self._inflight.popleft())
        return self.done

    def poll(self, rid: int) -> Optional[np.ndarray]:
        """Requester-side completion: the result for ``rid`` if its tick
        has retired, retiring in-flight ticks (oldest first) until that
        tick retires. ``None`` — with NO side effects — when ``rid`` is
        not in flight: never submitted, still queued, rejected, shed, or
        failed. (An unknown rid must not drain the pipeline as a side
        effect; only a rid genuinely riding an in-flight tick forces
        retirement, and only up to its own tick.)"""
        if rid in self.done:
            return self.done[rid]
        while rid in self._inflight_rids and self._inflight:
            self._complete(self._inflight.popleft())
        return self.done.get(rid)

    def reset(self) -> None:
        """Drop queued/served request state and observability counters
        (trace replays reuse one warmed engine across traces). In-flight
        ticks are retired first (their measurements still update the
        EMAs). Compiled executables, the staging buffers and the measured
        service-time estimates are kept — resetting never forgets what
        the device taught us."""
        self.drain()
        self.queue.clear()
        self.done.clear()
        self.dispatches = {b: 0 for b in self.buckets}
        self.last_tick = None
        self.request_log.clear()
        self.submitted_total = 0
        self.served_total = 0
        self.slo_violations = 0
        self._last_done = float("-inf")
        self._overlap_s = 0.0
        self._device_busy_s = 0.0
        self._dispatched_ticks = 0
        self._completed_ticks = 0
        # Robustness accounting resets with the request state; measured
        # knowledge (service EMAs, degrade spike history) is kept, and
        # the degrade mode itself stands down — a fresh trace starts
        # from the normal scheduling policy.
        self.rejected_total = 0
        self.shed_total = 0
        self.failed_total = 0
        self.retries_total = 0
        self.failed_ticks = 0
        self.queue_high_water = 0
        self.failed.clear()
        self.shed_rids.clear()
        self._pending_rids.clear()
        self._inflight_rids.clear()
        self._degrade_active = False
        self._degrade_entries = 0
        self._degrade_exits = 0
        self._degrade_calm = 0
        self._spikes_total = 0
        self._spike_streak = 0
        # Fault plans are keyed by dispatch index: replays that reset the
        # engine between traces expect the plan to re-apply from tick 0.
        self._tick_seq = 0

    # ------------------------------------------------------ observability
    def stats(self) -> Dict[str, object]:
        """Snapshot of the engine's request accounting: totals, per-bucket
        dispatch counts and service EMAs, SLO-violation count, latency /
        queue-wait aggregates over the bounded ``request_log`` window
        (submit→dispatch→done timestamps live in the individual
        ``RequestTrace`` records), and the pipeline's in-flight/overlap
        counters. Pure read — never mutates state (in particular it never
        retires in-flight ticks; ``served`` counts *completed* requests,
        dispatched-but-inflight ones appear under ``pipeline``)."""
        def _agg(vals: List[float]) -> Optional[Dict[str, float]]:
            if not vals:
                return None
            arr = np.asarray(vals)
            return {"mean_ms": float(arr.mean()) * 1e3,
                    "p50_ms": float(np.percentile(arr, 50)) * 1e3,
                    "p99_ms": float(np.percentile(arr, 99)) * 1e3,
                    "max_ms": float(arr.max()) * 1e3}

        # Latency/queue aggregates describe COMPLETED requests only —
        # rejected/shed/failed records carry no service leg and would
        # drag the percentiles toward their (zero-cost) decision times.
        window = [t for t in self.request_log
                  if t.outcome == OUTCOME_COMPLETED]
        return {
            "submitted": self.submitted_total,
            "served": self.served_total,
            "queued": len(self.queue),
            "slo_s": self.slo_s,
            "slo_violations": self.slo_violations,
            "dispatches": dict(self.dispatches),
            # Service EMAs are device-completion times under the serial-
            # device model (completion minus max(launch, previous
            # completion)) — NOT host-blocking wall time, so SLO deadline
            # budgets stay correct when ticks retire lazily under
            # pipelining.
            "service_ema_s": {b: s for b, s in self._svc.items()
                              if s is not None},
            "window": len(window),
            "latency": _agg([t.latency_s for t in window]),
            "queue_wait": _agg([t.queue_s for t in window]),
            "pipeline": {
                "depth": self.pipeline_depth,
                "inflight": len(self._inflight),
                "dispatched_ticks": self._dispatched_ticks,
                "completed_ticks": self._completed_ticks,
                "device_busy_s": self._device_busy_s,
                "overlap_s": self._overlap_s,
                # Fraction of device-busy time that elapsed while the host
                # was free to pack/dispatch other ticks: ~0 synchronous,
                # → 1 when packing fully hides behind device compute.
                "overlap_ratio": (self._overlap_s / self._device_busy_s
                                  if self._device_busy_s > 0 else 0.0),
            },
            # Sharded dispatch accounting: how each bucket splits across
            # the mesh (None = single-device engine). Service EMAs above
            # are wall times of the *sharded* dispatch — the scheduler's
            # deadline budgets automatically reflect multi-chip speed.
            "sharding": None if self.mesh is None else {
                "data_shards": self.data_shards,
                "mesh_devices": int(self.mesh.size),
                "per_chip_batch": {b: b // self.data_shards
                                   for b in self.buckets},
            },
            # Deployment history of the served plan: how many times the
            # ladder was hot-swapped (supervisor adoptions) and rolled
            # back. Counters survive reset() — deployment events are
            # engine-lifetime history, not per-trace request accounting.
            "plan": {
                "swaps": self.plan_swaps,
                "rollbacks": self.plan_rollbacks,
            },
            # Per-layer precision mix of the served plan: conv layer
            # counts per precision plus the int8 layer ids — the
            # operator-facing audit of what the quantization gate kept.
            "precision": {
                "mix": {
                    "int8": sum(1 for p in self.precisions.values()
                                if p == "int8"),
                    "bf16": (sum(1 for p in self.precisions.values()
                                 if p != "int8")
                             + sum(1 for n in self.graph.conv_nodes()
                                   if n.id not in self.precisions)),
                },
                "int8_layers": sorted(
                    n for n, p in self.precisions.items() if p == "int8"),
                "calibrated": self.act_scales is not None,
            },
            # Overload/fault accounting. Every submitted request is
            # conserved across the four terminal outcomes plus the
            # not-yet-terminal pending set (queued + riding an in-flight
            # tick): outcomes sum + pending == submitted, always.
            "robustness": {
                "max_queue": self.max_queue,
                "shed_deadline": self.shed_deadline,
                "outcomes": {
                    OUTCOME_COMPLETED: self.served_total,
                    OUTCOME_REJECTED: self.rejected_total,
                    OUTCOME_SHED: self.shed_total,
                    OUTCOME_FAILED: self.failed_total,
                },
                "pending": (len(self.queue)
                            + sum(len(t.reqs) for t in self._inflight)),
                "retries": self.retries_total,
                "failed_ticks": self.failed_ticks,
                "queue_high_water": self.queue_high_water,
                "degrade": {
                    "enabled": self._degrade_cfg is not None,
                    "active": self._degrade_active,
                    "entries": self._degrade_entries,
                    "exits": self._degrade_exits,
                    "straggler_spikes": self._spikes_total,
                },
            },
        }

    def run_until_done(self, max_ticks: int = 1000) -> Dict[int, np.ndarray]:
        """Drain the queue, ignoring SLO waits (shutdown/offline replay),
        then retire every in-flight tick."""
        for _ in range(max_ticks):
            if self.step(flush=True) == 0:
                break
        return self.drain()

    # ----------------------------------------------------- plan hot-swap
    def compile_ladder(self, plan: Optional[ExecutionPlan],
                       act_scales: Optional[Dict[int, float]] = None,
                       warm: bool = True) -> Dict[int, Callable]:
        """Compile one bucket ladder for ``plan`` under this engine's
        compile options (backend, epilogue, tuning record, mesh, donation,
        fault hook, shared cache) — the same call the constructor makes,
        so a ladder compiled here and swapped in is indistinguishable from
        constructing a fresh engine on ``plan``. Pure with respect to
        engine state: safe to call from a background thread (the shared
        ``ExecutableCache`` serializes concurrent compiles internally) and
        hand the result to ``swap_plan`` on the serving thread.

        ``warm=True`` invokes each executable once on an all-zeros batch
        (result discarded) so the JIT trace is paid here — on the compile
        thread — rather than by the first post-swap serving tick, whose
        wall time feeds the service EMAs and the supervisor's probation
        check."""
        hook = self._fault_hook if self.fault_plan is not None else None
        runs = {
            bucket: compile_plan(self.graph, plan,
                                 tuning_batch=bucket // self.data_shards,
                                 mesh=self.mesh,
                                 donate=self.pipeline_depth > 1,
                                 fault_hook=hook, cache=self.cache,
                                 act_scales=act_scales,
                                 **self._compile_kw)
            for bucket in self.buckets
        }
        if warm:
            for bucket, run in runs.items():
                x = np.zeros((bucket,) + self._shape, self.dtype)
                jax.block_until_ready(run(self.params, x))
        return runs

    def swap_plan(self, plan: Optional[ExecutionPlan],
                  runs: Optional[Dict[int, Callable]] = None, *,
                  act_scales: Optional[Dict[int, float]] = None,
                  rollback: bool = False) -> tuple:
        """Atomically deploy a new plan between ticks.

        Replaces the bucket ladder (``runs``, or compiled here via
        ``compile_ladder`` when None) plus the plan-derived state
        (``plan``/``precisions``/``act_scales``) in one step on the
        serving thread — the engine is single-threaded, so "atomic" means
        no tick can observe a half-swapped ladder: every dispatch before
        this call ran entirely on the old ladder, every one after runs
        entirely on the new.

        Everything else is deliberately preserved: the outcome ledger
        (conservation holds across the swap — a swap is not a request
        outcome), queued requests, in-flight ticks (each pinned its
        executable at dispatch and retires against the OLD ladder, fault
        replays included), and the per-bucket service EMAs (they are the
        scheduler's only deadline estimate; the 0.5/0.5 EMA re-converges
        on the new plan within a few ticks, and the supervisor snapshots
        pre-swap values for its regression check).

        Returns ``(old_plan, old_runs, old_act_scales)`` so the caller can
        re-arm the previous deployment (``rollback=True`` books the swap
        under the rollback counter instead)."""
        if runs is None:
            runs = self.compile_ladder(plan, act_scales=act_scales)
        missing = [b for b in self.buckets if b not in runs]
        if missing:
            raise ValueError(
                f"swap_plan ladder is missing buckets {missing} — a "
                "partial ladder would strand those buckets on the old "
                "plan; compile via compile_ladder(plan)")
        old = (self.plan, self._runs, self.act_scales)
        self.plan = plan
        self._runs = {b: runs[b] for b in self.buckets}
        self.act_scales = act_scales
        self.precisions = dict(getattr(plan, "precisions", None) or {}) \
            if plan is not None else {}
        if rollback:
            self.plan_rollbacks += 1
        else:
            self.plan_swaps += 1
        return old

    # ------------------------------------------------------------ warmup
    def _warmup(self) -> None:
        """Compile every bucket's executable and prime service estimates
        by timing two all-zeros dispatches per bucket — the first pays
        compilation, the second's wall time is the steady-state estimate
        (results discarded; the injected device delay is excluded so the
        estimate stays the raw device time)."""
        for bucket in self.buckets:
            x = np.zeros((bucket,) + self._shape, self.dtype)
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(self._runs[bucket](self.params, x))
                wall = time.perf_counter() - t0
            self._svc[bucket] = wall
