"""The pluggable execution layer: one task grid, one drain loop, four
scheduling policies.

A ``WorkRequest`` is the compiled form of one estimation request: the task
grid, the fused arrays (targets, training weights), one or more
``Segment``s (contiguous learner groups — mixed-learner grids such as IRM
carry one segment per distinct learner), and a durable ``TaskLedger``.

Execution goes through the **megabatch compiler** (repro/compile): the
union of every pending request's tasks is bucketed by (learner family,
padded N, padded P), stacked into ``(B, N_pad, P_pad)`` tensors with
validity masks, and run by one jitted program per bucket (Pallas
batched_gram / batched_predict on the hot linear path).  Equal-shape
canonical blocks — even from different requests — **fuse into one
launch** (compile/program.py, bitwise-equal to per-block launches), and
launches are **dispatched non-blocking**: the compiler hands back
in-flight ``jax.Array`` handles which each drain stream queues
(serverless/dispatch.py) and harvests only when a ledger's buckets must
complete, so host-side booking overlaps device execution.

Every backend is a **stream scheduler** on one loop,
``_StreamBackend.step``, over a live ``DrainState`` whose request set can
grow between steps (continuous admission from the session layer;
``run_requests`` is the batch wrapper: admit everything, step until
idle).  A step books whatever landed on any dispatch queue, hedges
overdue buckets, opens matured retry gates, and asks the backend's
policy — its ``_dispatch_next`` hook — for the next dispatch; when the
policy has nothing to dispatch, the step drains the in-flight tail.  The
policies:

  WaveBackend     the serverless-analogue wave scheduler (paper §4):
                  capacity-limited waves filled from the pending view,
                  sized by ``n_workers`` or the occupancy autoscaler
                  (serverless/autoscale.py), pipelined up to
                  ``pipeline_depth`` unsettled waves.  Waves are SHARED
                  across requests — a wave's lanes map onto bucket
                  slices, so one warm program serves every task of a
                  bucket regardless of which request it came from.
  InlineBackend   the first pending bucket group per step, one direct
                  program call on one device — the reference scheduler
                  tests compare against.
  ShardedBackend  the same bucket groups with the programs shard_map'd
                  over the mesh's "data" axis
                  (sharding/policy.py::megabatch_specs), pages
                  replicated; fused launches shard too, and each
                  bucket's parallelization axis is roofline-planned.
  TopologyBackend (serverless/topology.py) per-host-mesh dispatch
                  queues: units routed to the host whose PagePool
                  already holds their pages, idle hosts steal, one host
                  wave per step round-robin, each sized by its host's
                  autoscaler lane.

One booking function, ``_StreamBackend._book``, books every harvest on
every backend with Lambda semantics: identity-keyed fault verdicts
(serverless/chaos.py), straggler slowdown, simulated durations, the
``timeout_s`` cap, backoff-gated retries, and a retry-budget abort that
first books every sibling success.  Hedged re-dispatch, axis planning
and wave sizing likewise have one home each.  So all backends emit the
same ``RunReport``/``TaskLedger`` artifacts, and fault tolerance,
billing, and resume behave identically at the API layer; each holds a
persistent spec-keyed ``ProgramCache`` so repeat traffic through a
``DMLSession`` never re-traces, and a device-resident ``PagePool`` so
steady-state serving re-transfers no feature pages.

Determinism contract: every task draws its PRNG stream as
fold_in(segment seed, flat task id) at *compile* time, so predictions are
independent of backend, bucket composition, wave schedule, admission
order, fault pattern, and shard count — bitwise, for every learner family
including the key-consuming ones (mlp, kernel_ridge).
"""
from __future__ import annotations

import functools
import hashlib
import time
from dataclasses import dataclass, field
from typing import (
    TYPE_CHECKING, Callable, Dict, List, Optional, Protocol, Sequence, Tuple,
)

import jax
import numpy as np

from repro import obs
from repro.analysis.registry import warm_cache
from repro.runtime import bounded_put
from repro.serverless import sanitize
from repro.serverless.autoscale import AutoscaleDecision, OccupancyAutoscaler
from repro.serverless.chaos import chaos_plan
from repro.serverless.cost import Bill, BillingRecord, speedup_of
from repro.serverless.dispatch import (
    DispatchQueue, DispatchStats, HedgePair, PendingBucket,
)
from repro.serverless.ledger import DONE, TaskLedger

if TYPE_CHECKING:       # avoid the core <-> serverless import cycle
    from repro.compile import (
        CompileStats, MegabatchPlan, PagePool, PageStats, ProgramCache,
    )
    from repro.core.crossfit import TaskGrid


def _compile():
    """Deferred import of the megabatch compiler.

    repro.compile reaches into repro.core.crossfit whose package __init__
    imports this module (spec.py needs BACKEND_NAMES), so the compiler
    must load lazily — at which point the cycle is already resolved.
    """
    import repro.compile as compile_mod
    return compile_mod


@jax.jit
def _fold_key_table(base_key, ids):
    """(n,) task ids -> (n, key_width) key data via per-id fold_in."""
    return jax.vmap(
        lambda i: jax.random.key_data(jax.random.fold_in(base_key, i)))(ids)


@jax.jit
def _seed_key_table(seed, ids):
    """``_fold_key_table`` of ``jax.random.key(seed)`` in one call."""
    return _fold_key_table(jax.random.key(seed), ids)


@functools.lru_cache(maxsize=None)
def _host_device() -> jax.Device:
    """Where key tables build: the host's CPU device, since a table is
    read on the host; the default device where no CPU backend is up."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return jax.local_devices()[0]


def _build_key_table(base_key, seed: Optional[int],
                     n_tasks: int) -> Tuple[np.ndarray, str]:
    """fold_in(base key, t) key data for every task t, and the platform
    that built it.  A seed-keyed table (``base_key`` None) builds on
    ``_host_device`` from the seed in one jitted call; the seed goes in
    as an int64, which the call converts as ``jax.random.key(seed)``
    converts it, so the bits are those of the key itself.  A caller's
    key builds where the key lives."""
    ids = np.arange(n_tasks)
    if base_key is not None:
        dev = next(iter(base_key.devices()))
        return np.asarray(_fold_key_table(base_key, ids)), dev.platform
    dev = _host_device()
    with jax.default_device(dev):
        table = _seed_key_table(np.int64(seed), ids)
    return np.asarray(table), dev.platform


# Cache of computed key tables: steady serving re-compiles the same
# (plan, data) into fresh WorkRequests every drain, and the fold_in
# table is a pure function of (segment key, n_tasks).  Seed-keyed
# segments are looked up by their ``key_ref`` ("seed", s); a caller's
# key by its data.  Bounded FIFO: serving mixes cycle a small set of
# segment keys.
_KEY_TABLE_CACHE: Dict[Tuple, np.ndarray] = {}
_KEY_TABLE_CACHE_MAX = 512
# structural cache of WorkRequest index maps (see _index_maps)
_INDEX_MAP_CACHE: Dict[Tuple, Tuple] = {}


@warm_cache(name="fold_in_key_tables",
            key=("base_key", "n_tasks", "key_ref"))
def _segment_key_table(base_key, n_tasks: int,
                       key_ref: Optional[Tuple] = None) -> np.ndarray:
    """A segment's key table, from its key or, with ``base_key`` None,
    from the seed its ``key_ref`` ("seed", s) names."""
    if key_ref is not None:
        ck = ("ref", key_ref, int(n_tasks))
    else:
        kd = np.asarray(jax.random.key_data(base_key))
        ck = (kd.tobytes(), kd.shape, int(n_tasks))
    table = _KEY_TABLE_CACHE.get(ck)
    if table is None:
        seed = None if base_key is not None else int(key_ref[1])
        with obs.span("keys.table", tasks=int(n_tasks)) as sp:
            table, platform = _build_key_table(base_key, seed, n_tasks)
            sp.set(platform=platform)
        bounded_put(_KEY_TABLE_CACHE, ck, table, _KEY_TABLE_CACHE_MAX)
    return table


# ---------------------------------------------------------------------------
# substrate configuration (immutable — plans/sessions share PoolConfigs)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PoolConfig:
    """The knobs the paper's user controls (§4.2, §5.2).

    Frozen: reusing one PoolConfig across estimators/sessions must never
    let one caller's settings leak into another's (use
    ``dataclasses.replace`` to derive variants).
    """
    n_workers: int = 8                  # concurrent lambda-analogue workers
    memory_mb: int = 1024               # Lambda memory knob
    scaling: str = "n_rep"              # paper's scaling parameter
    timeout_s: float = 900.0            # Lambda 15-min cap
    max_retries: int = 3
    failure_rate: float = 0.0           # fault injection (per invocation)
    straggler_rate: float = 0.0         # P(invocation is a straggler)
    straggler_slowdown: float = 4.0
    simulate: bool = False              # model durations via the speed curve
    base_work_s: float = 0.0            # simulated seconds per task @1 vCPU
    dispatch_overhead_s: float = 0.005  # per-wave dispatch latency
    seed: int = 0
    checkpoint_path: Optional[str] = None
    # occupancy-driven autoscaling (serverless/autoscale.py): derive the
    # per-wave worker count from queue depth / bucket occupancy / padding
    # waste, priced through the Lambda cost model
    autoscale: bool = False
    min_workers: int = 1
    max_workers: int = 64
    autoscale_cost_weight: float = 1.0
    # device-resident feature-page pool budget (compile/pages.py); 0 turns
    # the pool off and falls back to host page stacking per launch
    page_pool_bytes: int = 256 * 1024 * 1024
    # topology backend (serverless/topology.py): number of simulated host
    # meshes when none is passed explicitly, and whether an idle host may
    # steal queued buckets from a loaded one
    n_hosts: int = 2
    steal: bool = True
    # same-shape block fusion (compile/program.py): pack equal-canonical-B
    # blocks of different requests into one launch (bitwise-equal to
    # per-block launches).  Since ISSUE 8 partitioned program caches fuse
    # too: the sharded backends carry a partition_fused transform that
    # wraps the lax.map fused body in shard_map over the host mesh
    fuse: bool = True
    # non-blocking dispatch: buckets a drain stream may hold in flight
    # before a push force-harvests the oldest (device-liveness bound)
    max_inflight: int = 8
    # cross-shape coalescing (compile/program.py, ISSUE 7): pack/morph
    # tail blocks of bitwise-proven families (MORPH_BITWISE_FAMILIES)
    # into combined launches
    coalesce: bool = True
    # double-buffered dispatch (ISSUE 7): waves a drain may hold
    # unsettled while filling/stacking the next one (wave k+1's host
    # work overlaps wave k's device execution).  Since ISSUE 10 chaos
    # pools pipeline too: fault verdicts are drawn per invocation
    # identity (serverless/chaos.py), not from an order-pinned stream
    pipeline_depth: int = 2
    # fault-tolerant drain (ISSUE 10): capped exponential backoff before
    # a failed invocation is re-dispatched (0 retries immediately — the
    # in-process default, where re-dispatch is the recovery)
    retry_backoff_s: float = 0.0
    retry_backoff_cap_s: float = 0.25
    # synthetic straggler long tail: a bucket carrying a straggler
    # verdict reports not-ready for this long after its launches land,
    # so the deadline/hedge machinery has a real tail to cut (0: off)
    straggler_hold_s: float = 0.0
    # hedged re-dispatch: None arms hedging exactly when a fault plan is
    # active (chaos pools / REPRO_CHAOS); True/False force it.  An
    # overdue in-flight bucket gets a duplicate dispatch — on another
    # host under the topology backend — and first-landing wins
    hedge: Optional[bool] = None
    # fixed overdue threshold override; None derives the deadline from
    # the bucket's roofline (launch/roofline.py::bucket_deadline_s),
    # capped by timeout_s
    hedge_after_s: Optional[float] = None

    def lanes_per_worker(self) -> int:
        """Worker 'memory' buys lane width (DESIGN.md §2 mapping)."""
        return max(1, self.memory_mb // 256)


@dataclass
class RunReport:
    fit_time_s: float = 0.0
    response_time_s: float = 0.0
    waves: int = 0
    bill: Bill = field(default_factory=Bill)
    wave_sizes: List[int] = field(default_factory=list)
    failures: int = 0
    stragglers: int = 0

    def summary(self) -> Dict:
        out = {"fit_time_s": self.fit_time_s,
               "response_time_s": self.response_time_s,
               "waves": self.waves, "failures": self.failures,
               "stragglers": self.stragglers}
        out.update(self.bill.summary())
        return out


# ---------------------------------------------------------------------------
# the unit of execution
# ---------------------------------------------------------------------------
@dataclass
class Segment:
    """A learner-uniform slice of a request's grid.

    ``l_ids`` are the nuisance indices this segment owns; its invocations
    are exactly those with ``inv % L in l_ids`` (both scaling levels place
    l in the low digit of the invocation id).

    ``learner``/``params`` name a registry learner with compile-time
    resolved hyperparameters — the megabatch compiler buckets on them and
    resolves the family's ``batched_fit_predict``.  ``learner_fn`` is the
    legacy opaque-callable path (ServerlessExecutor): such segments run
    through the vmap adapter at exact shapes.  ``cache_key`` is the hashable
    spec identity — requests built from equal specs share warm compiled
    programs; when absent, buckets fall back to object identity.

    The segment's PRNG key seeds its tasks: task t draws fold_in(key, t),
    fixed at compile time so no schedule can perturb the estimate.  A
    plan segment holds no key: ``key`` is None and ``key_ref`` ("seed",
    s) names ``jax.random.key(s)``, so its key table builds from the seed
    on the host.  A caller's ``key`` (raw requests) has ``key_ref`` None
    and keys the table cache by its data.
    """
    learner_fn: Optional[Callable] = None
    l_ids: Tuple[int, ...] = ()
    key: Optional[jax.Array] = None
    cache_key: Optional[Tuple] = None
    learner: Optional[str] = None
    params: Tuple = ()
    key_ref: Optional[Tuple] = None

    @property
    def bucket_id(self):
        """Value identity when the spec is known, object identity else."""
        if self.cache_key is not None:
            return self.cache_key
        return ("opaque", id(self.learner_fn))


def fingerprint_array(x) -> Tuple[str, Tuple[int, ...]]:
    """Content identity of a feature matrix — the ``PagePool`` key, so two
    requests over equal data share one device-resident page."""
    arr = np.ascontiguousarray(np.asarray(x, np.float32))
    return (hashlib.sha1(arr.tobytes()).hexdigest(), arr.shape)


@dataclass
class WorkRequest:
    """One estimation request, compiled to arrays + a durable ledger."""
    grid: TaskGrid
    scaling: str                        # invocation granularity (§4.2)
    x: np.ndarray                       # (N, P) float32, on the host
    targets: np.ndarray                 # (L, N)
    train_w: np.ndarray                 # (M, K, L, N)
    segments: List[Segment]
    ledger: TaskLedger
    report: RunReport
    tag: object = None                  # caller's request id
    fold_masks: Optional[np.ndarray] = None   # (M,K,N), set by the compiler
    data_key: object = None             # content identity of x (page pool)
    # content identity of (targets, train_w, segment keys): when set by
    # the front-end (compile_request), the compiler may cache this
    # request's stacked block tensors across drains — steady serving
    # re-lowers identical (plan, data) pairs every round.  None (raw
    # requests) disables the cache.
    work_key: object = None

    @classmethod
    def create(cls, grid: TaskGrid, scaling: str, x, targets, train_w,
               segments: List[Segment],
               ledger: Optional[TaskLedger] = None,
               report: Optional[RunReport] = None,
               tag: object = None, data_key: object = None,
               work_key: object = None) -> "WorkRequest":
        n_obs = int(np.asarray(targets).shape[-1])
        n_inv = grid.n_invocations(scaling)
        tpi = grid.tasks_per_invocation(scaling)
        if ledger is None:
            ledger = TaskLedger.create(n_inv, n_obs, tpi)
        elif (ledger.n_invocations, ledger.tasks_per_invocation,
              ledger.n_obs) != (n_inv, tpi, n_obs):
            raise ValueError(
                f"ledger shape ({ledger.n_invocations}, "
                f"{ledger.tasks_per_invocation}, {ledger.n_obs}) does not "
                f"match grid/scaling/data ({n_inv}, {tpi}, {n_obs}) — was it "
                "saved under a different plan?")
        # X stays on the host, a copy taken at admission (a caller may
        # reuse its buffer): its one upload is the launch's page
        x = np.array(x, np.float32, order="C")
        if data_key is None:
            data_key = fingerprint_array(x)
        return cls(grid=grid, scaling=scaling, x=x,
                   targets=np.asarray(targets), train_w=np.asarray(train_w),
                   segments=segments, ledger=ledger,
                   report=report or RunReport(), tag=tag, data_key=data_key,
                   work_key=work_key)

    # ---- derived index maps (cached) ------------------------------------
    # the grid's coordinate methods are pure functions of its scalar
    # shape fields (all keyed) — hence covers under grid.n_rep; the
    # per-instance memo self._maps is ambient
    @warm_cache(name="work_request_index_maps",
                key=("self.grid.n_rep", "self.grid.n_folds",
                     "self.grid.n_nuisance", "self.scaling",
                     "self.segments"),
                reads=("self.grid.invocation_task_ids",
                       "self.grid.task_coords",
                       "self.grid.n_invocations"),
                covers={"self.grid.n_rep": (
                    "self.grid.invocation_task_ids",
                    "self.grid.task_coords",
                    "self.grid.n_invocations")},
                ambient=("self._maps",))
    def _index_maps(self):
        if not hasattr(self, "_maps"):
            g = self.grid
            # structural cache: the maps depend only on (grid, scaling,
            # segment l_ids) — steady serving re-creates equal-structure
            # requests every drain and shares one entry
            ck = (g.n_rep, g.n_folds, g.n_nuisance, self.scaling,
                  tuple(s.l_ids for s in self.segments))
            maps = _INDEX_MAP_CACHE.get(ck)
            if maps is None:
                task_mat = g.invocation_task_ids(
                    np.arange(g.n_invocations(self.scaling)), self.scaling)
                tm, tk, tl = g.task_coords()
                seg_of_l = np.zeros(g.n_nuisance, np.int64)
                for si, seg in enumerate(self.segments):
                    for l in seg.l_ids:
                        seg_of_l[l] = si
                maps = (task_mat, tm, tk, tl, seg_of_l)
                bounded_put(_INDEX_MAP_CACHE, ck, maps, 512)
            self._maps = maps
        return self._maps

    def segment_of_inv(self, inv: np.ndarray) -> np.ndarray:
        _, _, _, _, seg_of_l = self._index_maps()
        return seg_of_l[np.asarray(inv) % self.grid.n_nuisance]

    def invocation_tasks(self, inv: int) -> np.ndarray:
        """Flat task ids of one invocation (tpi,)."""
        return self._index_maps()[0][int(inv)]

    def task_key_data(self, seg_idx: int, flat_tasks: np.ndarray) -> np.ndarray:
        """Per-task PRNG key data: fold_in(segment key, flat task id).

        Fixed at compile time and cached per segment, so a task's stream
        is identical however buckets, waves, retries, or shards slice the
        grid — the determinism contract for key-consuming learners.
        """
        if not hasattr(self, "_key_tables"):
            self._key_tables: Dict[int, np.ndarray] = {}
        table = self._key_tables.get(seg_idx)
        if table is None:
            seg = self.segments[seg_idx]
            table = _segment_key_table(seg.key, self.grid.n_tasks,
                                       key_ref=seg.key_ref)
            self._key_tables[seg_idx] = table
        return table[np.asarray(flat_tasks, np.int64)]

    def wave_arrays(self, flat_tasks: np.ndarray):
        """Gather (targets, weights) rows for flat task ids."""
        _, tm, tk, tl = self._index_maps()[:4]
        y = self.targets[tl[flat_tasks]]
        w = self.train_w[tm[flat_tasks], tk[flat_tasks], tl[flat_tasks]]
        return y, w

    def gathered_preds(self) -> np.ndarray:
        """Scatter ledger rows back to the (M, K, L, N) tensor."""
        g = self.grid
        task_mat, tm, tk, tl, _ = self._index_maps()
        flat = task_mat.reshape(-1)
        n_obs = self.ledger.n_obs
        out = np.zeros((g.n_rep, g.n_folds, g.n_nuisance, n_obs), np.float32)
        out[tm[flat], tk[flat], tl[flat]] = \
            self.ledger.preds.reshape(-1, n_obs)
        return out


class ExecutionBackend(Protocol):
    """Anything that can drain a stream of WorkRequests.

    The streaming contract is three primitives: ``begin_drain()`` opens a
    ``DrainState``; ``admit(state, req)`` lowers one request into the live
    bucket plan (legal at any point, including mid-drain); ``step(state)``
    performs one scheduling quantum — a wave (WaveBackend), one bucket
    slice (Inline/Sharded) or one host wave (Topology) — books
    ledgers/billing, and returns False once nothing is pending.  ``run_requests`` is the batch wrapper: after it
    returns, every request's ledger is complete (or an exception was
    raised), its report reflects the work performed in this call
    (appending to any prior state), and ``req.gathered_preds()`` yields
    the (M, K, L, N) prediction tensor.  Pre-completed ledger rows
    (resume) must not be re-executed.
    """
    name: str

    def begin_drain(self) -> "DrainState":
        ...

    def admit(self, state: "DrainState", req: WorkRequest) -> int:
        ...

    def step(self, state: "DrainState") -> bool:
        ...

    def run_requests(self, requests: Sequence[WorkRequest]) -> "BackendRunInfo":
        ...


@dataclass
class BackendRunInfo:
    """Cross-request accounting for one backend drain (session telemetry)."""
    backend: str
    waves: int = 0
    wave_members: List[List[object]] = field(default_factory=list)
    buckets: int = 0                    # distinct megabatch buckets drained
    compile: Optional[CompileStats] = None   # backend's warm-cache stats
    pages: Optional[PageStats] = None        # device page-pool accounting
    autoscale: List[AutoscaleDecision] = field(default_factory=list)
    topology: Optional[object] = None   # per-host streams (TopologyInfo)
    dispatch: Optional[DispatchStats] = None  # in-flight queue accounting
    # per-bucket parallelization-axis decisions (ISSUE 8): one
    # compile.buckets.AxisDecision per (bucket, mesh) the drain priced,
    # logged like autoscale decisions
    axis_plans: List[object] = field(default_factory=list)

    @property
    def shared_waves(self) -> int:
        """Waves that carried invocations from 2+ requests — the fusion
        the multi-request session exists to create.  (Members lists are
        deduplicated at construction.)"""
        return sum(1 for m in self.wave_members if len(m) > 1)


@dataclass
class DrainState:
    """Mutable state of one continuous drain.

    Owns the incremental ``MegabatchPlan`` (its request list is the
    admission order), the pool's fault plan (``chaos``,
    serverless/chaos.py — None for fault-free pools, whose hot path
    then pays nothing), the retry-backoff gates, one in-flight dispatch
    queue per stream (``queues``: key -1 on a single-stream backend, a
    host id per live host on the topology backend; every queue feeds
    ``info.dispatch``), and the cross-request ``BackendRunInfo``.  The
    session layer holds one of these per live drain and interleaves
    ``admit`` with ``step``.
    """
    plan: "MegabatchPlan"
    info: BackendRunInfo
    chaos: Optional[object] = None      # serverless/chaos.py::ChaosPlan
    # (req slot, invocation) -> perf_counter time before which a failed
    # row may not be re-dispatched (capped exponential backoff)
    retry_at: Dict[Tuple[int, int], float] = field(default_factory=dict)
    seen_buckets: set = field(default_factory=set)
    finalized: set = field(default_factory=set)
    queues: Dict[int, DispatchQueue] = field(default_factory=dict)
    # round-robin position over ``queues``: the topology's host waves
    # and the tail drain both start from it
    cursor: int = 0
    # pipelined waves dispatched but not yet settled (WaveBackend): each
    # settles — books ledgers, bills, finalizes — when its last bucket
    # lands; the sanitizer requires this empty at drain retirement
    waves_inflight: List = field(default_factory=list)
    # (bucket key, n_devices) -> AxisDecision memo: each bucket's
    # parallelization axis is priced once per drain per mesh size
    # (ISSUE 8); the decisions are also appended to info.axis_plans
    axis_planned: Dict = field(default_factory=dict)

    @property
    def requests(self) -> List[WorkRequest]:
        return self.plan.requests

    def in_flight_entries(self) -> set:
        """Every dispatched entry not yet booked: on a queue, or in a
        pipelined wave that has not settled — a harvested bucket leaves
        its queue before its wave books it, and re-dispatching its rows
        would double-book them."""
        out: set = set()
        for q in self.queues.values():
            q.in_flight_entries(out)
        for latch in self.waves_inflight:
            out.update(latch.entries)
        return out

    def mark_running(self, entries) -> None:
        """Flag dispatched (slot, invocation) entries RUNNING in their
        ledgers and take them out of the plan's pending view."""
        for ri, invs in _by_request(entries).items():
            self.requests[ri].ledger.mark_running(invs)
            self.plan.pending.take(ri, invs)


# ---------------------------------------------------------------------------
# helpers shared by backends
# ---------------------------------------------------------------------------
def _by_request(entries) -> Dict[int, List[int]]:
    """(slot, invocation) entries grouped by slot, in first-seen order."""
    out: Dict[int, List[int]] = {}
    for ri, inv in entries:
        out.setdefault(ri, []).append(inv)
    return out


def _tasks_per_invocation(req: WorkRequest) -> int:
    return req.grid.tasks_per_invocation(req.scaling)


def roofline_pending_inv_s(requests, groups) -> Optional[float]:
    """Mean roofline-modeled invocation duration over bucketed pending
    entries (launch/roofline.py) — the autoscaler's cold-start pricing
    signal, replacing the unit-work model before any duration has been
    observed.  Opaque-callable buckets carry no analytic model and are
    skipped; returns None when nothing could be priced."""
    from repro.compile.program import bucket_family
    from repro.launch.roofline import invocation_roofline_s
    total, n = 0.0, 0
    for key, entries in groups.items():
        learner = bucket_family(key)
        if learner is None:
            continue
        for ri, _ in entries:
            total += invocation_roofline_s(
                learner, dict(key.learner[1]),
                _tasks_per_invocation(requests[ri]),
                key.n_pad, key.p_pad,
                # the whole bucket typically rides one fused launch, so
                # each invocation carries an amortized share of its
                # dispatch overhead (launch/roofline.launch_overhead_s —
                # session-measured, constant fallback)
                amortized_launches=1.0 / len(entries))
            n += 1
    return total / n if n else None


def traced_step(step):
    """Run the drain's ``step`` inside its ``backend.step`` span: the
    waves so far, the requests the live drain holds, and the buckets the
    step dispatched."""
    @functools.wraps(step)
    def traced(self, state: DrainState) -> bool:
        with obs.span("backend.step", wave=state.info.waves,
                      drain_requests=len(state.requests)) as sp:
            stats = state.info.dispatch
            before = stats.dispatched
            progressed = step(self, state)
            sp.set(buckets=stats.dispatched - before)
            return progressed
    return traced


class _StreamBackend:
    """The one drain loop and everything the backends share: drain-state
    lifecycle, admission, booking, hedging, retry gates, axis planning,
    wave sizing, completion finalization, checkpoints, and the batch
    wrapper.  A backend supplies its scheduling policy as
    ``_dispatch_next``."""

    _state_cls = DrainState
    autoscaler = None           # the wave and topology backends arm one

    @property
    def _programs(self) -> Dict:
        return self.compiler._programs

    def _streams(self) -> Sequence[int]:
        """The dispatch streams a drain opens a queue for (-1: one)."""
        return (-1,)

    def begin_drain(self) -> DrainState:
        info = BackendRunInfo(backend=self.name, dispatch=DispatchStats())
        info.compile = self.compiler.stats
        if self.pages is not None:
            info.pages = self.pages.stats
        state = self._state_cls(plan=_compile().MegabatchPlan(), info=info,
                                chaos=chaos_plan(self.pool))
        state.queues = {h: DispatchQueue(self.pool.max_inflight,
                                         stats=info.dispatch)
                        for h in self._streams()}
        return state

    def _dispatch_opts(self) -> Dict:
        """The launch-scheduling knobs every dispatch_bucket call takes:
        same-shape fusion (which the chosen cache gates,
        ``ProgramCache.fuses``) and cross-shape coalescing."""
        return {"fuse": self.pool.fuse, "coalesce": self.pool.coalesce}

    def _dispatch_kwargs(self, state: DrainState, key, entries) -> Dict:
        """Per-bucket dispatch_bucket kwargs of this backend's own
        program cache (the sharded backend's alignment, axis decision
        and mesh); a hedge leg replicates them."""
        return {}

    def admit(self, state: DrainState, req: WorkRequest) -> int:
        """Lower one request into the live plan.  The admission slot is
        the request's identity in the drain's fault plan
        (serverless/chaos.py): verdicts are drawn per
        (slot, invocation, attempt), so no schedule — bucket-coherent
        fill, pipelining, hedges, host loss, resume — can perturb the
        fault pattern."""
        with obs.span("planner.admit", rid=req.tag):
            ri = state.plan.admit(req)
            self._finalize_request(state, ri)   # resumed-complete ledgers
        return ri

    def run_requests(self, requests: Sequence[WorkRequest]) -> BackendRunInfo:
        state = self.begin_drain()
        for req in requests:
            self.admit(state, req)
        while self.step(state):
            pass
        self._finish(state)
        return state.info

    # ---- the drain loop ------------------------------------------------
    @traced_step
    def step(self, state: DrainState) -> bool:
        """One scheduling quantum; False once nothing is pending or in
        flight.

        Books whatever landed on any stream (non-blocking), hedges
        overdue buckets, opens matured retry gates, and asks the
        backend's policy for its next dispatch.  When the policy has
        nothing to dispatch, drains the in-flight tail: with hedging
        armed it polls, since a blocking harvest would sleep out a held
        straggler's hold — the very tail its hedged duplicate exists to
        beat; else it blocks on the first stream from ``cursor`` that
        holds work; failing that, it waits out the earliest retry gate
        instead of spinning."""
        queues = state.queues
        for q in queues.values():
            q.harvest_ready()
        self._maybe_hedge(state)
        gate_wait = self._open_gates(state)
        if self._dispatch_next(state):
            return True
        if self._hedge_armed(state) \
                and any(not q.empty for q in queues.values()):
            self._maybe_hedge(state)
            if not sum(q.harvest_ready() for q in queues.values()):
                time.sleep(0.001)
            return True
        ids = list(queues)
        for off in range(len(ids)):
            i = (state.cursor + off) % len(ids)
            if queues[ids[i]].harvest_next():
                state.cursor = (i + 1) % len(ids)
                return True
        if gate_wait is not None:
            time.sleep(min(gate_wait, 0.05))
            return True
        return False

    def _dispatch_next(self, state: DrainState) -> bool:
        """The backend's policy: dispatch its next unit of work (a bucket
        slice, a wave, a host wave) without waiting and return True, or
        return False when nothing is dispatchable."""
        raise NotImplementedError

    def _push_bucket(self, state: DrainState, host: int, bd, book) -> None:
        """Wrap one dispatched bucket with its fault-tolerance context
        (deadline, straggler hold) and enqueue it on stream ``host``."""
        hold = self._hold_for(state, bd.entries)
        pb = PendingBucket(
            dispatch=bd, host=host,
            deadline_s=self._deadline_for(state, bd.key, bd.entries),
            not_ready_before=(time.perf_counter() + hold) if hold else 0.0)
        state.queues[host].push(pb, book)

    # ---- shared decisions ------------------------------------------------
    def _scaler(self, host: int) -> Optional[OccupancyAutoscaler]:
        """The occupancy autoscaler of stream ``host``, None if unarmed."""
        return self.autoscaler

    def _wave_capacity(self, state: DrainState, host: int, load) -> int:
        """Lanes of the next wave on stream ``host``: the stream's
        occupancy autoscaler prices the wave when the pool arms one, else
        the pool's ``n_workers`` do.  ``load()`` gives the stream's
        dispatchable invocations, their tasks, and a thunk of them by
        bucket (cold-start roofline pricing); only a decision calls it."""
        pool = self.pool
        scaler = self._scaler(host)
        workers = pool.n_workers
        if scaler is not None:
            depth, tasks, groups = load()
            decision = scaler.decide(
                depth, tasks_per_invocation=max(1, tasks // max(depth, 1)),
                padding_waste=self.compiler.stats.padding.waste_frac,
                # dispatched-but-unharvested work on this stream is
                # occupancy, not depth — never provisioned for twice
                in_flight=state.queues[host].in_flight,
                roofline_inv_s=lambda: roofline_pending_inv_s(
                    state.requests, groups()))
            state.info.autoscale.append(decision)
            workers = decision.n_workers
        return max(1, workers * pool.lanes_per_worker())

    def _plan_axis(self, state: DrainState, key, entries, n_devices: int):
        """Price the bucket's parallelization-axis candidates on an
        ``n_devices`` mesh once per drain, log the decision on
        ``info.axis_plans``, and return it (memoized) so the dispatch
        executes the planned layout (ISSUE 8/9)."""
        memo_key = (key, n_devices)
        if memo_key not in state.axis_planned:
            from repro.compile.buckets import plan_bucket_axis
            decision = plan_bucket_axis(key, n_tasks=len(entries),
                                        n_devices=n_devices)
            state.axis_planned[memo_key] = decision
            if decision is not None:
                state.info.axis_plans.append(decision)
        return state.axis_planned[memo_key]

    # ---- booking ---------------------------------------------------------
    def _book(self, state: DrainState, ri: int, invs: List[int],
              results: Dict, wall: float) -> Optional[int]:
        """Book one request's share of a harvest: ledger rows, bills,
        fault verdicts, retries.  ``wall`` is the measured wall billed
        to these invocations; predictions were already computed by the
        bucket launches (``results``), so chaos can only reorder or
        repeat work, never change an estimate.

        A fault-free pool consults nothing and batch-books (no draws, no
        per-invocation loop) unless the wall tripped the timeout cap.
        Otherwise each invocation gets its verdict from the drain's
        identity-keyed fault plan (serverless/chaos.py) — legal in any
        booking order, which is what lets chaos pools ride the pipelined
        bucket-coherent fill at all: simulated or measured duration,
        times ``straggler_slowdown`` for a straggler, failed when the
        verdict says so or the duration passes ``timeout_s``.  A failure
        arms a backoff gate in ``state.retry_at``; a row already DONE
        (a re-dispatch lost the race) is skipped.  Returns the
        invocation whose retry budget ran out, if any, instead of
        raising, so the caller books every sibling first."""
        pool = self.pool
        req = state.requests[ri]
        ledger, report = req.ledger, req.report
        per = wall / max(len(invs), 1)
        report.wave_sizes.append(len(invs))
        report.waves += 1
        plan = state.chaos
        if plan is None and per <= pool.timeout_s:
            sanitize.check_booking(ledger, invs, "record_successes")
            ledger.record_successes(
                invs, np.stack([results[(ri, inv)] for inv in invs]))
            for inv in invs:
                report.bill.add(BillingRecord(
                    invocation=int(inv), duration_s=per,
                    memory_mb=pool.memory_mb))
            return None
        if plan is None:
            durs = np.full(len(invs), per)
            failed = durs > pool.timeout_s                # lambda cap
        else:
            atts = ledger.attempts[np.asarray(invs, np.int64)]
            verdicts = [plan.verdict(ri, int(inv), int(att))
                        for inv, att in zip(invs, atts)]
            if pool.simulate:
                base = pool.base_work_s * _tasks_per_invocation(req) \
                    / speedup_of(pool.memory_mb)
                durs = base * np.array([v.noise for v in verdicts])
            else:
                durs = np.full(len(invs), per)
            is_strag = np.array([v.straggler for v in verdicts], bool)
            durs = np.where(is_strag, durs * pool.straggler_slowdown, durs)
            report.stragglers += int(is_strag.sum())
            # injected failures fire on attempt 0 only (retries converge)
            failed = np.array([v.failed for v in verdicts], bool)
            failed |= durs > pool.timeout_s               # lambda cap
        now = time.perf_counter()
        exhausted = None
        for i, inv in enumerate(invs):
            if ledger.status[inv] == DONE:   # a re-dispatch lost the race
                continue
            if failed[i]:
                if ledger.attempts[inv] >= pool.max_retries:
                    exhausted = int(inv)
                    continue
                sanitize.check_booking(ledger, inv, "record_failure")
                ledger.record_failure(inv)
                report.failures += 1
                if plan is not None:
                    state.retry_at[(ri, int(inv))] = \
                        now + plan.backoff_s(int(ledger.attempts[inv]))
                continue
            sanitize.check_booking(ledger, inv, "record_success")
            ledger.record_success(int(inv), results[(ri, inv)])
            report.bill.add(BillingRecord(
                invocation=int(inv), duration_s=float(durs[i]),
                memory_mb=pool.memory_mb,
                retry=int(ledger.attempts[inv])))
        if pool.simulate:
            # response time = slowest invocation in flight this wave
            report.response_time_s += float(np.max(durs)) \
                + pool.dispatch_overhead_s
        return exhausted

    def _settle(self, state: DrainState, host: int, by_req: Dict,
                results: Dict, walls: Dict, waited: Optional[float] = None):
        """Book each request's share of one harvest (``walls[ri]``, its
        billed wall), feed stream ``host``'s autoscaler the mean wall of
        an invocation, charge each request ``waited`` (else its share)
        as response and fit time, finalize what completed, and
        checkpoint what was booked.  A spent retry budget aborts the
        drain only once every request's successes are booked: completed
        work is durable, an abort never discards it."""
        touched = sorted(by_req)
        spent = [inv for inv in [self._book(state, ri, by_req[ri], results,
                                            walls[ri]) for ri in touched]
                 if inv is not None]
        if spent:
            raise RuntimeError(f"invocation {spent[0]} exceeded retry budget")
        scaler = self._scaler(host)
        if scaler is not None:
            scaler.observe(sum(walls[ri] for ri in touched)
                           / sum(len(invs) for invs in by_req.values()))
        requests = state.requests
        for ri in touched:
            wall = walls[ri] if waited is None else waited
            report = requests[ri].report
            report.response_time_s += wall
            report.fit_time_s += wall
            self._finalize_request(state, ri)
        if self.pages is not None:
            state.info.pages = self.pages.stats
        self._checkpoint(state, touched)

    def _book_harvest(self, state: DrainState, pb: PendingBucket,
                      results: Dict, elapsed: float):
        """Booking continuation of one bucket slice (inline, sharded,
        topology): each request is billed its lanes' share of the
        bucket's elapsed wall and charged the whole of it as response
        time, since it waited for the bucket to land."""
        by_req = _by_request(pb.entries)
        per = elapsed / max(len(pb.entries), 1)
        self._note_members(state, by_req)
        self._settle(state, pb.host, by_req, results,
                     {ri: per * len(invs) for ri, invs in by_req.items()},
                     waited=elapsed)

    @staticmethod
    def _note_members(state: DrainState, ris) -> None:
        """Log one wave's member list: its requests' tags (the slot when
        untagged), deduplicated, in first-seen order."""
        requests = state.requests
        members = []
        for ri in ris:
            tag = requests[ri].tag
            tag = ri if tag is None else tag
            if tag not in members:
                members.append(tag)
        state.info.wave_members.append(members)

    # ------------------------------------------------------------------
    def _finish(self, state: DrainState):
        sanitize.check_drained(state, "backend finish")
        for ri in range(len(state.requests)):
            self._finalize_request(state, ri)

    def _finalize_request(self, state: DrainState, ri: int):
        """Close out one request's report the moment its ledger completes
        (the early-result hook the session's event loop polls)."""
        if ri in state.finalized:
            return
        req = state.requests[ri]
        if not req.ledger.complete:
            return
        state.finalized.add(ri)
        state.plan.pending.retire(ri)
        if self.pool.simulate:
            req.report.fit_time_s = (req.report.response_time_s
                                     + self.pool.dispatch_overhead_s)

    def _checkpoint(self, state: DrainState, booked):
        """Persist the ledgers of the ``booked`` slots, the only ones a
        booking changed (durable sessions bind each ledger a path;
        ``pool.checkpoint_path`` writes one file per request)."""
        path = self.pool.checkpoint_path
        for ri in booked:
            ledger = state.requests[ri].ledger
            ledger.checkpoint()
            if path:
                ledger.save(path if len(state.requests) == 1
                            else f"{path}.r{ri}")

    # ---- fault-tolerant dispatch (ISSUE 10) --------------------------
    def _hedge_armed(self, state: DrainState) -> bool:
        """Hedged re-dispatch is on when the pool says so, else exactly
        when a fault plan is active (chaos is what makes tails long)."""
        if self.pool.hedge is not None:
            return self.pool.hedge
        return state.chaos is not None

    def _deadline_for(self, state: DrainState, bkey,
                      entries) -> Optional[float]:
        """Overdue threshold for one dispatched bucket slice: the pool's
        fixed override, else the roofline-derived deadline capped by
        timeout_s.  None disarms hedging for this bucket."""
        if not self._hedge_armed(state) or not entries:
            return None
        pool = self.pool
        if pool.hedge_after_s is not None:
            return pool.hedge_after_s
        from repro.compile.program import bucket_family
        from repro.launch.roofline import bucket_deadline_s
        learner = bucket_family(bkey)
        if learner is None:
            # no analytic model: the Lambda cap is the only deadline
            return pool.timeout_s
        d = bucket_deadline_s(learner, dict(bkey.learner[1]),
                              _tasks_per_invocation(
                                  state.requests[entries[0][0]]),
                              bkey.n_pad, bkey.p_pad, len(entries),
                              n_workers=len(entries))
        return min(d, pool.timeout_s)

    def _hold_for(self, state: DrainState, entries) -> float:
        """Synthetic straggler tail: when the pool opts in
        (straggler_hold_s > 0) and any entry of the slice draws a
        straggler verdict, the bucket reports not-ready for the hold —
        the long tail a hedged duplicate then beats."""
        plan = state.chaos
        hold = self.pool.straggler_hold_s
        if plan is None or hold <= 0:
            return 0.0
        for ri, inv in entries:
            att = int(state.requests[ri].ledger.attempts[inv])
            if plan.verdict(ri, inv, att).straggler:
                return hold
        return 0.0

    def _hedge_dispatch(self, state: DrainState, key, entries,
                        host: int) -> Tuple[object, object, Dict]:
        """(program cache, page pool, dispatch kwargs) the duplicate leg
        of an overdue bucket runs with on stream ``host``: the same
        compiled program as the original leg, so the race is bitwise."""
        return self.compiler, self.pages, \
            self._dispatch_kwargs(state, key, entries)

    def _maybe_hedge(self, state: DrainState) -> int:
        """Duplicate-dispatch every overdue in-flight bucket onto the
        least-loaded *other* live stream — its own when it is the only
        one; returns the number hedged."""
        if not self._hedge_armed(state):
            return 0
        queues = state.queues
        n = 0
        for host, q in queues.items():
            for pb in q.overdue():
                others = [h for h in queues if h != host] or [host]
                self._hedge_bucket(
                    state, pb, q,
                    min(others, key=lambda h: queues[h].in_flight))
                n += 1
        return n

    def _hedge_bucket(self, state: DrainState, pb: PendingBucket,
                      owner_q: DispatchQueue, host: int) -> None:
        """Launch the duplicate leg of an overdue bucket on stream
        ``host`` and wire the race: same key, same entries, same
        per-task fold_in keys — so whichever leg lands first books
        bitwise-identical results.  The winner's harvest settles the
        pair (HedgePair.settle, the sole cancel performer) and the loser
        is discarded unbooked."""
        sanitize.check_hedge(pb)
        # RUNNING -> RUNNING (legal re-mark): a checkpoint taken
        # mid-race must still re-queue these rows on restart
        state.mark_running(pb.entries)
        compiler, pages, kwargs = self._hedge_dispatch(
            state, pb.key, pb.entries, host)
        bd = _compile().dispatch_bucket(
            state.plan, compiler, pb.key, list(pb.entries), pages=pages,
            **kwargs, **self._dispatch_opts())
        push_q = state.queues[host]
        pair = HedgePair()
        hpb = PendingBucket(dispatch=bd, host=host, book=pb.book,
                            is_hedge=True, pair=pair)
        pair.legs = [(pb, owner_q), (hpb, push_q)]
        pb.state = "HEDGED"
        pb.pair = pair
        push_q.stats.hedges += 1
        push_q.push(hpb)

    def _open_gates(self, state: DrainState) -> Optional[float]:
        """Purge matured retry gates (their rows rejoin the pending view
        at its next sync); seconds until the earliest gate still armed,
        None when nothing is gated."""
        if not state.retry_at:
            return None
        now = time.perf_counter()
        for e, t in list(state.retry_at.items()):
            if t <= now:
                del state.retry_at[e]
        if not state.retry_at:
            return None
        return max(min(state.retry_at.values()) - now, 0.0)


class _BucketStreamBackend(_StreamBackend):
    """Inline/Sharded policy: the first pending bucket group per step,
    dispatched whole and harvested on a later step (non-blocking
    dispatch) — the step that dispatches bucket k+1 books bucket k's
    results while the device executes."""

    def _dispatch_next(self, state: DrainState) -> bool:
        groups = state.plan.pending_by_bucket(
            exclude=state.in_flight_entries(), gated=state.retry_at)
        if not groups:
            return False
        bkey, entries = next(iter(groups.items()))
        kwargs = self._dispatch_kwargs(state, bkey, entries)
        state.mark_running(entries)
        bd = _compile().dispatch_bucket(
            state.plan, self.compiler, bkey, entries, pages=self.pages,
            **kwargs, **self._dispatch_opts())
        self._push_bucket(
            state, -1, bd,
            lambda pb, res, el: self._book_harvest(state, pb, res, el))
        state.seen_buckets.add(bkey)
        state.info.buckets = len(state.seen_buckets)
        state.info.waves += 1
        return True


# ---------------------------------------------------------------------------
# InlineBackend — direct bucket drain, the reference scheduler
# ---------------------------------------------------------------------------
class InlineBackend(_BucketStreamBackend):
    """Every pending bucket in one direct program call.  No capacity
    limit, single-device task layout: the oracle the other schedulers
    must agree with."""
    name = "inline"

    def __init__(self, pool: Optional[PoolConfig] = None):
        self.pool = pool or PoolConfig()
        self.compiler = _compile().ProgramCache()
        self.pages = _compile().PagePool(self.pool.page_pool_bytes) \
            if self.pool.page_pool_bytes else None


# ---------------------------------------------------------------------------
# ShardedBackend — the bucket programs SPMD over a device mesh
# ---------------------------------------------------------------------------
def make_sharded_compiler(mesh) -> "ProgramCache":
    """A ProgramCache whose programs SPMD over ``mesh``'s "data" axis.

    Unfused programs shard the single-block specs (the PR 1 path);
    fused launches go through the shard_map-wrapped ``lax.map`` form
    (ISSUE 8, ``megabatch_specs(fused=True)``), so a partitioned cache
    participates in same-shape fusion like an unpartitioned one.  The
    mesh axes (names + sizes) become the cache's ``partition_axes`` —
    part of every sharded-fused program's cache key.
    """
    from repro.sharding.policy import megabatch_specs
    in_specs, out_specs = megabatch_specs("data")
    fin_specs, fout_specs = megabatch_specs("data", fused=True)

    def partition(fn):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def partition_fused(fn):
        return jax.shard_map(fn, mesh=mesh, in_specs=fin_specs,
                             out_specs=fout_specs, check_vma=False)

    axes = tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)
    return _compile().ProgramCache(partition=partition,
                                   partition_fused=partition_fused,
                                   partition_axes=axes)


class ShardedBackend(_BucketStreamBackend):
    """The same megabatch programs with the task-batch axis shard_map'd
    over the mesh's "data" axis (pages replicated on every device;
    sharding/policy.py::megabatch_specs).  Fused launches shard too
    (ISSUE 8): shard_map wraps the lax.map fused body, so same-shape
    fusion survives partitioning.  Every bucket's parallelization axis
    is roofline-priced (compile/buckets.py::plan_bucket_axis) and the
    decision logged on BackendRunInfo.axis_plans.  Reuses launch/mesh.py
    meshes; stays warm across requests via the spec-keyed ProgramCache."""
    name = "sharded"

    def __init__(self, pool: Optional[PoolConfig] = None, mesh=None):
        self.pool = pool or PoolConfig()
        self._mesh = mesh
        self._compiler: Optional[ProgramCache] = None
        self.pages = _compile().PagePool(self.pool.page_pool_bytes) \
            if self.pool.page_pool_bytes else None

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_host_mesh
            self._mesh = make_host_mesh()
        return self._mesh

    def _n_shards(self) -> int:
        return int(self.mesh.shape["data"])

    def _dispatch_kwargs(self, state: DrainState, key, entries) -> Dict:
        """The mesh's task alignment and the bucket's planned axis, which
        data/feature decisions execute in-mesh (ISSUE 9)."""
        return {"b_align": self._n_shards(),
                "axis_decision": self._plan_axis(state, key, entries,
                                                 self._n_shards()),
                "mesh": self._axis_mesh()}

    def _axis_mesh(self):
        """Data/feature AxisDecisions lower onto this backend's mesh."""
        return self.mesh

    @property
    def compiler(self) -> ProgramCache:
        if self._compiler is None:
            self._compiler = make_sharded_compiler(self.mesh)
        return self._compiler


# ---------------------------------------------------------------------------
# WaveBackend — the serverless-analogue scheduler, multi-request
# ---------------------------------------------------------------------------
@dataclass(eq=False)            # identity equality: removed by list.remove
class _WaveLatch:
    """One pipelined wave awaiting settlement (ISSUE 7 double-buffered
    dispatch).

    A wave does not barrier at the end of its step — its buckets stay in
    flight while the next wave is filled and stacked.  The latch
    accumulates the wave's results and frontier-attributed wall shares
    as each bucket's booking continuation fires, and the wave
    **settles** (ledgers booked, bills recorded, requests finalized,
    checkpoint written) the moment its last bucket lands.
    """
    entries: List[Tuple[int, int]]      # the wave's (slot, invocation)s
    outstanding: int                    # buckets still in flight
    results: Dict = field(default_factory=dict)
    wall_of_req: Dict = field(default_factory=dict)


class WaveBackend(_StreamBackend):
    """The paper's wave scheduler (§4) generalized to a request stream.

    One *invocation* = the paper's lambda call; each step fills one wave
    of up to ``n_workers * lanes_per_worker`` invocations (or the
    occupancy autoscaler's pick, ``pool.autoscale``) from every admitted
    request's pending set, so concurrent estimations share dispatch
    cycles (fused waves).  A wave's lanes are grouped by megabatch
    bucket and executed as one compiled program launch per bucket — one
    warm "worker program" serves every task of a bucket regardless of
    which request it came from.  Up to ``pool.pipeline_depth`` waves
    stay unsettled while the next one fills; each books when its last
    bucket lands.

    Billing: measured (a request's share of its buckets' program wall
    time divided over its lanes) or modeled via the Lambda memory/vCPU
    curve (simulate=True).
    """
    name = "wave"

    def __init__(self, pool: Optional[PoolConfig] = None):
        self.pool = pool or PoolConfig()
        self.compiler = _compile().ProgramCache()
        self.pages = _compile().PagePool(self.pool.page_pool_bytes) \
            if self.pool.page_pool_bytes else None
        self.autoscaler = OccupancyAutoscaler(self.pool) \
            if self.pool.autoscale else None

    def _dispatch_next(self, state: DrainState) -> bool:
        """Fill one wave from the pending view and dispatch every bucket
        of it without waiting — under chaos too, since fault verdicts
        are identity-keyed (serverless/chaos.py) and so immune to
        dispatch order — then bound the pipeline at
        ``pool.pipeline_depth`` unsettled waves."""
        view = state.plan.pending
        view.sync(exclude=state.in_flight_entries(), gated=state.retry_at)
        if not view.depth:
            return False

        def load():
            tasks = sum(n * _tasks_per_invocation(state.requests[ri])
                        for ri, n in view.by_slot())
            return view.depth, tasks, view.groups

        # ---- fill the wave (whole-bucket units, ISSUE 8) ----------------
        batch = view.fill(self._wave_capacity(state, -1, load))
        self._note_members(state, (ri for ri, _ in batch))
        state.mark_running(batch)
        # ---- execute: one compiled launch per bucket in the wave --------
        # every bucket dispatches without blocking, so a wave's launches
        # run on device while the host stacks the next bucket; the latch
        # settles the wave when its last bucket lands — possibly steps
        # later, while wave k+1 is already filling
        groups = state.plan.group_entries(batch)
        latch = _WaveLatch(entries=batch, outstanding=len(groups))
        state.waves_inflight.append(latch)

        def book(pb, res, elapsed):
            latch.results.update(res)
            per = elapsed / max(len(pb.entries), 1)
            for ri, _ in pb.entries:
                latch.wall_of_req[ri] = latch.wall_of_req.get(ri, 0.0) + per
            latch.outstanding -= 1
            if latch.outstanding == 0:
                self._settle_wave(state, latch)

        for bkey, ents in groups.items():
            state.seen_buckets.add(bkey)
            bd = _compile().dispatch_bucket(state.plan, self.compiler,
                                            bkey, ents, pages=self.pages,
                                            **self._dispatch_opts())
            self._push_bucket(state, -1, bd, book)
        state.info.buckets = len(state.seen_buckets)
        state.info.waves += 1
        # bound the pipeline: harvest the oldest buckets until at most
        # pipeline_depth waves remain unsettled
        q = state.queues[-1]
        depth = max(1, self.pool.pipeline_depth)
        if self._hedge_armed(state):
            # poll, don't block: a blocked harvest picks the held
            # straggler and sleeps out the very hold the hedged
            # duplicate exists to beat — every race would settle for
            # the original
            while len(state.waves_inflight) > depth and not q.empty:
                self._maybe_hedge(state)
                if q.harvest_ready() == 0:
                    time.sleep(0.001)
        else:
            while len(state.waves_inflight) > depth and q.harvest_next():
                pass
        return True

    def _settle_wave(self, state: DrainState, latch: _WaveLatch):
        """Book one pipelined wave the moment its last bucket lands.
        Each request is billed and charged its share of the queue's
        NON-overlapping attribution frontier, so concurrent waves'
        billed spans sum to the true elapsed wall instead of
        double-charging overlap."""
        state.waves_inflight.remove(latch)
        self._settle(state, -1, _by_request(latch.entries), latch.results,
                     latch.wall_of_req)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
BACKENDS = {"wave": WaveBackend, "inline": InlineBackend,
            "sharded": ShardedBackend}
# "topology" resolves lazily in make_backend: serverless/topology.py
# imports this module, so eager registration would be a cycle
BACKEND_NAMES = tuple(BACKENDS) + ("topology",)


def make_backend(backend, pool: Optional[PoolConfig] = None):
    """Resolve a backend name (or pass through an instance)."""
    if isinstance(backend, str):
        if backend == "topology":
            from repro.serverless.topology import TopologyBackend
            return TopologyBackend(pool)
        if backend not in BACKENDS:
            raise KeyError(f"unknown backend {backend!r}; known: "
                           f"{BACKEND_NAMES}")
        return BACKENDS[backend](pool)
    return backend
