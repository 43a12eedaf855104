"""Megabatch bucket planning: the whole cross-fitting grid -> few shapes.

The planner takes the union of all pending ``WorkRequest``s — across
sessions, repetitions, folds, nuisances, and mixed learner families — and
groups every task into a **bucket** keyed by

    (learner identity, padded N bucket, padded P bucket)

Tasks inside a bucket are shape-compatible after padding, so one jitted
program (see program.py) serves all of them regardless of which request
they came from: the serverless-ML lesson (pack many small homogeneous
work items into few large compiled invocations) applied to the paper's
M x K x L task grid.

Padding rules per learner family:

  * registry learners: N rounded up to the sublane quantum
    (``aligned_bucket``, multiples of 8 — mirroring the B tail rule),
    so N-axis waste is bounded at < 8 rows per lane instead of pow2's
    <2x; P stays pow2-bucketed for the feature-pad-safe families (the
    long tail of widths collapses onto a handful of programs);
  * mlp (init scale depends on the true P): N aligned, P exact;
  * opaque callables (the legacy raw-array path): exact shapes — we
    cannot prove padding is inert for arbitrary user code.

The aligned N rule trades program variety for waste: distinct N values
8 apart no longer share a program, but steady serving re-presents the
same N values and the N-axis was the dominant waste term once B was
fixed (35.9% on BENCH_asyncdrain vs 25% B; now gated <= 30% in CI).

The planner is pure bookkeeping (numpy only); execution and the warm
program cache live in program.py.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.analysis.registry import warm_cache
from repro.core.crossfit import aligned_bucket, pow2_bucket
from repro.learners import FEATURE_PAD_SAFE

Entry = Tuple[int, int]                 # (request index, invocation id)


@dataclass(frozen=True)
class BucketKey:
    """Identity of one megabatch program family."""
    learner: object                     # Segment.bucket_id (spec or opaque)
    n_pad: int
    p_pad: int


@dataclass
class _Slot:
    """One live request of a ``PendingView``: its ledger, and the bucket
    of each of its invocations (an index into ``keys``)."""
    ledger: object
    kidx: List[int]
    keys: List[BucketKey]


class PendingView:
    """The dispatchable invocations of a live drain, kept as the drain
    runs instead of rebuilt from every ledger at every step.

    A request slot enters at admission with its not-DONE invocations,
    grouped by bucket in ascending order, and leaves when its ledger
    completes (``retire``).  ``take`` moves dispatched invocations out;
    ``sync`` brings back each one that is neither in flight nor held by a
    retry gate and is still owed: a failed booking, a hedge's discarded
    loser, a lost host's orphans, a matured gate.  So a read costs what
    the live requests hold, never what the drain has completed.

    Order is the drain's contract: buckets by their first (request slot,
    invocation), entries by slot then invocation.  That is the order a
    scan over every request's ledger gives, so the waves, launches and
    padding do not depend on how the view is kept.
    """

    def __init__(self):
        self._slots: Dict[int, _Slot] = {}
        # bucket -> slot -> its dispatchable invocations, ascending;
        # slots in admission (= ascending) order
        self._holders: Dict[BucketKey, Dict[int, List[int]]] = {}
        self._count: Dict[BucketKey, int] = {}
        self._out: set = set()          # taken, not yet back or finished
        self._gated: List[Entry] = []   # owed but gated at the last sync
        self.scanned = 0                # lists and entries the last read saw

    @property
    def live(self) -> int:
        """Request slots that still owe invocations."""
        return len(self._slots)

    @property
    def depth(self) -> int:
        """Dispatchable invocations over every live slot."""
        return sum(self._count.values())

    def admit(self, ri: int, ledger, kidx: np.ndarray,
              keys: List[BucketKey]) -> None:
        owed = ledger.pending()
        if not len(owed):
            return
        self._slots[ri] = _Slot(ledger, kidx.tolist(), keys)
        for k, key in enumerate(keys):
            invs = owed[kidx[owed] == k].tolist()
            if invs:
                self._holders.setdefault(key, {})[ri] = invs
                self._count[key] = self._count.get(key, 0) + len(invs)

    def retire(self, ri: int) -> None:
        """Drop a slot whose ledger completed."""
        slot = self._slots.pop(ri, None)
        if slot is None:
            return
        for key in slot.keys:
            hold = self._holders.get(key)
            if hold is None or ri not in hold:
                continue
            self._count[key] -= len(hold.pop(ri))
            if not hold:
                del self._holders[key], self._count[key]

    def take(self, ri: int, invs: Sequence[int]) -> None:
        """Move dispatched invocations of one slot out of the view (a
        hedge's duplicate takes nothing: its entries are out already)."""
        slot = self._slots.get(ri)
        if slot is None:
            return
        fresh = [int(i) for i in invs if (ri, int(i)) not in self._out]
        by_key: Dict[int, List[int]] = {}
        for inv in fresh:
            by_key.setdefault(slot.kidx[inv], []).append(inv)
            self._out.add((ri, inv))
        for k, ins in by_key.items():
            key = slot.keys[k]
            invs_k = self._holders[key][ri]
            n0 = len(invs_k)
            if invs_k[:len(ins)] == ins:        # a fill takes a prefix
                del invs_k[:len(ins)]
            else:
                drop = set(ins)
                invs_k[:] = [i for i in invs_k if i not in drop]
            self._count[key] -= n0 - len(invs_k)

    def sync(self, *, exclude, gated=None) -> None:
        """Bring back every taken invocation that is not in ``exclude``
        (the caller's in-flight entries), not held by a ``gated`` retry
        gate and still owed.  Leaving an in-flight entry out of
        ``exclude`` would dispatch it twice."""
        exclude = exclude or ()
        gated = gated or ()
        self._gated = []
        for e in list(self._out):
            if e in exclude:
                continue
            ri, inv = e
            slot = self._slots.get(ri)
            if slot is None or not slot.ledger.owes(inv):
                self._out.remove(e)
            elif e in gated:
                self._gated.append(e)
            else:
                self._out.remove(e)
                key = slot.keys[slot.kidx[inv]]
                bisect.insort(self._holders[key][ri], inv)
                self._count[key] += 1

    def _order(self, extra: Sequence[Entry] = ()) -> List[BucketKey]:
        """Buckets with dispatchable entries, by their first (slot,
        invocation); ``extra`` entries count toward that first one."""
        first: Dict[BucketKey, Entry] = {}
        for key, hold in self._holders.items():
            if not self._count[key]:
                continue
            for ri, invs in hold.items():
                self.scanned += 1
                if invs:
                    first[key] = (ri, invs[0])
                    break
        for ri, inv in extra:
            slot = self._slots[ri]
            key = slot.keys[slot.kidx[inv]]
            if key in first and (ri, inv) < first[key]:
                first[key] = (ri, inv)
        return sorted(first, key=first.__getitem__)

    def _stream(self, key: BucketKey) -> Iterator[Entry]:
        for ri, invs in self._holders[key].items():
            self.scanned += 1
            for inv in invs:
                self.scanned += 1
                yield ri, inv

    def groups(self) -> Dict[BucketKey, List[Entry]]:
        """Every dispatchable entry, by bucket.  A bucket is placed as if
        its gated entries were still there (the backoff used to filter
        them out of this grouping, not before it)."""
        self.scanned = 0
        return {key: list(self._stream(key))
                for key in self._order(self._gated)}

    def fill(self, capacity: int) -> List[Entry]:
        """One wave of at most ``capacity`` entries, in whole-bucket units.

        Round-robin admission is fair but fragments a bucket's canonical
        tail blocks across waves: a 24-lane bucket cut 6/18 by the
        capacity limit pads to 8 + 24 lanes instead of one 24-lane
        launch.  So a bucket small enough to ever travel whole is taken
        whole, in bucket order, or deferred to the next wave; only
        buckets larger than a full wave are split, and those split
        round-robin across each other so concurrent oversize requests
        still share dispatch cycles.  The counts decide; only the
        entries taken are read."""
        with obs.span("planner.fill") as sp:
            self.scanned = 0
            batch: List[Entry] = []
            oversized: List[Iterator[Entry]] = []
            for key in self._order():
                n = self._count[key]
                if n > capacity:
                    oversized.append(self._stream(key))
                elif n <= capacity - len(batch):
                    batch.extend(self._stream(key))
                # else: whole-bucket sized but no room left — defer intact
            while len(batch) < capacity and oversized:
                for it in oversized:
                    if len(batch) < capacity:
                        batch.append(next(it))
            sp.set(entries=len(batch), live=self.live,
                   scanned=self.scanned)
        return batch

    def by_slot(self) -> Iterator[Tuple[int, int]]:
        """(slot, dispatchable invocations) of each live slot."""
        for ri, slot in self._slots.items():
            yield ri, sum(len(self._holders.get(key, {}).get(ri, ()))
                          for key in slot.keys)


@dataclass
class MegabatchPlan:
    """The lowered view of a stream of requests: every (request, segment)
    mapped to its bucket, lazily-built padded data pages, and the live
    ``PendingView`` the schedulers read.

    The plan is **incremental**: ``admit()`` lowers one request at a time,
    so the continuous-admission drain engine can extend a live plan while
    earlier requests are already executing.  ``plan_buckets`` stays as the
    batch convenience (admit everything up front).
    """
    requests: List = field(default_factory=list)
    bucket_of: Dict[Tuple[int, int], BucketKey] = field(default_factory=dict)
    seg_of: Dict[Tuple[int, BucketKey], int] = field(default_factory=dict)
    min_n: int = 8
    min_p: int = 8
    _pages: Dict[Tuple[int, int, int], np.ndarray] = field(
        default_factory=dict)
    pending: PendingView = field(default_factory=PendingView)

    # ---- continuous admission -------------------------------------------
    def admit(self, req) -> int:
        """Lower one request into the plan and its owed invocations into
        the pending view; returns its request index."""
        ri = len(self.requests)
        self.requests.append(req)
        n = int(req.ledger.n_obs)
        p = int(req.x.shape[1])
        keys: List[BucketKey] = []
        k_of_seg: List[int] = []
        for si, seg in enumerate(req.segments):
            if seg.learner is None:            # opaque callable: exact shapes
                n_pad, p_pad = n, p
            elif seg.learner in FEATURE_PAD_SAFE:
                n_pad = aligned_bucket(n, self.min_n)
                p_pad = pow2_bucket(p, self.min_p)
            else:                              # e.g. mlp: P must stay exact
                n_pad, p_pad = aligned_bucket(n, self.min_n), p
            key = BucketKey(seg.bucket_id, n_pad, p_pad)
            self.bucket_of[(ri, si)] = key
            # first-wins: if two segments of one request collapse onto one
            # bucket (their *resolved* params are equal), either resolves
            # the same batched fn — per-task PRNG streams are looked up
            # via segment_of_inv in run_bucket, never through this map
            self.seg_of.setdefault((ri, key), si)
            if key not in keys:
                keys.append(key)
            k_of_seg.append(keys.index(key))
        inv_seg = req.segment_of_inv(np.arange(req.ledger.n_invocations))
        self.pending.admit(ri, req.ledger, np.asarray(k_of_seg)[inv_seg],
                           keys)
        return ri

    # ---- planning shapes -------------------------------------------------
    @property
    def buckets(self) -> List[BucketKey]:
        out: List[BucketKey] = []
        for key in self.bucket_of.values():
            if key not in out:
                out.append(key)
        return out

    # the plan owns its requests, so req_idx names one fixed request for
    # this plan's lifetime (the cache dict dies with the plan: ambient)
    @warm_cache(name="plan_pages", key=("req_idx", "key.n_pad",
                                        "key.p_pad"),
                ambient=("self",))
    def page(self, req_idx: int, key: BucketKey) -> np.ndarray:
        """The request's feature page padded to the bucket shape."""
        pkey = (req_idx, key.n_pad, key.p_pad)
        page = self._pages.get(pkey)
        if page is None:
            x = self.requests[req_idx].x
            page = np.zeros((key.n_pad, key.p_pad), np.float32)
            page[:x.shape[0], :x.shape[1]] = x
            self._pages[pkey] = page
        return page

    # ---- entry grouping --------------------------------------------------
    def group_entries(self, entries: Sequence[Entry]) \
            -> Dict[BucketKey, List[Entry]]:
        """Group (request, invocation) pairs by their bucket, preserving
        order (deterministic program launch order)."""
        groups: Dict[BucketKey, List[Entry]] = {}
        by_req: Dict[int, List[int]] = {}
        for ri, inv in entries:
            by_req.setdefault(ri, []).append(inv)
        for ri, invs in by_req.items():
            req = self.requests[ri]
            seg_idx = req.segment_of_inv(np.asarray(invs, np.int64))
            for inv, si in zip(invs, seg_idx):
                key = self.bucket_of[(ri, int(si))]
                groups.setdefault(key, []).append((ri, int(inv)))
        return groups

    def pending_by_bucket(self, exclude=None, gated=None
                          ) -> Dict[BucketKey, List[Entry]]:
        """Every dispatchable invocation of every live request, bucketed
        (``PendingView.sync`` then ``groups``).

        ``exclude`` is the dispatched-but-unharvested entry set of the
        caller's in-flight queues: those invocations are on device already
        and must not be re-dispatched while their launch is pending.
        ``gated`` holds failed rows until their retry backoff matures."""
        with obs.span("planner.fill") as sp:
            self.pending.sync(exclude=exclude, gated=gated)
            groups = self.pending.groups()
            sp.set(entries=sum(len(e) for e in groups.values()),
                   live=self.pending.live, scanned=self.pending.scanned)
            return groups


def pack_tail_blocks(lane_counts: Sequence[int], b_block: int,
                     quantum: int = 8, b_align: int = 1,
                     ) -> Tuple[List[List[int]], int]:
    """Pack tail-block lane counts into combined launch blocks sharing
    ONE uniform lane count ``T`` (ISSUE 7 cross-shape coalescing).
    Returns ``(groups, T)``: index groups plus the shared padded size.

    A uniform T is what lets every packed group fuse into a single
    ``lax.map`` launch without a second morph-up pass (morphing smaller
    groups up to the largest one is where naive packing bleeds padding).
    T is chosen by sweeping every aligned candidate up to ``b_block``
    and greedily first-fit packing against it, keeping the T that
    minimizes total padded lanes (ties: fewer groups, then smaller T).

    Deterministic: inputs are visited in order and placed into the
    first group with room, so a bucket's packing is a pure function of
    its tail sizes — the same traffic packs the same way on every
    drain.  Pure bookkeeping; the bitwise-safety of launching packed
    lanes at a different compiled B is the compiled-B invariance proven
    per family in tests/test_compile.py and audited by
    analysis/jaxpr_audit.py.
    """
    counts = [int(k) for k in lane_counts]
    lo = max(aligned_bucket(k, quantum, b_align) for k in counts)
    cands = sorted({aligned_bucket(v, quantum, b_align)
                    for v in range(lo, max(b_block, lo) + 1)})

    def pack(cap: int) -> Tuple[List[List[int]], List[int]]:
        groups: List[List[int]] = []
        totals: List[int] = []
        for i, k in enumerate(counts):
            for gi, tot in enumerate(totals):
                if aligned_bucket(tot + k, quantum, b_align) <= cap:
                    groups[gi].append(i)
                    totals[gi] = tot + k
                    break
            else:
                groups.append([i])
                totals.append(k)
        return groups, totals

    best = None
    for cap in cands:
        groups, _ = pack(cap)
        score = (len(groups) * cap, len(groups), cap)
        if best is None or score < best[0]:
            best = (score, groups, cap)
    return best[1], best[2]


def plan_buckets(requests: Sequence, *, min_n: int = 8,
                 min_p: int = 8) -> MegabatchPlan:
    """Assign every (request, segment) to a megabatch bucket (batch form
    of ``MegabatchPlan.admit``)."""
    plan = MegabatchPlan(min_n=min_n, min_p=min_p)
    for req in requests:
        plan.admit(req)
    return plan


# ---------------------------------------------------------------------------
# Per-bucket parallelization-axis planning (ISSUE 8)
# ---------------------------------------------------------------------------
@dataclass
class AxisDecision:
    """One bucket's parallelization-axis choice plus the full roofline
    candidate table it was picked from — logged on
    ``BackendRunInfo.axis_plans`` exactly like autoscale decisions, so a
    drain's layout choices are auditable after the fact.  The planner
    fields are written once; ``executed`` is the one mutable slot —
    ``dispatch_bucket`` stamps the axis the drain actually lowered
    (ISSUE 9), so decision-vs-executed mixes are auditable too."""
    bucket: BucketKey
    axis: str                           # task | data | feature
    shards: int                         # mesh devices the layout spans
    n_tasks: int                        # pending tasks priced
    n_pad: int
    p_pad: int
    mesh_devices: int                   # devices the planner could use
    priced_by: str = "roofline"
    # (axis, shards, est_s, executable) per candidate, planner input
    candidate_costs: Tuple[Tuple[str, int, float, bool], ...] = ()
    # axis dispatch_bucket actually executed: None until the bucket's
    # first dispatch; "task" when a data/feature plan fell back (e.g.
    # no mesh, a non-Gram family, or a non-divisible shard count)
    executed: Optional[str] = None

    @property
    def est_s(self) -> float:
        """The chosen candidate's priced wall-clock."""
        for axis, shards, est, _ in self.candidate_costs:
            if axis == self.axis and shards == self.shards:
                return est
        return float("nan")


def plan_bucket_axis(key: BucketKey, *, n_tasks: int, n_devices: int,
                     ) -> "AxisDecision | None":
    """Pick the parallelization axis for one bucket on an
    ``n_devices`` mesh: roofline-price the task-parallel, data-parallel
    (blocked Gram) and feature-parallel candidates
    (``launch/roofline.py::axis_candidate_costs``) and take the cheapest
    *executable* one.  Returns None for opaque-callable buckets (no
    analytic model — they always run task-parallel unsharded).

    Pure pricing: deterministic in (bucket, n_tasks, n_devices), no
    device access — so the decision is unit-testable and the bench gate
    "the planner never picks a candidate priced strictly worse than
    another executable one" holds by construction.
    """
    ident = key.learner
    if not (isinstance(ident, tuple) and len(ident) == 2
            and isinstance(ident[0], str)) or ident[0] == "opaque":
        return None
    from repro.launch.roofline import axis_candidate_costs
    learner, ptuple = ident
    cands = axis_candidate_costs(learner, dict(ptuple), n_tasks,
                                 key.n_pad, key.p_pad, n_devices)
    runnable = [c for c in cands if c[3]]
    if not runnable:                      # e.g. tall-N non-Gram family
        runnable = [c for c in cands if c[0] == "task"]
    axis, shards, _, _ = min(runnable, key=lambda c: (c[2], c[1], c[0]))
    return AxisDecision(bucket=key, axis=axis, shards=shards,
                        n_tasks=int(n_tasks), n_pad=key.n_pad,
                        p_pad=key.p_pad, mesh_devices=int(n_devices),
                        candidate_costs=tuple(cands))
