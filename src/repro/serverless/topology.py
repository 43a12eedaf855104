"""The topology layer: per-mesh drain streams with locality-aware
placement of each request's work.

The drain engine so far ran one stream over one host mesh.  This module
models the *cluster*: a ``Topology`` of host meshes — real pods split out
of ``launch/mesh.py::make_production_mesh`` ("pod", "data", "model"), or
N simulated hosts over this process's devices — each owning a per-host
device-resident ``PagePool`` (all pools sharing one ``PageDirectory``)
and one drain stream.  ``TopologyBackend`` is the scheduler over them:

  * **placement** — the unit of placement is a request's pending
    invocations in one megabatch bucket (one page: the request's data
    at the bucket's shape).  Every new unit is routed to a host by
    ``sharding/policy.py::place_unit``, scored against each host's
    page residency (stack-cached > pages-resident > cold, ties to the
    least-loaded host).  Steady-state traffic therefore re-lands on the
    host already holding its pages: zero transfers of any kind; and a
    bucket of many requests on fresh data — a Monte-Carlo study's
    replications — spreads over every host by load.
  * **per-mesh streams** — one ``step()`` advances ONE host's stream by
    one wave (round-robin cursor), so the session's event loop
    interleaves all hosts exactly as it interleaves waves today;
    ledgers complete out of order across hosts as they do within one.
    Since ISSUE 5 each host stream owns an in-flight **dispatch queue**
    (serverless/dispatch.py): a wave launches its buckets without
    blocking, and results are booked by later steps' non-blocking
    harvest — so one mesh's device execution overlaps every other
    host's placement, stealing, and booking.
  * **work-stealing** — a host whose queue drained steals not-yet-
    dispatched units from the most-loaded host, least local first
    (``policy.steal_choice``); a stolen unit's page arrives
    device-to-device from the holder (a *cross-host transfer*, counted
    by the directory) and stays resident, so a re-stolen unit is free.
  * **autoscaling** — a ``TopologyAutoscaler`` sizes each host's wave
    independently, pricing cold candidates with the compiler's
    per-bucket roofline FLOP estimates
    (``launch/roofline.py::invocation_roofline_s``) until measured
    durations take over.
  * **axis planning** (ISSUE 8) — every bucket's parallelization axis
    is roofline-priced on its host's own mesh
    (``compile/buckets.py::plan_bucket_axis``): compute-heavy buckets
    dispatch as sharded-fused launches (shard_map around the lax.map
    fused body) through a per-host program cache built on that host's
    mesh, small serving buckets stay single-device, and data/feature
    decisions are *executed* in-mesh (ISSUE 9): ``dispatch_bucket``
    lowers them through the sharded Gram executors
    (sharding/gram.py), chunk-paging tall N, and stamps the
    ``executed`` axis back on the decision.  Tall-N Gram buckets
    (``n_pad > DEVICE_PAGE_ROWS``) are routed — and stolen — only by
    hosts whose data axis can stream them: eligibility stays a property
    of the bucket.  Decisions land on
    ``BackendRunInfo.axis_plans`` like autoscale decisions.
  * **fault tolerance** (ISSUE 10) — chaos pools draw identity-keyed
    failure/straggler verdicts at booking (serverless/chaos.py) exactly
    as the single-stream backends do; overdue buckets are hedged onto
    the least-loaded *other* live host through the shared
    bitwise-reference cache; and ``kill_host`` simulates losing a mesh
    mid-drain — its page pool is invalidated (directory detach), its
    in-flight buckets are abandoned (LOST), its units are unassigned,
    and their still-RUNNING ledger rows resurface through the pending
    view to be re-routed onto the survivors, whose pools
    re-materialize any orphaned pages on first touch.

Determinism: placement and stealing only decide *where* a unit's
fixed-shape program runs; per-task PRNG streams are fixed at compile
time, so buckets the planner keeps on the task@1 layout (the whole
serving mix) are bitwise-identical to the single-host inline path
(tests/test_topology.py, gated in CI by BENCH_topology.json).  Buckets
routed to a host's sharded-fused cache inherit that path's parity
tier: bitwise on 1-device hosts, ~1e-6 float tolerance on multi-device
hosts (see the B_BLOCK caveat in compile/program.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.compile.pages import PageDirectory, PagePool, PageStats
from repro.serverless.autoscale import TopologyAutoscaler
from repro.serverless.backends import (
    BackendRunInfo, DrainState, PoolConfig, _compile, _StreamBackend,
    make_sharded_compiler, roofline_pending_inv_s, traced_step,
)
from repro.serverless.chaos import chaos_plan
from repro.serverless.dispatch import (
    DispatchQueue, DispatchStats, PendingBucket,
)
from repro.sharding.policy import place_unit, steal_choice

# the unit of placement: (bucket key, request slot) — one request's
# pending invocations in one bucket
Unit = Tuple[object, int]


# ---------------------------------------------------------------------------
# the cluster model
# ---------------------------------------------------------------------------
@dataclass
class HostMesh:
    """One host: its device mesh, the lead device its page pool pins
    pages to, and the pool itself (directory-shared)."""
    host_id: int
    mesh: object                        # jax.sharding.Mesh of this host
    device: object                      # lead device (page residency)
    pool: PagePool

    @property
    def n_devices(self) -> int:
        return int(np.asarray(self.mesh.devices).size)


class Topology:
    """The set of host meshes one ``TopologyBackend`` schedules over.

    Pools (and therefore page residency) persist across drains — the
    topology is the warm state; drains come and go.
    """

    def __init__(self, hosts: List[HostMesh], directory: PageDirectory):
        self.hosts = hosts
        self.directory = directory
        self.dead: set = set()          # host_ids lost mid-flight

    def __len__(self) -> int:
        return len(self.hosts)

    def alive(self) -> List[HostMesh]:
        """The hosts still schedulable (host loss is permanent for the
        topology's lifetime — pools persist across drains, corpses
        don't come back)."""
        return [h for h in self.hosts if h.host_id not in self.dead]

    def kill(self, host_id: int) -> None:
        """Lose one host: invalidate its page pool (every resident page
        and stack dropped, directory withdrawn so no d2d fetch is ever
        brokered against its device memory) and mark it dead for
        routing/stealing.  In-flight work recovery is the backend's job
        (``TopologyBackend.kill_host``)."""
        if host_id in self.dead:
            return
        self.dead.add(host_id)
        self.hosts[host_id].pool.invalidate()

    @classmethod
    def _from_meshes(cls, meshes, page_pool_bytes: int) -> "Topology":
        directory = PageDirectory()
        hosts = []
        for i, mesh in enumerate(meshes):
            dev = np.asarray(mesh.devices).flat[0]
            hosts.append(HostMesh(
                host_id=i, mesh=mesh, device=dev,
                pool=PagePool(page_pool_bytes, host_id=i,
                              directory=directory, device=dev)))
        return cls(hosts, directory)

    @classmethod
    def simulated(cls, n_hosts: int,
                  page_pool_bytes: int = 256 * 1024 * 1024) -> "Topology":
        """N simulated hosts over this process's devices (the forced
        host-platform CI path)."""
        from repro.launch.mesh import make_sim_host_meshes
        return cls._from_meshes(make_sim_host_meshes(n_hosts),
                                page_pool_bytes)

    @classmethod
    def from_mesh(cls, mesh,
                  page_pool_bytes: int = 256 * 1024 * 1024) -> "Topology":
        """One host per index of the mesh's leading "pod" axis (the
        production ("pod", "data", "model") meshes); a pod-less mesh
        becomes a single-host topology."""
        from repro.launch.mesh import split_pod_meshes
        return cls._from_meshes(split_pod_meshes(mesh), page_pool_bytes)

    def page_stats(self) -> PageStats:
        """Cluster-wide page accounting (sum of the per-host pools)."""
        out = PageStats()
        for h in self.hosts:
            out = out.merge(h.pool.stats)
        return out


class ClusterPages:
    """The per-host pools seen as the one ``pages`` a backend exposes:
    ``stats`` is the cluster-wide sum, as a single-stream backend's
    ``pages.stats`` is its one pool's.  Telemetry reads it; the drain
    itself stages through each host's own pool."""

    def __init__(self, topology: Topology):
        self._topology = topology

    @property
    def stats(self) -> PageStats:
        return self._topology.page_stats()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
@dataclass
class HostLaneInfo:
    """Per-host-stream accounting for one drain."""
    host_id: int
    n_devices: int
    waves: int = 0
    invocations: int = 0
    units_placed: int = 0               # routed here at admission
    steals: int = 0                     # units this host stole


@dataclass
class TopologyInfo:
    """Cross-host accounting for one topology drain (session telemetry:
    ``last_run_info.topology``)."""
    n_hosts: int
    hosts: List[HostLaneInfo]
    steals: int = 0                     # units stolen
    placements: List[Tuple[object, int, int, float]] = field(
        default_factory=list)           # (bucket key, request slot, host,
                                        #  score)
    host_losses: int = 0                # hosts killed mid-drain
    lost_buckets: int = 0               # in-flight buckets abandoned


@dataclass
class TopologyDrainState(DrainState):
    """One continuous drain over all host streams: the shared bucket
    plan plus the live unit→host assignment (units with pending,
    not-yet-dispatched invocations only), the round-robin cursor the
    event loop steps with, and one in-flight dispatch queue per host
    mesh (the per-host streams are the dispatch unit)."""
    assignment: Dict[Unit, int] = field(default_factory=dict)
    cursor: int = 0
    queues: Dict[int, DispatchQueue] = field(default_factory=dict)

    def in_flight_entries(self) -> set:
        out = set()
        for q in self.queues.values():
            out |= q.in_flight_entries()
        return out


# ---------------------------------------------------------------------------
# the backend
# ---------------------------------------------------------------------------
class TopologyBackend(_StreamBackend):
    """Per-mesh drain streams with page-locality routing of units (a
    request's pending invocations in one bucket).

    One ``step(state)`` advances one host stream by one wave: the
    session's event loop therefore steps all streams round-robin, and a
    host's wave is sized by its own autoscaler lane.  Chaos pools draw
    identity-keyed fault verdicts at booking (serverless/chaos.py);
    overdue buckets hedge cross-host; ``kill_host`` loses a mesh
    mid-drain and the survivors finish every admitted request.  The
    reference for its results is the single-host inline path, bitwise.
    """
    name = "topology"

    def __init__(self, pool: Optional[PoolConfig] = None,
                 topology: Optional[Topology] = None,
                 n_hosts: Optional[int] = None):
        self.pool = pool or PoolConfig()
        if topology is None:
            topology = Topology.simulated(
                n_hosts if n_hosts is not None else self.pool.n_hosts,
                self.pool.page_pool_bytes or 0)
        self.topology = topology
        self.compiler = _compile().ProgramCache()
        # per-host sharded program caches (ISSUE 8): lazily built on each
        # host's own mesh so a bucket the axis planner prices as
        # task-parallel-over-the-mesh dispatches as a sharded-fused
        # launch on that mesh.  All host caches feed the shared
        # CompileStats so session telemetry stays one block.
        self._host_compilers: Dict[int, object] = {}
        self.autoscaler = TopologyAutoscaler(self.pool, len(topology)) \
            if self.pool.autoscale else None
        self.pages = ClusterPages(topology)

    @property
    def _programs(self) -> Dict:
        return self.compiler._programs

    # ---- drain lifecycle ---------------------------------------------
    def begin_drain(self) -> TopologyDrainState:
        info = BackendRunInfo(backend=self.name)
        info.compile = self.compiler.stats
        info.pages = self.topology.page_stats()
        info.topology = TopologyInfo(
            n_hosts=len(self.topology),
            hosts=[HostLaneInfo(h.host_id, h.n_devices)
                   for h in self.topology.hosts])
        state = TopologyDrainState(plan=_compile().MegabatchPlan(),
                                   info=info)
        state.chaos = chaos_plan(self.pool)
        # one in-flight queue per host mesh, all feeding one stats block
        info.dispatch = DispatchStats()
        state.queues = {
            h.host_id: DispatchQueue(self.pool.max_inflight,
                                     stats=info.dispatch)
            for h in self.topology.hosts}
        return state

    # admit() is inherited: routing happens lazily in step() (one pass
    # over all unassigned units), so batch admission stays linear

    # ---- placement ----------------------------------------------------
    @staticmethod
    def _units(groups) -> Dict[Unit, List]:
        """The pending view regrouped into units, in bucket then
        request order."""
        units: Dict[Unit, List] = {}
        for key, entries in groups.items():
            for e in entries:
                units.setdefault((key, e[0]), []).append(e)
        return units

    def _pkey(self, state, unit: Unit):
        """A unit's one page: its request's data at its bucket's shape."""
        key, ri = unit
        return PagePool.page_key(state.requests[ri], key.n_pad, key.p_pad)

    def _eligible_hosts(self, key) -> List[int]:
        """The hosts a bucket's units may be routed to.  Tall-N Gram
        buckets (``n_pad > DEVICE_PAGE_ROWS``: no single device holds
        the page, so the drain must chunk-stream them data-parallel)
        go only to hosts whose mesh can stream them — the
        largest data-axis size that divides ``n_pad``; every other
        bucket runs anywhere.  Dead hosts are never eligible."""
        hosts = [h.host_id for h in self.topology.alive()]
        from repro.compile.program import bucket_family
        from repro.launch.roofline import DEVICE_PAGE_ROWS, GRAM_FAMILIES
        if key.n_pad <= DEVICE_PAGE_ROWS \
                or bucket_family(key) not in GRAM_FAMILIES:
            return hosts

        def axis_m(h: int) -> int:
            mesh = self.topology.hosts[h].mesh
            return int(mesh.shape["data"]) \
                if "data" in mesh.axis_names else 1

        ok = [h for h in hosts if key.n_pad % axis_m(h) == 0]
        if not ok:                      # nothing divides: route anywhere,
            return hosts                # dispatch falls back to task axis
        best = max(axis_m(h) for h in ok)
        return [h for h in ok if axis_m(h) == best]

    def _route(self, state: TopologyDrainState,
               units: Dict[Unit, List]) -> Dict[int, List[Unit]]:
        """Keep the assignment of every unit still pending, place every
        new one on its best host among its bucket's eligible set (loads
        maintained incrementally), and return each host's units in
        queue order.  Units whose invocations all went in flight drop
        out of the assignment; a retry that resurfaces them is placed
        again, by residency back where it ran."""
        with obs.span("topology.route") as sp:
            old = state.assignment
            assignment: Dict[Unit, int] = {}
            loads = [0] * len(self.topology)
            fresh: List[Unit] = []
            for u, ents in units.items():
                h = old.get(u)
                if h is None:
                    fresh.append(u)
                else:
                    assignment[u] = h
                    loads[h] += len(ents)
            pools = [h.pool for h in self.topology.hosts]
            info = state.info.topology
            elig_of: Dict[object, List[int]] = {}
            cold = 0
            for u in fresh:
                key = u[0]
                elig = elig_of.get(key)
                if elig is None:
                    elig = elig_of[key] = self._eligible_hosts(key)
                placed = place_unit(self._pkey(state, u),
                                    [pools[h] for h in elig],
                                    [loads[h] for h in elig])
                host = elig[placed.host]
                assignment[u] = host
                loads[host] += len(units[u])
                cold += placed.score == 0
                info.hosts[host].units_placed += 1
                info.placements.append((key, u[1], host, placed.score))
            state.assignment = assignment
            sp.set(units=len(fresh), cold=cold)
            by_host: Dict[int, List[Unit]] = {}
            for u in units:
                by_host.setdefault(assignment[u], []).append(u)
            return by_host

    def _try_steal(self, state: TopologyDrainState,
                   units: Dict[Unit, List],
                   by_host: Dict[int, List[Unit]], thief: int) -> List:
        """An idle host takes the least-local units of the most loaded
        host, up to half its queue; the migration is recorded and the
        assignment flipped so the thief runs them.  Only units in the
        pending view are candidates, so no in-flight entry moves."""
        with obs.span("topology.steal", thief=thief) as sp:
            # tall-N Gram buckets stay on streaming-capable meshes
            runnable = {k for k in {u[0] for u in units}
                        if thief in self._eligible_hosts(k)}
            queues = {h: [u for u in us if u[0] in runnable]
                      for h, us in by_host.items() if h != thief}
            pick = steal_choice(
                queues, [h.pool for h in self.topology.hosts],
                lambda u: self._pkey(state, u), lambda u: len(units[u]))
            if pick is None:
                sp.set(donor=-1, invocations=0)
                return []
            donor, stolen = pick
            for u in stolen:
                state.assignment[u] = thief
            info = state.info.topology
            info.steals += len(stolen)
            info.hosts[thief].steals += len(stolen)
            sp.set(donor=donor,
                   invocations=sum(len(units[u]) for u in stolen))
            return stolen

    # ---- per-bucket axis planning (ISSUE 8) ---------------------------
    def _host_compiler(self, host_id: int):
        """This host's sharded-fused program cache, lazily built on its
        own mesh.  Shares the backend-wide CompileStats so per-host
        caches don't fragment session telemetry."""
        cache = self._host_compilers.get(host_id)
        if cache is None:
            cache = make_sharded_compiler(self.topology.hosts[host_id].mesh)
            cache.stats = self.compiler.stats
            self._host_compilers[host_id] = cache
        return cache

    def _plan_host_axis(self, state, key, entries, host_id: int):
        """Price the bucket's axis candidates on the owning host's mesh
        (once per (bucket, mesh size) per drain) and log the decision."""
        host = self.topology.hosts[host_id]
        memo_key = (key, host.n_devices)
        if memo_key in state.axis_planned:
            return state.axis_planned[memo_key]
        from repro.compile.buckets import plan_bucket_axis
        decision = plan_bucket_axis(key, n_tasks=len(entries),
                                    n_devices=host.n_devices)
        state.axis_planned[memo_key] = decision
        if decision is not None:
            state.info.axis_plans.append(decision)
        return decision

    def _bucket_compiler(self, host_id: int, decision):
        """(program cache, b_align, axis mesh) one bucket dispatches
        through on this host: the host-mesh sharded-fused cache when
        the planner picked an m-way task layout; the shared
        single-device cache *plus the host's mesh* when it picked a
        data/feature layout — ``dispatch_bucket`` lowers those through
        the in-mesh Gram executors (sharding/gram.py, ISSUE 9),
        chunk-paging tall N; the shared cache alone otherwise."""
        if decision is not None and decision.axis == "task" \
                and decision.shards > 1 \
                and self.topology.hosts[host_id].n_devices > 1:
            return self._host_compiler(host_id), decision.shards, None
        if decision is not None and decision.axis in ("data", "feature"):
            return self.compiler, 1, self.topology.hosts[host_id].mesh
        return self.compiler, 1, None

    # ---- the per-host wave --------------------------------------------
    def _wave_capacity(self, state, host_id: int, mine: List[Unit],
                       units: Dict[Unit, List]) -> int:
        pool = self.pool
        if pool.worker_schedule is not None:   # legacy static ramp, per
            sched = pool.worker_schedule       # host stream (wave parity)
            waves_done = state.info.topology.hosts[host_id].waves
            w = sched[min(waves_done, len(sched) - 1)]
            return max(1, w * pool.lanes_per_worker())
        if self.autoscaler is None:
            return max(1, pool.n_workers * pool.lanes_per_worker())
        queued: Dict[object, List] = {}
        for u in mine:
            queued.setdefault(u[0], []).extend(units[u])
        depth = sum(len(ents) for ents in queued.values())
        tasks = sum(
            state.requests[ri].grid.tasks_per_invocation(
                state.requests[ri].scaling)
            for ents in queued.values() for ri, _ in ents)
        decision = self.autoscaler.decide(
            host_id, depth,
            tasks_per_invocation=max(1, tasks // max(depth, 1)),
            padding_waste=self.compiler.stats.padding.waste_frac,
            # dispatched-but-unharvested work on this host's stream is
            # occupancy, not queue depth — never provisioned for twice
            in_flight=state.queues[host_id].in_flight,
            roofline_inv_s=lambda: roofline_pending_inv_s(
                state.requests, queued))
        state.info.autoscale.append(decision)
        return max(1, decision.n_workers * pool.lanes_per_worker())

    def _book_harvest(self, state: TopologyDrainState, pb: PendingBucket,
                      results: Dict, elapsed: float):
        """Booking callback fired at harvest: ledgers, bills, autoscaler
        EMA for the launching host, wave close-out, checkpoint."""
        per_req = self._book_direct(state, pb.entries, results, elapsed)
        if self.autoscaler is not None and pb.entries:
            self.autoscaler.observe(pb.host, elapsed / len(pb.entries))
        if per_req:                     # a fully-failed slice books nothing
            self._note_wave(state, list(per_req), elapsed)
        state.info.pages = self.topology.page_stats()
        self._checkpoint(state)

    def _host_wave(self, state: TopologyDrainState, host_id: int,
                   mine: List[Unit], units: Dict[Unit, List]) -> None:
        """Dispatch one wave of this host's units WITHOUT waiting — the
        launches land in the host's in-flight queue and are booked by a
        later step's harvest, so every other host's placement, stealing,
        and booking overlaps this mesh's execution."""
        with obs.span("topology.wave", host=host_id) as sp:
            host = self.topology.hosts[host_id]
            # a zero byte budget means "pool off" (PoolConfig contract):
            # fall back to host page stacking instead of churning an
            # always-evicting device pool
            host_pages = host.pool if host.pool.byte_budget > 0 else None
            lane = state.info.topology.hosts[host_id]
            q = state.queues[host_id]
            book = lambda pb, res, el: self._book_harvest(state, pb, res,
                                                          el)
            capacity = self._wave_capacity(state, host_id, mine, units)
            # fill the wave unit-by-unit, truncating the last unit to the
            # remaining capacity; each selection takes at least one
            # invocation, so a wave always makes progress.  A bucket's
            # selected units dispatch together, so they fuse
            selected: Dict[object, List] = {}
            taken = 0
            for u in mine:
                if taken >= capacity and selected:
                    break
                ents = units[u][:max(capacity - taken, 1)]
                selected.setdefault(u[0], []).extend(ents)
                taken += len(ents)
            for key, ents in selected.items():
                running: Dict[int, List[int]] = {}
                for ri, inv in ents:
                    running.setdefault(ri, []).append(inv)
                for ri, invs in running.items():
                    state.requests[ri].ledger.mark_running(invs)
                decision = self._plan_host_axis(state, key, ents, host_id)
                compiler, b_align, axis_mesh = self._bucket_compiler(
                    host_id, decision)
                opts = dict(self._dispatch_opts())
                # fusion follows the *chosen* cache, not the shared one: a
                # host's sharded-fused cache fuses, a partition-only cache
                # would not (compile/program.py gate)
                opts["fuse"] = self.pool.fuse and (
                    compiler.partition is None
                    or compiler.partition_fused is not None)
                bd = _compile().dispatch_bucket(
                    state.plan, compiler, key, ents, pages=host_pages,
                    b_align=b_align, axis_decision=decision, mesh=axis_mesh,
                    **opts)
                self._push_bucket(state, q, bd, book, host=host_id)
                state.seen_buckets.add(key)
            sp.set(invocations=taken)
        lane.waves += 1
        lane.invocations += taken
        state.info.waves += 1
        state.info.buckets = len(state.seen_buckets)
        state.info.pages = self.topology.page_stats()

    # ---- fault tolerance ----------------------------------------------
    def _maybe_hedge(self, state: TopologyDrainState) -> int:
        """Cross-host hedging: the duplicate leg of an overdue bucket
        lands on the least-loaded *other* live host and dispatches
        through the shared single-device cache — the bitwise-reference
        path — so whichever leg wins the booked rows are identical
        regardless of either host's axis plan."""
        if not self._hedge_armed(state):
            return 0
        ids = [h.host_id for h in self.topology.alive()
               if h.host_id in state.queues]
        n = 0
        for hid in ids:
            q = state.queues[hid]
            for pb in q.overdue():
                others = [i for i in ids if i != hid] or [hid]
                target = min(
                    others, key=lambda i: state.queues[i].in_flight)
                tpool = self.topology.hosts[target].pool
                self._hedge_bucket(
                    state, pb, q, state.queues[target],
                    compiler=self.compiler,
                    pages=tpool if tpool.byte_budget > 0 else None,
                    host=target)
                n += 1
        return n

    def kill_host(self, state: TopologyDrainState, host_id: int) -> int:
        """Lose one host mid-drain (the chaos suite's host-loss fault):
        its pool is invalidated, its queue's in-flight buckets are
        abandoned (sole abandon performer — their ledger rows stay
        RUNNING, so once the dead queue stops shadowing them the
        pending view resurfaces exactly the orphaned invocations), and
        its unit assignments are cleared so ``_route`` re-places them
        on the survivors, whose pools re-materialize any orphaned pages
        on first touch.  Returns the number of abandoned buckets."""
        topo = self.topology
        if host_id in topo.dead:
            return 0
        topo.kill(host_id)
        q = state.queues.pop(host_id, None)
        orphans = q.abandon() if q is not None else []
        for u in [u for u, h in state.assignment.items() if h == host_id]:
            del state.assignment[u]
        info = state.info.topology
        info.host_losses += 1
        info.lost_buckets += len(orphans)
        state.info.pages = topo.page_stats()
        return len(orphans)

    # ---- the stream scheduler -----------------------------------------
    @traced_step
    def step(self, state: TopologyDrainState) -> bool:
        """Advance ONE host stream by one wave (round-robin); False once
        no host has pending or in-flight work.  Every step first books
        any landed buckets on any host (non-blocking) and hedges any
        overdue ones, so harvest is interleaved with — and overlapped
        by — dispatch on other hosts."""
        book = lambda pb, res, el: self._book_harvest(state, pb, res, el)
        for q in state.queues.values():
            q.harvest_ready(book)
        self._maybe_hedge(state)
        groups = state.plan.pending_by_bucket(
            exclude=state.in_flight_entries())
        gate_wait = None
        if state.retry_at:              # failed rows awaiting backoff
            gated = {}
            for key in list(groups):
                ents, wait = self._backoff_filter(state, groups[key])
                if wait is not None:
                    gate_wait = wait if gate_wait is None \
                        else min(gate_wait, wait)
                if ents:
                    gated[key] = ents
            groups = gated
        ids = [h.host_id for h in self.topology.alive()
               if h.host_id in state.queues]
        n = len(ids)
        if not groups:
            if not n:
                return False
            # nothing dispatchable: drain in-flight work.  A blocking
            # harvest would sleep out a held straggler's hold and
            # defeat the hedge race, so with hedging armed poll instead
            if self._hedge_armed(state) \
                    and any(not state.queues[h].empty for h in ids):
                if not sum(state.queues[h].harvest_ready(book)
                           for h in ids):
                    time.sleep(0.001)
                return True
            for off in range(n):
                h = ids[(state.cursor + off) % n]
                if state.queues[h].harvest_next(book):
                    state.cursor = (state.cursor + off + 1) % n
                    return True
            if gate_wait is not None:   # only backoff gates remain
                time.sleep(min(gate_wait, 0.05))
                return True
            return False
        units = self._units(groups)
        by_host = self._route(state, units)   # retries may resurface units
        for off in range(n):
            h = ids[(state.cursor + off) % n]
            mine = by_host.get(h, [])
            if not mine and self.pool.steal:
                mine = self._try_steal(state, units, by_host, h)
            if not mine:
                continue
            self._host_wave(state, h, mine, units)
            state.cursor = (state.cursor + off + 1) % n
            return True
        return False
