"""Device-resident feature-page pool (ISSUE 3 tentpole, compile layer).

The megabatch programs consume *feature pages*: one (N_pad, P_pad)
zero-padded copy of a request's X matrix per bucket shape.  Before this
module the pages were re-stacked on the host and re-transferred
host->device on every drain — for steady-state serving (the same datasets
estimated over and over) that round-trip is pure waste, and it is exactly
the transfer the paper's Lambda workers avoid by caching their S3 pull.

``PagePool`` keeps pages resident on device across drains:

  * pages are keyed by ``(data fingerprint, N_pad, P_pad)`` — pure value
    identity, like the ``ProgramCache``, so repeat traffic (same dataset
    content, any request object) hits without transfer;
  * per launch the pool assembles the (D, N_pad, P_pad) page stack by
    *lane assignment on device*: resident pages are gathered into lanes
    (a device-side copy, no host round-trip), newly admitted requests'
    pages transfer once and join in place, and the assembled stack —
    itself a materialized device array — is cached by its lane
    composition, so steady-state traffic re-presents the same composition
    and gets the **same array object** back: a warm drain performs zero
    transfers and zero copies;
  * an LRU byte budget bounds device residency of pages *and* cached
    stacks: stacks evict first (rebuildable without any host round-trip),
    then least-recently-used pages; a later request for an evicted page
    pays one re-transfer.

Keeping D equal to the launch's own page count (pow2-bucketed), rather
than the pool's total, keeps compiled program shapes independent of pool
history — part of the bitwise schedule-invariance contract.

``PageStats`` feeds the session telemetry and BENCH_asyncdrain.json
(hit rate, bytes transferred vs saved, evictions, stack reuse).

Multi-host (ISSUE 4): one ``PagePool`` per host mesh, all sharing a
``PageDirectory`` — the cluster-wide fingerprint map of which hosts hold
which pages.  A host that misses locally but whose directory names a
peer holder fetches the page device-to-device (cheaper than the host
round-trip, and accounted separately as a *cross-host transfer*); the
topology layer's placement policy exists to make those fetches converge
to zero by routing each bucket to the host already holding its pages.
``resident`` / ``stack_cached`` are the residency probes that policy
scores hosts with.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.registry import warm_cache
from repro.core.crossfit import pow2_bucket

# page identity: (data fingerprint, n_pad, p_pad)
PageKey = Tuple[object, int, int]

DEFAULT_BYTE_BUDGET = 256 * 1024 * 1024
MAX_CACHED_STACKS = 128


@dataclass
class PageStats:
    """Hit/miss/transfer accounting across drains.

    A *cross-host fetch* is a local miss served device-to-device from a
    peer pool instead of the host round-trip: it counts as a miss for
    this pool's hit rate, its bytes land in ``bytes_d2d`` (never
    ``bytes_h2d``), and steady-state topology traffic is gated on it
    reaching zero.
    """
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    stack_builds: int = 0
    stack_hits: int = 0
    bytes_h2d: int = 0                  # host->device page transfers
    bytes_saved: int = 0                # transfers avoided by residency
    cross_host_fetches: int = 0         # misses served from a peer pool
    bytes_d2d: int = 0                  # device->device cross-host bytes

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def summary(self) -> Dict:
        return {"page_hits": self.hits, "page_misses": self.misses,
                "page_hit_rate": self.hit_rate,
                "page_evictions": self.evictions,
                "stack_builds": self.stack_builds,
                "stack_hits": self.stack_hits,
                "page_bytes_h2d": self.bytes_h2d,
                "page_bytes_saved": self.bytes_saved,
                "cross_host_fetches": self.cross_host_fetches,
                "page_bytes_d2d": self.bytes_d2d}

    # snapshot/delta/merge iterate the dataclass fields so a counter
    # added above is automatically carried through all three
    def snapshot(self) -> "PageStats":
        return dataclasses.replace(self)

    def delta(self, since: "PageStats") -> "PageStats":
        return PageStats(*(getattr(self, f.name) - getattr(since, f.name)
                           for f in dataclasses.fields(self)))

    def merge(self, other: "PageStats") -> "PageStats":
        """Aggregate two pools' accounting (topology-wide telemetry)."""
        return PageStats(*(getattr(self, f.name) + getattr(other, f.name)
                           for f in dataclasses.fields(self)))


class PageDirectory:
    """Cluster-wide fingerprint directory over per-host ``PagePool``s.

    Maps every page key to the set of hosts currently holding it, and
    brokers device-to-device fetches between pools: a host that misses
    locally asks the directory, which hands back a peer's resident array
    (the caller places it on its own device).  Pure bookkeeping plus the
    fetch counters the topology acceptance gates read — placement policy
    is the caller's job (sharding/policy.py).
    """

    def __init__(self):
        self._holders: Dict[PageKey, Set[int]] = {}
        self._pools: Dict[int, "PagePool"] = {}
        self.fetches = 0                # cross-host page fetches brokered
        self.bytes_fetched = 0

    def attach(self, pool: "PagePool") -> None:
        self._pools[pool.host_id] = pool

    def detach(self, pool: "PagePool") -> None:
        """Withdraw a dead host: drop it from the pool map and purge it
        from every holder set so no d2d fetch is ever brokered against
        unreachable device memory (host-loss recovery)."""
        self._pools.pop(pool.host_id, None)
        for pkey in list(self._holders):
            self.unregister(pkey, pool.host_id)

    def register(self, pkey: PageKey, host_id: int) -> None:
        self._holders.setdefault(pkey, set()).add(host_id)

    def unregister(self, pkey: PageKey, host_id: int) -> None:
        holders = self._holders.get(pkey)
        if holders is not None:
            holders.discard(host_id)
            if not holders:
                del self._holders[pkey]

    def holders(self, pkey: PageKey) -> frozenset:
        return frozenset(self._holders.get(pkey, ()))

    def fetch(self, pkey: PageKey, requester: int):
        """A peer's resident page array, or None if no peer holds it.
        Deterministic source choice (lowest holder id); does not touch
        the source pool's LRU order."""
        for hid in sorted(self._holders.get(pkey, ())):
            if hid == requester:
                continue
            src = self._pools.get(hid)
            page = src._pages.get(pkey) if src is not None else None
            if page is not None:
                self.fetches += 1
                self.bytes_fetched += src._nbytes[pkey]
                return page
        return None


class PagePool:
    """LRU pool of device-resident padded feature pages.

    One instance per backend (it sits next to the backend's
    ``ProgramCache`` and persists across drains).  ``byte_budget`` counts
    the canonical page entries; assembled stacks are composition-keyed
    views capped at ``MAX_CACHED_STACKS`` entries.

    Topology mode: one pool per host mesh, identified by ``host_id``,
    pinned to that host's lead ``device``, and registered with the shared
    ``PageDirectory`` — local misses then try a device-to-device fetch
    from a peer holder before paying the host round-trip.
    """

    def __init__(self, byte_budget: int = DEFAULT_BYTE_BUDGET, *,
                 host_id: int = 0, directory: Optional[PageDirectory] = None,
                 device=None):
        self.byte_budget = int(byte_budget)
        self.host_id = host_id
        self.directory = directory
        self.device = device
        if directory is not None:
            directory.attach(self)
        self.stats = PageStats()
        self._pages: "OrderedDict[PageKey, object]" = OrderedDict()
        self._nbytes: Dict[PageKey, int] = {}
        self._page_bytes = 0
        # (tuple of page keys, d_pad) -> stacked device array
        self._stacks: "OrderedDict[Tuple, object]" = OrderedDict()
        self._stacks_of: Dict[PageKey, Set[Tuple]] = {}
        self._stack_bytes = 0

    # ------------------------------------------------------------------
    @staticmethod
    def page_key(req, n_pad: int, p_pad: int) -> PageKey:
        return (req.data_key, n_pad, p_pad)

    @property
    def n_pages(self) -> int:
        return len(self._pages)

    # ---- residency probes (placement policy, sharding/policy.py) -----
    def resident(self, pkey: PageKey) -> bool:
        """Membership test without touching LRU order or stats."""
        return pkey in self._pages

    def stack_cached(self, pkeys: Sequence[PageKey]) -> bool:
        """Whether the lane composition is launch-ready with zero
        copies: a singleton composition's launch array IS its resident
        page; multi-lane compositions need their assembled stack."""
        pkeys = tuple(pkeys)
        if len(pkeys) == 1:
            return pkeys[0] in self._pages
        return (pkeys, pow2_bucket(len(pkeys), 1)) in self._stacks

    @property
    def total_bytes(self) -> int:
        """Device bytes held: canonical pages + materialized stacks."""
        return self._page_bytes + self._stack_bytes

    # ------------------------------------------------------------------
    def _put(self, arr):
        """Place an array on this pool's host device (default placement
        when the pool is not device-pinned)."""
        if self.device is not None:
            return jax.device_put(arr, self.device)
        return jnp.asarray(arr)

    def _page(self, pkey: PageKey, req, n_pad: int, p_pad: int):
        """The request's device-resident padded page, shaped
        ``(1, n_pad, p_pad)`` so a singleton launch can consume it
        directly with zero copies; a local miss tries a device-to-device
        fetch from a peer pool (directory) before paying the
        host->device transfer, inside a ``pages.fetch`` span whose
        ``source`` says which (``d2d`` or ``h2d``)."""
        page = self._pages.get(pkey)
        nbytes = n_pad * p_pad * 4
        if page is not None:
            self._pages.move_to_end(pkey)
            self.stats.hits += 1
            self.stats.bytes_saved += nbytes
            return page
        self.stats.misses += 1
        with obs.span("pages.fetch", bytes=nbytes) as sp:
            peer = self.directory.fetch(pkey, self.host_id) \
                if self.directory is not None else None
            if peer is not None:
                sp.set(source="d2d")
                page = self._put(peer)              # d2d cross-host copy
                self.stats.cross_host_fetches += 1
                self.stats.bytes_d2d += nbytes
            else:
                sp.set(source="h2d")
                x = np.asarray(req.x, np.float32)
                host = np.zeros((1, n_pad, p_pad), np.float32)
                host[0, :x.shape[0], :x.shape[1]] = x
                page = self._put(host)              # the one h2d copy
                self.stats.bytes_h2d += nbytes
        self._pages[pkey] = page
        self._nbytes[pkey] = nbytes
        self._page_bytes += nbytes
        if self.directory is not None:
            self.directory.register(pkey, self.host_id)
        return page

    def _drop_stack(self, skey: Tuple):
        stack = self._stacks.pop(skey, None)
        if stack is not None:
            self._stack_bytes -= int(stack.size) * 4
        for pk in skey[0]:
            self._stacks_of.get(pk, set()).discard(skey)

    def _evict_lru(self, keep: Set[PageKey], keep_stack: Tuple = None):
        """Shrink to the byte budget: drop LRU cached stacks first (they
        rebuild without any host round-trip), then evict LRU pages (never
        ones needed by the in-flight launch), dropping their stacks."""
        while self._stack_bytes + self._page_bytes > self.byte_budget:
            victim = next((sk for sk in self._stacks if sk != keep_stack),
                          None)
            if victim is None:
                break
            self._drop_stack(victim)
        for pkey in list(self._pages):
            if self.total_bytes <= self.byte_budget:
                return
            if pkey in keep:
                continue
            self._pages.pop(pkey)
            self._page_bytes -= self._nbytes.pop(pkey)
            self.stats.evictions += 1
            if self.directory is not None:
                self.directory.unregister(pkey, self.host_id)
            for skey in list(self._stacks_of.pop(pkey, ())):
                self._drop_stack(skey)

    def invalidate(self) -> None:
        """Host loss: drop every resident page and stack and withdraw
        from the cluster directory.  Surviving hosts re-materialize any
        page they need from host memory (``_page`` falls through to the
        h2d path once no peer holds the key) — the orphaned work itself
        is re-placed by the topology backend, not by the pool."""
        if self.directory is not None:
            self.directory.detach(self)
        self._pages.clear()
        self._nbytes.clear()
        self._page_bytes = 0
        self._stacks.clear()
        self._stacks_of.clear()
        self._stack_bytes = 0

    # ------------------------------------------------------------------
    # page contents are pinned by the PageKeys inside ``needs`` (a
    # page_key embeds the request's data_key); the composition cache
    # and residency maps live on this pool instance (ambient)
    @warm_cache(name="page_pool_stacks", key=("needs", "n_pad", "p_pad"),
                ambient=("self",))
    def stack(self, needs: Sequence[Tuple[PageKey, object]],
              n_pad: int, p_pad: int):
        """Assemble the (D, N_pad, P_pad) stack for one launch.

        ``needs`` is ``[(page_key, request), ...]`` in lane order (lane i
        = needs[i]); D is pow2 of the lane count.

        Singleton launches (per-block dispatch: one need per launch)
        consume the resident ``(1, N_pad, P_pad)`` page **directly** —
        no copy, no second device allocation, no cache entry beyond the
        page itself; a repeat composition is booked as a stack hit
        because the launch array was served with zero copies.  The
        multi-lane path below serves **fused launches** (ISSUE 5): a
        multi-request same-shape group hands its union composition here,
        pays one concatenation cold, and every warm repeat of the same
        composition gets the identical materialized stack back — the
        fused hot path is zero-copy exactly like the singleton one.
        """
        if len(needs) == 1:
            pk, req = needs[0]
            was_resident = pk in self._pages
            page = self._page(pk, req, n_pad, p_pad)
            if was_resident:
                self.stats.stack_hits += 1
            else:
                self.stats.stack_builds += 1
                self._evict_lru(keep={pk})
            return page
        pkeys = tuple(pk for pk, _ in needs)
        d_pad = pow2_bucket(max(len(pkeys), 1), 1)
        skey = (pkeys, d_pad)
        cached = self._stacks.get(skey)
        if cached is not None and all(pk in self._pages for pk in pkeys):
            self._stacks.move_to_end(skey)
            self.stats.stack_hits += 1
            for pk, req in needs:                   # LRU touch + accounting
                self._pages.move_to_end(pk)
                self.stats.hits += 1
                self.stats.bytes_saved += n_pad * p_pad * 4
            return cached
        lanes = [self._page(pk, req, n_pad, p_pad) for pk, req in needs]
        if d_pad > len(lanes):
            zero = self._put(jnp.zeros((1, n_pad, p_pad), np.float32))
            lanes = lanes + [zero] * (d_pad - len(lanes))
        stack = jnp.concatenate(lanes)
        self.stats.stack_builds += 1
        self._stacks[skey] = stack
        self._stack_bytes += d_pad * n_pad * p_pad * 4
        for pk in pkeys:
            self._stacks_of.setdefault(pk, set()).add(skey)
        while len(self._stacks) > MAX_CACHED_STACKS:
            self._drop_stack(next(iter(self._stacks)))
        self._evict_lru(keep=set(pkeys), keep_stack=skey)
        return stack
