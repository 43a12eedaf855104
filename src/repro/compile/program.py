"""Megabatch program build, cache, and execution.

A **program** is one jitted function per (bucket, padded batch shape):

    run(pages (D, N_pad, P_pad), data_idx (B,), y (B, N_pad),
        w (B, N_pad), valid (B, N_pad), key_data (B, ...)) -> (B, N_pad)

It gathers every task's feature page, rebuilds the per-task typed PRNG
keys, and calls the learner family's ``batched_fit_predict`` — on the
linear/ridge path that bottoms out in the fused Pallas kernels
(``batched_gram`` / ``batched_predict`` in kernels/ops.py).  The batch
axis B is wave-capacity-aligned (``aligned_bucket``: multiples of the
lane quantum, so steady traffic lands on the same few shapes with <1
quantum of waste) and the page axis D is pow2-bucketed, so repeat traffic
of *any* composition hits a previously-compiled program: the warm cache
is keyed by spec, never by object identity or request.  Feature pages
come from the device-resident ``PagePool`` (pages.py) when the backend
passes one — warm drains then perform zero host->device page transfer.

**Same-shape block fusion** (ISSUE 5 tentpole): equal-canonical-B blocks
from *different* requests pack into ONE device launch via a leading
block axis —

    run_fused(pages (D, N_pad, P_pad), data_idx (G, B), y (G, B, N_pad),
              ... ) -> (G, B, N_pad)

implemented as ``lax.map`` of the single-block body over axis 0, with
the G blocks sharing one union page stack (the ``PagePool`` multi-lane
composition cache, so warm fused launches are zero-copy).  ``lax.map``
— not ``vmap`` — is the float-pinning choice: the mapped body compiles
to exactly the single-block computation, so fused launches are
**bitwise-identical** to per-block launches for every learner family
(vmap's extra leading dim lets XLA retile reductions, ~1e-7 drift;
verified and CI-gated in tests/test_compile.py).  Each task's compiled
B stays pinned to its own request's canonical grid — fusion only
changes how many blocks ride per launch, never a block's shape.

**Non-blocking dispatch**: ``dispatch_bucket`` launches a bucket's
blocks and returns an in-flight ``BucketDispatch`` holding the raw
``jax.Array`` handles — no ``block_until_ready``.  The backends queue
these (serverless/dispatch.py) and harvest only when a ledger's buckets
must complete, so host-side booking, placement, stealing, admission,
and autoscaling overlap device execution.  ``run_bucket`` remains the
synchronous wrapper (dispatch + harvest in one call).

``ProgramCache`` owns the programs plus hit/miss/padding accounting; the
execution backends (serverless/backends.py) hold one instance each and
stay warm across ``run_requests`` calls.  An optional ``partition`` hook
wraps the program body before jit — ShardedBackend passes a shard_map
over the batch axis (sharding/policy.py::megabatch_specs); partitioned
programs never fuse (the specs map one block's operands).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.analysis.registry import warm_cache
from repro.core.crossfit import PaddingStats, aligned_bucket, pow2_bucket
from repro.compile.buckets import (BucketKey, Entry, MegabatchPlan,
                                   pack_tail_blocks)
from repro.compile.pages import PagePool
from repro.compile.persist import (PersistentProgramCache, backend_platform,
                                   configure_compilation_cache,
                                   default_persist, jax_build, pin_executable,
                                   program_avals, program_fingerprint)
from repro.learners import as_batched, get_batched_learner
from repro.runtime import bounded_put


@dataclass
class CompileStats:
    """Warm-cache and padding accounting across program launches.

    ``launches`` counts device dispatches; ``blocks`` counts the
    canonical blocks they carried — ``blocks > launches`` is same-shape
    fusion at work (``fused_launches`` of them carried 2+ launch
    blocks).  ``coalesced_blocks`` counts canonical tail blocks that
    rode a *combined* launch block (cross-shape coalescing);
    ``disk_hits``/``disk_misses`` track the persistent program cache —
    a disk hit deserializes an executable instead of compiling, so it
    does NOT count as a compile (``misses``)."""
    hits: int = 0
    misses: int = 0
    disk_hits: int = 0
    disk_misses: int = 0
    launches: int = 0
    blocks: int = 0
    fused_launches: int = 0
    coalesced_blocks: int = 0
    padding: PaddingStats = field(default_factory=PaddingStats)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def summary(self) -> Dict:
        return {"programs_compiled": self.misses,
                "cache_hits": self.hits,
                "cache_hit_rate": self.hit_rate,
                "disk_hits": self.disk_hits,
                "disk_misses": self.disk_misses,
                "launches": self.launches,
                "blocks": self.blocks,
                "fused_launches": self.fused_launches,
                "coalesced_blocks": self.coalesced_blocks,
                "padding_waste_frac": self.padding.waste_frac,
                "padding_waste_b_frac": self.padding.b_waste_frac,
                "padding_waste_b_morphed_frac":
                    self.padding.b_waste_frac_morphed,
                "padding_waste_n_frac": self.padding.n_waste_frac,
                "padding_waste_p_frac": self.padding.p_waste_frac,
                "tasks": self.padding.tasks,
                "padded_tasks": self.padding.padded_tasks}


def segment_batched_fn(seg) -> Callable:
    """Resolve a segment's megabatch implementation: registry learners get
    their native batched form, opaque callables the vmap adapter."""
    if seg.learner is not None:
        return get_batched_learner(seg.learner, dict(seg.params))
    return as_batched(seg.learner_fn)


class ProgramCache:
    """Spec-keyed cache of compiled megabatch programs.

    Keys are ``(BucketKey, B_pad, D_pad)`` — pure value identity, so two
    requests built from equal plans share programs, and a session's
    repeat traffic never re-traces.

    When a ``PersistentProgramCache`` is attached (default: the
    environment-configured one, see ``persist.ENV_CACHE_DIR``), an
    in-memory miss consults the disk before tracing: spec-identified,
    unpartitioned programs are AOT-compiled against their exact avals,
    serialized to disk on first compile, and deserialized (~14x cheaper
    than compiling here) by later processes — a disk-warm cold drain
    compiles zero programs.

    Donation: the megabatch output ``(…, B, N_pad) f32`` is shape- and
    dtype-identical to the ``y`` operand, so ``y`` (argnum 2) is donated
    and XLA writes the predictions in place.  The page stack is NEVER
    donated — the device-resident ``PagePool`` retains and reuses those
    buffers across launches.
    """

    def __init__(self, partition: Optional[Callable] = None,
                 persist: object = "auto",
                 partition_fused: Optional[Callable] = None,
                 partition_axes: Optional[Tuple] = None):
        configure_compilation_cache()
        self._programs: Dict[Tuple, Callable] = {}
        self.partition = partition
        # ISSUE 8: shard_map transform for the *fused* calling convention
        # (leading block axis G replicated, task axis sharded).  When
        # set, partitioned buckets fuse again — the per-shard body is the
        # unsharded lax.map program, so fused sharded launches stay
        # bitwise-equal to per-block unsharded ones.  partition_axes
        # names the mesh axes (and their sizes) the transform closes
        # over; it is part of the program cache key because two meshes
        # with different shard counts compile different programs.
        self.partition_fused = partition_fused
        self.partition_axes = tuple(partition_axes) if partition_axes \
            else None
        self.persist: Optional[PersistentProgramCache] = \
            default_persist() if persist == "auto" else persist
        self.stats = CompileStats()

    @property
    def fuses(self) -> bool:
        """Whether this cache runs same-shape fused launches: an
        unpartitioned cache always does, and a partitioned one when it
        carries the sharded-fused transform (ISSUE 8: shard_map around
        the lax.map fused body); a partition-only cache maps
        single-block operands."""
        return self.partition is None or self.partition_fused is not None

    def _disk(self, key: BucketKey):
        """(persist, fingerprint-builder inputs) when this program may be
        persisted: spec-identified learners only, never partitioned
        programs (shard_map closes over mesh state the serialized
        executable would not carry)."""
        if self.persist is None or self.partition is not None \
                or self.partition_fused is not None:
            return None
        return self.persist

    def _disk_lookup(self, fp):
        prog = self.persist.lookup(jax_build(), backend_platform(), fp)
        if prog is not None:
            self.stats.disk_hits += 1
        else:
            self.stats.disk_misses += 1
        return prog

    def _compile_persistable(self, run, fp, key, b_pad, d_pad, g=None):
        """AOT-compile at exact avals and serialize to disk.  The
        returned executable is operand-pinned (``pin_executable``):
        unlike jit dispatch, a direct AOT call does not keep the
        caller's host operands alive while it reads them
        asynchronously."""
        compiled = jax.jit(run, donate_argnums=(2,)).lower(
            *program_avals(key, b_pad, d_pad, g)).compile()
        self.persist.store(jax_build(), backend_platform(), fp, compiled)
        return pin_executable(compiled)

    # BucketKey pins the segment's (learner, params) and padded shapes,
    # which fully determine the batched fn the thunk builds — hence
    # covers={"key": ("fn_thunk",)}; the cache dict lives on this
    # ProgramCache instance, so instance state is ambient.
    @warm_cache(name="program_cache", key=("key", "b_pad", "d_pad"),
                reads=("fn_thunk",), covers={"key": ("fn_thunk",)},
                ambient=("self",))
    def program(self, key: BucketKey, b_pad: int, d_pad: int,
                fn_thunk: Callable[[], Callable]) -> Callable:
        pkey = (key, b_pad, d_pad)
        prog = self._programs.get(pkey)
        if prog is not None:
            self.stats.hits += 1
            return prog
        with obs.span("program.build", tier="disk") as sp:
            fp = program_fingerprint(key, b_pad, d_pad) \
                if self._disk(key) is not None else None
            if fp is not None:
                prog = self._disk_lookup(fp)
                if prog is not None:
                    self._programs[pkey] = prog
                    return prog
            sp.set(tier="compile")
            self.stats.misses += 1
            batched_fn = fn_thunk()

            def run(pages, data_idx, y, w, valid, key_data):
                xb = pages[data_idx]                       # (B, N_pad, P_pad)
                keys = jax.random.wrap_key_data(key_data)  # (B,) typed keys
                return batched_fn(xb, y, w, valid, keys)

            if self.partition is not None:
                prog = jax.jit(self.partition(run))
            elif fp is not None:
                prog = self._compile_persistable(run, fp, key, b_pad, d_pad)
            else:
                prog = jax.jit(run, donate_argnums=(2,))
            self._programs[pkey] = prog
        return prog

    @warm_cache(name="fused_program_cache",
                key=("key", "b_pad", "d_pad", "g"),
                reads=("fn_thunk",), covers={"key": ("fn_thunk",)},
                ambient=("self",))
    def fused_program(self, key: BucketKey, b_pad: int, d_pad: int,
                      g: int, fn_thunk: Callable[[], Callable]) -> Callable:
        """One launch carrying ``g`` same-shape blocks over a shared
        union page stack: ``lax.map`` of the single-block body over the
        leading block axis.  lax.map (not vmap) is the float pinning —
        the mapped body is compiled exactly as the single-block program,
        so fused results are bitwise-equal to per-block launches."""
        pkey = (key, b_pad, d_pad, g)
        prog = self._programs.get(pkey)
        if prog is not None:
            self.stats.hits += 1
            return prog
        with obs.span("program.build", tier="disk") as sp:
            fp = program_fingerprint(key, b_pad, d_pad, g) \
                if self._disk(key) is not None else None
            if fp is not None:
                prog = self._disk_lookup(fp)
                if prog is not None:
                    self._programs[pkey] = prog
                    return prog
            sp.set(tier="compile")
            self.stats.misses += 1
            batched_fn = fn_thunk()

            def run_one(pages, data_idx, y, w, valid, key_data):
                xb = pages[data_idx]
                keys = jax.random.wrap_key_data(key_data)
                return batched_fn(xb, y, w, valid, keys)

            def run_fused(pages, data_idx, y, w, valid, key_data):
                return jax.lax.map(lambda t: run_one(pages, *t),
                                   (data_idx, y, w, valid, key_data))

            if fp is not None:
                prog = self._compile_persistable(run_fused, fp, key, b_pad,
                                                 d_pad, g)
            else:
                prog = jax.jit(run_fused, donate_argnums=(2,))
            self._programs[pkey] = prog
        return prog

    # The sharded-fused program closes over the mesh the partition_fused
    # transform was built with, so the mesh axes (names + sizes) join the
    # cache key — same bucket on a differently-sized mesh is a different
    # program.  Never persisted to disk (the serialized executable would
    # not carry the mesh), which _disk() enforces.
    @warm_cache(name="sharded_fused_program_cache",
                key=("key", "b_pad", "d_pad", "g", "self.partition_axes"),
                reads=("fn_thunk",), covers={"key": ("fn_thunk",)},
                ambient=("self",))
    def sharded_fused_program(self, key: BucketKey, b_pad: int, d_pad: int,
                              g: int,
                              fn_thunk: Callable[[], Callable]) -> Callable:
        """The fused launch shard_mapped over the host mesh (ISSUE 8):
        ``shard_map`` *around* the ``lax.map`` fused body, task axis
        sharded and the block axis G replicated
        (``megabatch_specs(fused=True)``), lifting the PR 5 "sharded
        caches never fuse" restriction.  Each shard compiles the SAME
        lax.map body as the unsharded fused program over its B/m lane
        slice — the structural contract audited by
        analysis/jaxpr_audit.py (sharded-fused-wraps-scan).  Parity vs
        the unsharded fused launch is bitwise on a 1-device mesh; on an
        m-way mesh XLA may retile reductions at the smaller compiled
        B/m (measured: B-invariance holds down to 16 lanes, not below),
        so multi-device results sit in the same ~1e-6 float tier as the
        unfused sharded path — verified per family by
        tests/test_compile.py::test_sharded_fused_launch_bitwise_parity.
        The win is launch count: partitioned drains now pack blocks
        into fused launches instead of one launch per block."""
        pkey = (key, b_pad, d_pad, g, ("mesh",) + self.partition_axes)
        prog = self._programs.get(pkey)
        if prog is not None:
            self.stats.hits += 1
            return prog
        with obs.span("program.build", tier="compile"):
            self.stats.misses += 1
            batched_fn = fn_thunk()

            def run_one(pages, data_idx, y, w, valid, key_data):
                xb = pages[data_idx]
                keys = jax.random.wrap_key_data(key_data)
                return batched_fn(xb, y, w, valid, keys)

            def run_fused(pages, data_idx, y, w, valid, key_data):
                return jax.lax.map(lambda t: run_one(pages, *t),
                                   (data_idx, y, w, valid, key_data))

            prog = jax.jit(self.partition_fused(run_fused))
            self._programs[pkey] = prog
        return prog


# A launch carries at most B_BLOCK task lanes.  The compiled B is part
# of the determinism contract: per-lane floats are independent of lane
# position and of the *other lanes' contents* (verified per family by
# tests/test_compile.py::test_tail_launch_b_invariance).  Whether they
# depend on the compiled B itself is a *per-family, per-platform*
# property (XLA reduction tiling CAN retile across B): families listed
# in MORPH_BITWISE_FAMILIES below are proven **compiled-B invariant** —
# the same lane content launched at B=16 and B=32 is bitwise-equal —
# by the parametrized morph gate in tests/test_compile.py and a
# structural check in analysis/jaxpr_audit.py.  For those families a
# task's launch B is a scheduling degree of freedom; for everything
# else (opaque callables, unproven families) it must stay a pure
# function of the task's own request.  Within each (request, segment),
# the segment's flat tasks in ascending order split into **canonical
# blocks** of B_BLOCK tasks, and a block's canonical size — full blocks
# at B_BLOCK, the tail at its sublane-aligned count — is what launches
# even when a capacity-limited wave executes only part of it (the
# missing lanes ride as padding; lane-content independence makes the
# result identical to the full-block launch).  Flat task ids are
# scaling-level-invariant, so per-split and per-fold scaling also
# compile identical launch shapes.
#
# **Cross-shape coalescing** (ISSUE 7 tentpole): for morph-proven
# families the scheduler goes one step further — canonical *tail*
# blocks (b_pad < B_BLOCK) from different requests pack
# lane-contiguously into one combined launch block
# (buckets.pack_tail_blocks), and when a bucket is still left with
# mixed shapes under fusion, the smaller blocks morph UP to the largest
# b_pad so the whole bucket rides one lax.map launch.  Packing is
# deterministic (first-fit in block order) and bitwise-neutral by the
# proven B-invariance + lane-content independence; families outside the
# bitwise set never morph.
#
# This replaces the PR-3 rule that padded *every* launch up to B_BLOCK:
# constant-shape was sufficient for bitwise invariance but blew B-axis
# waste to ~65% on small-bucket traffic (BENCH_asyncdrain.json) — a
# 12-task bucket burned 20 padding lanes per launch.  Canonical tails
# launch at aligned size instead (12 tasks -> B=16), capping a bucket's
# B waste at the tail block's alignment.  16 for B_BLOCK would cut
# single-request waste further but doubles launch count and halves
# steady throughput on the session benches — 32 is the measured sweet
# spot.
#
# Caveat: partitioned paths agree with the unsharded schedulers to
# float tolerance (~1e-6) on multi-device meshes, bitwise only on a
# 1-device mesh.  For the *unfused* sharded path the cause is shard_map
# retiling the batched learner's B-axis reductions; the *sharded-fused*
# path (ISSUE 8) wraps the lax.map fused body so each shard runs the
# per-lane program unchanged (structurally audited), but it compiles
# that body at B/m lanes and compiled-B invariance only holds down to
# 16 lanes on this platform — below that XLA retiles and the same
# ~1e-6 tier applies.  Verified per family by the sharded-fused parity
# gate in tests/test_compile.py.
B_BLOCK = 32

# Families with a standing bitwise compiled-B invariance proof on this
# backend: the same lane content produces bit-identical floats at any
# aligned launch B.  Enforced empirically (per-family parametrized gate,
# tests/test_compile.py) and structurally (analysis/jaxpr_audit.py
# morph audit, which also requires every registered family to be here);
# the coalescing scheduler only morphs these.
MORPH_BITWISE_FAMILIES = frozenset(
    {"ols", "ridge", "lasso", "logistic", "kernel_ridge", "mlp"})


def bucket_family(key: BucketKey) -> Optional[str]:
    """Learner family name of a spec-identified bucket, else None."""
    ident = key.learner
    if isinstance(ident, tuple) and len(ident) == 2 \
            and isinstance(ident[0], str) and ident[0] != "opaque":
        return ident[0]
    return None


def morph_allowed(key: BucketKey) -> bool:
    """May this bucket's tail blocks be coalesced/morphed?  Bitwise
    families only; any other family and opaque callables never."""
    return bucket_family(key) in MORPH_BITWISE_FAMILIES


@dataclass
class _Block:
    """One canonical launch block, stacked and ready to launch."""
    ri: int
    si: int
    members: List[Tuple[int, int, int]]   # (flat task, inv, row-in-inv)
    b_pad: int
    k: int                                # real task lanes
    n: int                                # true N of the request
    p: int                                # true P of the request
    tpi: int                              # rows per invocation buffer


@dataclass
class _LaunchBlock:
    """One launch-shaped unit: one canonical block at its canonical
    shape (the common case), several tail blocks packed
    lane-contiguously (cross-shape coalescing), or a block morphed up
    to a neighbor's B.  ``offsets[i]`` is the first lane of
    ``parts[i]`` inside the combined (b_pad,) batch axis."""
    parts: List[_Block]
    offsets: List[int]
    b_pad: int
    k: int                                # total real lanes


def _coalesce(blocks: List[_Block], b_block: int, b_align: int,
              morph: bool, fuse: bool) -> List[_LaunchBlock]:
    """Lower canonical blocks to launch blocks.

    Without morphing this is the identity wrapping (every block at its
    own canonical shape).  With morphing: tails pack first-fit into
    combined blocks at one uniform padded size T chosen to minimize
    total padded lanes (buckets.pack_tail_blocks), then — if fusing
    would still face mixed shapes (full blocks vs packed tails) —
    remaining blocks morph up to the largest b_pad so the bucket fuses
    into a single lax.map launch.
    """
    out = [_LaunchBlock([b], [0], b.b_pad, b.k)
           for b in blocks if b.b_pad >= b_block]
    tails = [b for b in blocks if b.b_pad < b_block]
    if not morph or len(tails) <= 1:
        out += [_LaunchBlock([b], [0], b.b_pad, b.k) for b in tails]
    else:
        groups, target = pack_tail_blocks([b.k for b in tails], b_block,
                                          8, b_align)
        for idxs in groups:
            parts = [tails[i] for i in idxs]
            offs, tot = [], 0
            for p in parts:
                offs.append(tot)
                tot += p.k
            out.append(_LaunchBlock(parts, offs, target, tot))
    if morph and fuse and len(out) > 1:
        target = max(lb.b_pad for lb in out)
        out = [lb if lb.b_pad == target else
               _LaunchBlock(lb.parts, lb.offsets, target, lb.k)
               for lb in out]
    return out


@dataclass(eq=False)            # identity equality: comparing in-flight
class Launch:                   # jax arrays elementwise would raise
    """One device dispatch: ``out`` is the raw in-flight ``jax.Array``
    ((B, N_pad) single launch block, (G, B, N_pad) fused)."""
    out: object
    blocks: List[_LaunchBlock]
    fused: bool

    def is_ready(self) -> bool:
        return bool(self.out.is_ready()) if hasattr(self.out, "is_ready") \
            else True


@dataclass(eq=False)            # identity equality (holds Launches)
class BucketDispatch:
    """One bucket slice in flight: every launch its entries need.

    An invocation's rows can straddle two canonical blocks (and so two
    launches with different tail shapes), so booking is only legal once
    ALL launches have landed — ``harvest`` is therefore the bucket-level
    barrier, and the dispatch queue (serverless/dispatch.py) tracks
    these whole, never individual launches.
    """
    key: BucketKey
    launches: List[Launch]
    entries: List[Entry]
    n_tasks: int

    def ready(self) -> bool:
        """Non-blocking poll: have all launches landed on device?"""
        return all(l.is_ready() for l in self.launches)

    def harvest(self) -> Dict[Entry, np.ndarray]:
        """Block until every launch lands; scatter predictions back per
        invocation.  Returns {(req_idx, inv): preds (tpi, n_obs)}."""
        # function-level import: the compile layer must not load the
        # serverless package at module scope (core <-> serverless cycle)
        from repro.serverless.sanitize import check_harvest_once
        check_harvest_once(self)
        results: Dict[Entry, np.ndarray] = {}
        with obs.span("program.harvest", launches=len(self.launches)) as sp:
            d2h = 0
            for launch in self.launches:
                with obs.span("program.wait"):
                    jax.block_until_ready(launch.out)
                out = np.asarray(launch.out, np.float32)
                d2h += out.nbytes
                outs = out if launch.fused else out[None]
                for g, lb in enumerate(launch.blocks):
                    for blk, ofs in zip(lb.parts, lb.offsets):
                        for lane, (_, inv, row) in enumerate(blk.members):
                            buf = results.get((blk.ri, inv))
                            if buf is None:
                                buf = results[(blk.ri, inv)] = \
                                    np.empty((blk.tpi, blk.n), np.float32)
                            buf[row] = outs[g, ofs + lane, :blk.n]
            sp.set(d2h_bytes=d2h)
        return results

    def discard(self) -> None:
        """Retire a cancelled dispatch WITHOUT building results: block
        until the launches land (freeing the runtime's stream in order)
        and drop the handles.  Shares ``harvest``'s arm-once flag, so a
        discarded dispatch can never also be booked — and vice versa:
        the losing leg of a hedge race is structurally unbookable."""
        from repro.serverless.sanitize import check_harvest_once
        check_harvest_once(self)
        for launch in self.launches:
            jax.block_until_ready(launch.out)
        self.launches = []


# Structural cache of per-request block layouts: the canonical-block
# assignment is a pure function of (grid, scaling, segment l_ids,
# invocation subset, b_block, b_align) — steady serving re-lowers
# identical requests every round, and recomputing the rank arithmetic
# per drain was a dominant warm dispatch cost.  Value: a list of
# ((si, block, b_pad, canon_total), members) group descriptors.
_BLOCK_LAYOUT_CACHE: Dict[Tuple, List] = {}
_BLOCK_LAYOUT_CACHE_MAX = 1024


# segment_of_inv and _index_maps are pure functions of (grid, scaling,
# segment l_ids) — all key components — hence covers under req.segments
@warm_cache(name="block_layouts",
            key=("req.grid.n_rep", "req.grid.n_folds",
                 "req.grid.n_nuisance", "req.scaling", "req.segments",
                 "invs", "b_block", "b_align"),
            reads=("req.segment_of_inv", "req._index_maps"),
            covers={"req.segments": ("req.segment_of_inv",
                                     "req._index_maps")})
def _request_block_layout(req, invs: List[int], b_block: int,
                          b_align: int) -> List:
    layout_key = (req.grid.n_rep, req.grid.n_folds, req.grid.n_nuisance,
                  req.scaling,
                  tuple(tuple(sorted(s.l_ids)) for s in req.segments),
                  tuple(invs), b_block, b_align)
    hit = _BLOCK_LAYOUT_CACHE.get(layout_key)
    if hit is not None:
        return hit
    invs_arr = np.asarray(invs, np.int64)
    # exact segment per invocation, one vectorized lookup (robust to two
    # segments of a request collapsing onto one bucket after param
    # resolution)
    sis = req.segment_of_inv(invs_arr)
    tasks_mat = req._index_maps()[0][invs_arr]         # (m, tpi)
    L = req.grid.n_nuisance
    groups: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
    for mi, (inv, si) in enumerate(zip(invs, sis)):
        si = int(si)
        l_ids = sorted(req.segments[si].l_ids)
        pos = {l: i for i, l in enumerate(l_ids)}
        for row, t in enumerate(tasks_mat[mi]):
            t = int(t)
            rank = (t // L) * len(l_ids) + pos[t % L]
            groups.setdefault((si, rank // b_block), []).append(
                (t, int(inv), row))
    out = []
    for (si, block), members in groups.items():
        n_l = len(req.segments[si].l_ids)
        seg_total = req.grid.n_rep * req.grid.n_folds * n_l
        canon = min(b_block, seg_total - block * b_block)
        out.append(((si, block, aligned_bucket(canon, 8, b_align)),
                    members))
    bounded_put(_BLOCK_LAYOUT_CACHE, layout_key, out,
                _BLOCK_LAYOUT_CACHE_MAX)
    return out


def _plan_blocks(plan: MegabatchPlan, key: BucketKey,
                 entries: Sequence[Entry], b_block: int,
                 b_align: int) -> List[_Block]:
    """Group a bucket slice's tasks into canonical launch blocks
    (order = first appearance); the per-request rank arithmetic is
    served from the structural layout cache on repeat traffic."""
    requests = plan.requests
    by_req: Dict[int, List[int]] = {}
    for ri, inv in entries:
        by_req.setdefault(ri, []).append(int(inv))

    blocks: List[_Block] = []
    for ri, invs in by_req.items():
        req = requests[ri]
        n = int(req.ledger.n_obs)
        p = int(req.x.shape[1])
        tpi = req.grid.tasks_per_invocation(req.scaling)
        for (si, block, b_pad), members in \
                _request_block_layout(req, invs, b_block, b_align):
            blocks.append(_Block(ri=ri, si=si, members=members,
                                 b_pad=b_pad, k=len(members),
                                 n=n, p=p, tpi=tpi))
    return blocks


# Content-keyed cache of stacked block tensors: a block's (y, w, valid,
# key_data) stack is a pure function of the request's ``work_key`` (set
# by the front-end when the tensors' provenance is fully pinned — the
# FULL data content, not just the feature page) and the block's lane
# content — steady serving re-lowers identical requests every round,
# and re-gathering/zero-padding the same tensors was a dominant warm
# dispatch cost.  Entries are marked read-only.  Unlike the small
# metadata caches this one holds real arrays, so it is bounded by
# BYTES (FIFO eviction), the same discipline as the PagePool.
_BLOCK_TENSOR_CACHE: Dict[Tuple, Tuple] = {}
_BLOCK_TENSOR_CACHE_BYTES = 256 * 1024 * 1024
_block_tensor_bytes = 0


# work_key pins the FULL data content plus plan structure (the PR 5
# staleness fix), which determines the wave arrays and key-data tables;
# a block's lane count k is determined by its member list
@warm_cache(name="block_tensors",
            key=("req.work_key", "seg_idx", "blk.members", "blk.b_pad",
                 "n_pad"),
            reads=("req.wave_arrays", "req.task_key_data", "blk.k",
                   "blk.n"),
            covers={"req.work_key": ("req.wave_arrays",
                                     "req.task_key_data", "blk.n"),
                    "blk.members": ("blk.k",)})
def _block_tensors(req, seg_idx: int, blk: _Block, n_pad: int):
    """Stack one block's task tensors at its canonical padded shape."""
    global _block_tensor_bytes
    tasks_t = tuple(t for t, _, _ in blk.members)
    ck = None
    if req.work_key is not None:
        ck = (req.work_key, seg_idx, tasks_t, blk.b_pad, n_pad)
        hit = _BLOCK_TENSOR_CACHE.get(ck)
        if hit is not None:
            return hit
    tasks = np.asarray(tasks_t, np.int64)
    ye, we = req.wave_arrays(tasks)
    kde = req.task_key_data(seg_idx, tasks)
    k, b_pad, n = blk.k, blk.b_pad, blk.n
    y = np.zeros((b_pad, n_pad), np.float32)
    w = np.zeros((b_pad, n_pad), np.float32)
    valid = np.zeros((b_pad, n_pad), np.float32)
    kd = np.zeros((b_pad,) + kde.shape[1:], kde.dtype)
    y[:k, :n] = ye
    w[:k, :n] = we
    valid[:k, :n] = 1.0
    kd[:k] = kde
    if ck is not None:
        nbytes = y.nbytes + w.nbytes + valid.nbytes + kd.nbytes
        if nbytes <= _BLOCK_TENSOR_CACHE_BYTES:
            for arr in (y, w, valid, kd):
                arr.flags.writeable = False
            while (_block_tensor_bytes + nbytes
                   > _BLOCK_TENSOR_CACHE_BYTES) and _BLOCK_TENSOR_CACHE:
                old = _BLOCK_TENSOR_CACHE.pop(
                    next(iter(_BLOCK_TENSOR_CACHE)))
                _block_tensor_bytes -= sum(a.nbytes for a in old)
            _BLOCK_TENSOR_CACHE[ck] = (y, w, valid, kd)
            _block_tensor_bytes += nbytes
    return y, w, valid, kd


class _PaddingAcc:
    """Plain-int padding accumulator: one ``PaddingStats`` merge per
    dispatch call instead of one dataclass round-trip per block (the
    per-block churn was measurable on the warm dispatch path)."""
    __slots__ = ("true_cells", "padded_cells", "tasks", "padded_tasks",
                 "lane_cells", "lane_cells_pow2", "true_feats",
                 "padded_feats")

    def __init__(self):
        for f in self.__slots__:
            setattr(self, f, 0)

    def book_part(self, key: BucketKey, blk: _Block, exact_shapes: bool):
        """Per-canonical-block terms: true work and N/P-axis lanes."""
        # opaque exact-shape buckets never padded N under either rule
        n_pow2 = blk.n if exact_shapes else pow2_bucket(blk.n, 8)
        self.true_cells += blk.k * blk.n
        self.tasks += blk.k
        self.lane_cells += blk.k * key.n_pad
        self.lane_cells_pow2 += blk.k * n_pow2
        self.true_feats += blk.k * blk.p
        self.padded_feats += blk.k * key.p_pad

    def book_launch(self, key: BucketKey, lb: _LaunchBlock):
        """Per-launch-block terms: what the device actually burned —
        a coalesced launch block bills its combined b_pad ONCE."""
        self.padded_cells += lb.b_pad * key.n_pad
        self.padded_tasks += lb.b_pad

    def stats(self, padded_tasks_pow2: int,
              padded_tasks_morphed: int) -> PaddingStats:
        return PaddingStats(
            true_cells=self.true_cells, padded_cells=self.padded_cells,
            tasks=self.tasks, padded_tasks=self.padded_tasks,
            padded_tasks_pow2=padded_tasks_pow2,
            padded_tasks_morphed=padded_tasks_morphed,
            lane_cells=self.lane_cells,
            lane_cells_pow2=self.lane_cells_pow2,
            true_feats=self.true_feats, padded_feats=self.padded_feats)


def _page_key_of(plan: MegabatchPlan, pages: Optional[PagePool],
                 blk: _Block, n_pad: int, p_pad: int):
    """Identity of a block's feature page: the PagePool content key when
    pooled, the request index on the host-stacked path."""
    if pages is not None:
        return PagePool.page_key(plan.requests[blk.ri], n_pad, p_pad)
    return blk.ri


def _launch_pages(plan: MegabatchPlan, pages: Optional[PagePool],
                  key: BucketKey, lbs: List[_LaunchBlock],
                  n_pad: int, p_pad: int):
    """Union page stack + page-key -> lane map across launch blocks."""
    lane_of: Dict[object, int] = {}
    needs = []
    for lb in lbs:
        for blk in lb.parts:
            pk = _page_key_of(plan, pages, blk, n_pad, p_pad)
            if pk not in lane_of:
                lane_of[pk] = len(lane_of)
                needs.append((pk, plan.requests[blk.ri]))
    if pages is not None:
        pages_arr = pages.stack(needs, n_pad, p_pad)
    else:
        stack = [plan.page(ri, key) for ri, _ in needs]
        d_pad = pow2_bucket(len(stack), 1)
        stack += [np.zeros((n_pad, p_pad), np.float32)] \
            * (d_pad - len(stack))
        pages_arr = np.stack(stack)
    return pages_arr, lane_of


def _launch_tensors(plan: MegabatchPlan, lb: _LaunchBlock, n_pad: int):
    """One launch block's (y, w, valid, kd) at its launch shape.

    Single canonical blocks at their own shape come straight from the
    content-keyed tensor cache (zero copy); packed or morphed launch
    blocks assemble their combined batch axis from the parts' cached
    tensors (padding lanes stay zero with valid=0)."""
    if len(lb.parts) == 1 and lb.b_pad == lb.parts[0].b_pad:
        blk = lb.parts[0]
        return _block_tensors(plan.requests[blk.ri], blk.si, blk, n_pad)
    y = np.zeros((lb.b_pad, n_pad), np.float32)
    w = np.zeros((lb.b_pad, n_pad), np.float32)
    valid = np.zeros((lb.b_pad, n_pad), np.float32)
    kd = None
    for blk, ofs in zip(lb.parts, lb.offsets):
        py, pw, pv, pkd = _block_tensors(plan.requests[blk.ri], blk.si,
                                         blk, n_pad)
        if kd is None:
            kd = np.zeros((lb.b_pad,) + pkd.shape[1:], pkd.dtype)
        k = blk.k
        y[ofs:ofs + k] = py[:k]
        w[ofs:ofs + k] = pw[:k]
        valid[ofs:ofs + k] = pv[:k]
        kd[ofs:ofs + k] = pkd[:k]
    return y, w, valid, kd


def _launch_didx(plan: MegabatchPlan, pages: Optional[PagePool],
                 lb: _LaunchBlock, lane_of: Dict[object, int],
                 n_pad: int, p_pad: int) -> np.ndarray:
    """Per-lane page index for one launch block.  Padding lanes point at
    page 0 — their gather is masked by valid=0, and a fixed index keeps
    the launch deterministic."""
    didx = np.zeros((lb.b_pad,), np.int32)
    for blk, ofs in zip(lb.parts, lb.offsets):
        didx[ofs:ofs + blk.k] = \
            lane_of[_page_key_of(plan, pages, blk, n_pad, p_pad)]
    return didx


def _nbytes(*arrays) -> int:
    """Host bytes a launch stages as operands (pages excluded: they
    come from the device-resident pool, or are counted by PageStats)."""
    return sum(int(a.nbytes) for a in arrays)


def _launch_rids(plan: MegabatchPlan, lbs: List[_LaunchBlock]) -> List:
    """Request ids (the session's tags, else plan indices) riding one
    launch, in first-appearance order."""
    out: Dict[object, None] = {}
    for lb in lbs:
        for blk in lb.parts:
            tag = plan.requests[blk.ri].tag
            out.setdefault(blk.ri if tag is None else tag)
    return list(out)


def _axis_to_execute(key: BucketKey, axis_decision, mesh
                     ) -> Optional[Tuple[str, int]]:
    """(axis, shards) the drain can actually lower for this bucket, or
    None for the task path.  A data/feature ``AxisDecision`` executes
    only when the in-mesh executors apply: a Gram family, a mesh with a
    "data" device axis, and the sharded dimension divisible by the
    axis size (N_pad is 8-aligned, P_pad pow2 — so power-of-two meshes
    always divide; anything else falls back to task, which
    ``dispatch_bucket`` stamps on the decision)."""
    from repro.launch.roofline import GRAM_FAMILIES
    if axis_decision is None or mesh is None:
        return None
    axis = axis_decision.axis
    if axis not in ("data", "feature"):
        return None
    if bucket_family(key) not in GRAM_FAMILIES:
        return None
    if "data" not in mesh.axis_names:
        return None
    m = int(mesh.shape["data"])
    if axis == "data" and key.n_pad % m != 0:
        return None
    if axis == "feature" and key.p_pad % m != 0:
        return None
    return axis, m


def _dispatch_axis_bucket(plan: MegabatchPlan, cache: ProgramCache,
                          key: BucketKey, entries: Sequence[Entry],
                          blocks: List[_Block], axis: str, mesh,
                          *, b_align: int, pages: Optional[PagePool],
                          b_block: int, coalesce: bool) -> BucketDispatch:
    """Lower a bucket slice through the planner's data@m/feature@m
    layout (ISSUE 9): every launch block dispatches through the in-mesh
    fit-predict program (sharding/gram.py::axis_fit_program) instead of
    the ProgramCache's task program — the data form streams each
    shard's N/m rows as chunks through the blocked Gram kernel with
    psum reassembly, the feature form shards P with the all-gather row
    term, and the solve epilogue runs replicated.  Page stacking, task
    tensors, coalescing, harvest booking, and DispatchStats/
    PaddingStats attribution are identical to the task path; results
    sit in the explicit tolerance tier (the task axis stays the bitwise
    reference), so axis launches never fuse across blocks or morph into
    foreign shapes beyond the same tail packing the task path does."""
    from repro.sharding.gram import (axis_fit_program,
                                     axis_fit_program_cached)
    requests = plan.requests
    n_pad, p_pad = key.n_pad, key.p_pad
    family = bucket_family(key)
    params = tuple(key.learner[1])
    can_morph = morph_allowed(key)
    morph = coalesce and can_morph
    lblocks = _coalesce(blocks, b_block, b_align, morph, False)
    morphed_tasks = sum(lb.b_pad for lb in lblocks) if morph == can_morph \
        else sum(lb.b_pad for lb in
                 _coalesce(blocks, b_block, b_align, can_morph, False))

    pad_acc = _PaddingAcc()
    launches: List[Launch] = []
    # operands may be committed to a single device (the host PagePool
    # pins pages to its lead device); re-place them replicated on the
    # mesh so the jitted shard_map accepts and partitions them
    from jax.sharding import NamedSharding, PartitionSpec
    repl = NamedSharding(mesh, PartitionSpec())
    for lb in lblocks:
        with obs.span("program.stage", b_pad=lb.b_pad, g=1) as st:
            pages_arr, lane_of = _launch_pages(plan, pages, key, [lb],
                                               n_pad, p_pad)
            y, w, valid, kd = _launch_tensors(plan, lb, n_pad)
            didx = _launch_didx(plan, pages, lb, lane_of, n_pad, p_pad)
            st.set(staged_bytes=_nbytes(y, w, valid, kd, didx))
            pages_arr, didx, y, w, valid, kd = jax.device_put(
                (pages_arr, didx, y, w, valid, kd), repl)
        if axis_fit_program_cached(mesh, axis, family, params):
            cache.stats.hits += 1
        else:
            cache.stats.misses += 1
        prog = axis_fit_program(mesh, axis, family, params)
        with obs.span("program.launch", b_pad=lb.b_pad, g=1,
                      rids=_launch_rids(plan, [lb])):
            out = prog(pages_arr, didx, y, w, valid, kd)
        launches.append(Launch(out=out, blocks=[lb], fused=False))
        cache.stats.launches += 1
        cache.stats.blocks += len(lb.parts)
        if len(lb.parts) > 1:
            cache.stats.coalesced_blocks += len(lb.parts)
            cache.stats.fused_launches += 1
        for blk in lb.parts:
            pad_acc.book_part(
                key, blk,
                requests[blk.ri].segments[blk.si].learner is None)
        pad_acc.book_launch(key, lb)

    total_tasks = sum(blk.k for blk in blocks)
    cache.stats.padding = cache.stats.padding.merge(
        pad_acc.stats(pow2_bucket(total_tasks, 8), morphed_tasks))
    return BucketDispatch(key=key, launches=launches,
                          entries=list(entries), n_tasks=total_tasks)


def _dispatch_span(dispatch):
    """Run a bucket dispatch inside its ``program.dispatch`` span."""
    @functools.wraps(dispatch)
    def traced(plan, cache, key: BucketKey, entries: Sequence[Entry],
               **opts) -> BucketDispatch:
        with obs.span("program.dispatch", n_pad=key.n_pad, p_pad=key.p_pad,
                      entries=len(entries)):
            return dispatch(plan, cache, key, entries, **opts)
    return traced


@_dispatch_span
def dispatch_bucket(plan: MegabatchPlan, cache: ProgramCache,
                    key: BucketKey, entries: Sequence[Entry], *,
                    b_align: int = 1, pages: Optional[PagePool] = None,
                    b_block: int = B_BLOCK, fuse: bool = True,
                    coalesce: bool = True, axis_decision=None, mesh=None,
                    ) -> BucketDispatch:
    """Launch one bucket slice WITHOUT waiting for the device.

    Groups the entries' tasks into canonical launch blocks; for
    morph-proven families (``coalesce``, see MORPH_BITWISE_FAMILIES)
    tail blocks pack cross-request into combined launch blocks and
    residual mixed shapes morph up so the bucket fuses into one
    ``lax.map`` launch.  Equal-``b_pad`` launch blocks pack into fused
    launches (a leading block axis over one union page stack; per-block
    launches when ``fuse`` is off, the block is unique at its shape, or
    the cache does not fuse: ``ProgramCache.fuses``).  Returns the in-flight
    ``BucketDispatch``; call ``.harvest()`` (or go through
    ``run_bucket``) for the results.

    ``axis_decision``/``mesh`` (ISSUE 9): a planner ``AxisDecision``
    whose axis is data/feature lowers through the in-mesh Gram
    executors on ``mesh`` (``_dispatch_axis_bucket``) when the
    executability guards pass; the decision's ``executed`` field is
    stamped with the axis that actually ran either way.
    """
    requests = plan.requests
    n_pad, p_pad = key.n_pad, key.p_pad
    blocks = _plan_blocks(plan, key, entries, b_block, b_align)
    # execute the axis plan (ISSUE 9): a data/feature decision lowers
    # through the in-mesh Gram executors; anything else (including a
    # data/feature plan the guards reject) runs the task path, and the
    # decision records which axis actually ran
    axis_m = _axis_to_execute(key, axis_decision, mesh)
    if axis_m is not None:
        axis_decision.executed = axis_m[0]
        return _dispatch_axis_bucket(
            plan, cache, key, entries, blocks, axis_m[0], mesh,
            b_align=b_align, pages=pages, b_block=b_block,
            coalesce=coalesce)
    if axis_decision is not None:
        axis_decision.executed = "task"
    fuse = fuse and cache.fuses
    can_morph = morph_allowed(key)
    morph = coalesce and can_morph
    lblocks = _coalesce(blocks, b_block, b_align, morph, fuse)
    # the morphed-B comparator: what the coalescing scheduler burns (or
    # would burn, when coalesce is off) on this slice's B axis
    morphed_tasks = sum(lb.b_pad for lb in lblocks) if morph == can_morph \
        else sum(lb.b_pad for lb in
                 _coalesce(blocks, b_block, b_align, can_morph, fuse))

    by_shape: Dict[int, List[_LaunchBlock]] = {}
    for lb in lblocks:
        by_shape.setdefault(lb.b_pad, []).append(lb)

    pad_acc = _PaddingAcc()
    launches: List[Launch] = []
    for b_pad, group in by_shape.items():
        lead = group[0].parts[0]
        seg = requests[lead.ri].segments[lead.si]
        if not fuse or len(group) == 1:
            for lb in group:
                with obs.span("program.stage", b_pad=b_pad, g=1) as st:
                    pages_arr, lane_of = _launch_pages(
                        plan, pages, key, [lb], n_pad, p_pad)
                    y, w, valid, kd = _launch_tensors(plan, lb, n_pad)
                    didx = _launch_didx(plan, pages, lb, lane_of,
                                        n_pad, p_pad)
                    st.set(staged_bytes=_nbytes(y, w, valid, kd, didx))
                blk_seg = requests[lb.parts[0].ri].segments[lb.parts[0].si]
                prog = cache.program(
                    key, b_pad, int(pages_arr.shape[0]),
                    lambda: segment_batched_fn(blk_seg))
                with obs.span("program.launch", b_pad=b_pad, g=1,
                              rids=_launch_rids(plan, [lb])):
                    out = prog(pages_arr, didx, y, w, valid, kd)
                launches.append(Launch(out=out, blocks=[lb], fused=False))
                cache.stats.launches += 1
                cache.stats.blocks += len(lb.parts)
                if len(lb.parts) > 1:
                    # a coalesced multi-part launch IS a fused launch:
                    # 2+ canonical blocks went up in one dispatch
                    cache.stats.coalesced_blocks += len(lb.parts)
                    cache.stats.fused_launches += 1
                for blk in lb.parts:
                    pad_acc.book_part(
                        key, blk,
                        requests[blk.ri].segments[blk.si].learner is None)
                pad_acc.book_launch(key, lb)
            continue

        # ---- fused launch: G same-shape launch blocks, one union stack
        g = len(group)
        with obs.span("program.stage", b_pad=b_pad, g=g) as st:
            pages_arr, lane_of = _launch_pages(plan, pages, key, group,
                                               n_pad, p_pad)
            ys = np.empty((g, b_pad, n_pad), np.float32)
            ws = np.empty((g, b_pad, n_pad), np.float32)
            valids = np.empty((g, b_pad, n_pad), np.float32)
            didx = np.empty((g, b_pad), np.int32)
            kds = None
            for gi, lb in enumerate(group):
                y, w, valid, kd = _launch_tensors(plan, lb, n_pad)
                if kds is None:
                    kds = np.empty((g,) + kd.shape, kd.dtype)
                ys[gi], ws[gi], valids[gi], kds[gi] = y, w, valid, kd
                didx[gi] = _launch_didx(plan, pages, lb, lane_of,
                                        n_pad, p_pad)
                cache.stats.blocks += len(lb.parts)
                if len(lb.parts) > 1:
                    cache.stats.coalesced_blocks += len(lb.parts)
                for blk in lb.parts:
                    pad_acc.book_part(
                        key, blk,
                        requests[blk.ri].segments[blk.si].learner is None)
                pad_acc.book_launch(key, lb)
            st.set(staged_bytes=_nbytes(ys, ws, valids, kds, didx))
        if cache.partition_fused is not None:
            prog = cache.sharded_fused_program(
                key, b_pad, int(pages_arr.shape[0]), g,
                lambda: segment_batched_fn(seg))
        else:
            prog = cache.fused_program(
                key, b_pad, int(pages_arr.shape[0]), g,
                lambda: segment_batched_fn(seg))
        with obs.span("program.launch", b_pad=b_pad, g=g,
                      rids=_launch_rids(plan, group)):
            out = prog(pages_arr, didx, ys, ws, valids, kds)
        launches.append(Launch(out=out, blocks=list(group), fused=True))
        cache.stats.launches += 1
        cache.stats.fused_launches += 1

    total_tasks = sum(blk.k for blk in blocks)
    # one merge per dispatch; padded_tasks_pow2 records what the old rule
    # (one pow2 launch per bucket slice) would have cost, and
    # padded_tasks_morphed what the coalescing scheduler costs
    cache.stats.padding = cache.stats.padding.merge(
        pad_acc.stats(pow2_bucket(total_tasks, 8), morphed_tasks))
    return BucketDispatch(key=key, launches=launches,
                          entries=list(entries), n_tasks=total_tasks)


def run_bucket(plan: MegabatchPlan, cache: ProgramCache, key: BucketKey,
               entries: Sequence[Entry], *, b_align: int = 1,
               pages: Optional[PagePool] = None, b_block: int = B_BLOCK,
               fuse: bool = True, coalesce: bool = True,
               axis_decision=None, mesh=None,
               ) -> Tuple[Dict[Entry, np.ndarray], float]:
    """Synchronous wrapper: dispatch one bucket slice and block for its
    results.  Returns ({(req_idx, inv): preds (tpi, n_obs)}, wall_s).

    When a ``PagePool`` is passed, feature pages come from the
    device-resident pool (zero host->device transfer on warm pages, and
    fused launches reuse the composition-cached union stack); otherwise
    pages are stacked on the host.
    """
    t0 = time.perf_counter()
    bd = dispatch_bucket(plan, cache, key, entries, b_align=b_align,
                         pages=pages, b_block=b_block, fuse=fuse,
                         coalesce=coalesce, axis_decision=axis_decision,
                         mesh=mesh)
    results = bd.harvest()
    return results, time.perf_counter() - t0
