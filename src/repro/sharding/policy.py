"""Named sharding-policy variants for §Perf hillclimbing, plus the
topology layer's placement policy: which host runs each unit of work.

A variant = (rules transform, model-build overrides).  The dry-run CLI takes
``--variant NAME`` so a hypothesis is one flag away from its measurement; the
baseline tables always use ``default``.

Placement: the unit is a request's pending invocations in one megabatch
bucket.  ``place_unit`` scores a unit's page against every host's
page-pool residency — stack-cached beats pages-resident beats cold —
and ``steal_choice`` picks the units an idle host takes from the most
loaded one.  Both are pure functions of the observed pools/queues, so a
drain's routing is reproducible; and because per-task PRNG is fixed at
compile time, no placement they produce can move an estimate.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.sharding.axes import rules_for


@dataclass(frozen=True)
class Variant:
    name: str
    description: str
    rules_update: Dict[str, object] = field(default_factory=dict)
    attn_chunk: Optional[int] = None
    remat: Optional[str] = None
    n_microbatch: Optional[int] = None


VARIANTS: Dict[str, Variant] = {v.name: v for v in [
    Variant("default", "paper-faithful baseline policy"),
    Variant("no_seqpar",
            "hypothesis: sequence-parallel residual constraint is causing "
            "extra reshard traffic — drop it",
            rules_update={"seq_shard": None}),
    Variant("no_seqpar_m16",
            "no_seqpar trades wire for replicated activation checkpoints; "
            "recover HBM with 16 microbatches",
            rules_update={"seq_shard": None}, n_microbatch=16),
    Variant("dp_heavy",
            "hypothesis: TP all-reduces dominate — shard FFN/heads over "
            "(data,model) jointly and keep activations DP-only",
            rules_update={"act_heads": None, "act_ff": None,
                          "seq_shard": None}),
    Variant("remat_dots",
            "hypothesis: full remat recompute inflates the compute term — "
            "save matmul outputs instead",
            remat="dots"),
    Variant("chunk512", "smaller attention KV chunks (less transient traffic)",
            attn_chunk=512),
    Variant("chunk2048", "larger attention KV chunks (fewer softmax passes)",
            attn_chunk=2048),
]}


def megabatch_specs(batch_axis: str = "data",
                    pages_axis: Optional[str] = None, *,
                    fused: bool = False):
    """PartitionSpecs for a megabatch bucket program (repro/compile).

    The program signature is (pages, data_idx, y, w, valid, key_data) ->
    preds; every per-task tensor is sharded along the task-batch axis —
    the compiler pads B to a multiple of the shard count.

    ``pages_axis=None`` (the single-host default) replicates the
    device-resident page stack so every shard can gather any task's
    dataset.  Passing an axis name instead shards the page D axis — the
    multi-host megabatch layout where each host pool holds only its
    buckets' pages; callers must then also route each bucket's task
    slices to the shard holding its pages (ROADMAP "multi-host
    megabatch").

    ``fused=True`` (ISSUE 8) returns specs for the *fused* calling
    convention, where every per-task operand carries a leading canonical
    block axis G: the G axis is replicated (each shard runs all blocks)
    and the task-batch axis — now axis 1 — is sharded.  A PartitionSpec
    shorter than the operand rank leaves the trailing dims (N_pad, key
    tail) unsharded, so one spec covers all fused operand ranks.
    """
    from jax.sharding import PartitionSpec as P
    pages = P(pages_axis) if pages_axis else P()
    task = P(None, batch_axis) if fused else P(batch_axis)
    in_specs = (pages, task, task, task, task, task)
    out_specs = task
    return in_specs, out_specs


# ---------------------------------------------------------------------------
# Unit -> host placement (topology layer)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class UnitPlacement:
    """One routing decision and the residency evidence it came from."""
    host: int
    score: float                        # page points of the unit's page


def _page_points(pool, pk) -> float:
    """Locality value of one page on one host: 2 if it is launch-ready
    with zero copies (for canonical singleton launches the resident page
    IS the launch array, so this fires for every resident page), 1 if
    only the raw page is held (zero transfers but a copy pending — the
    multi-lane fusion case), 0 cold."""
    if pool.stack_cached((pk,)):
        return 2.0
    if pool.resident(pk):
        return 1.0
    return 0.0


def place_unit(pkey, pools: Sequence,
               loads: Sequence[int]) -> UnitPlacement:
    """Route one unit — a request's pending invocations in one bucket —
    to the host best positioned to run it.

    ``pkey`` is the unit's page key (the request's data at the bucket's
    shape), ``pools`` the per-host PagePools, ``loads`` each host's
    queued invocation count.  Score = the page's locality points
    (stack-cached > resident > cold); ties break to the least-loaded
    host, then the lowest host id — fully deterministic.
    """
    best = None
    for hid, pool in enumerate(pools):
        score = _page_points(pool, pkey)
        rank = (-score, loads[hid], hid)
        if best is None or rank < best[0]:
            best = (rank, UnitPlacement(host=hid, score=score))
    return best[1]


def steal_choice(queues: Dict[int, List], pools: Sequence,
                 pkey_of: Callable[[object], object],
                 size_of: Callable[[object], int]) \
        -> Optional[Tuple[int, List]]:
    """What an idle host steals: from the donor with the most queued
    invocations among those holding more than one unit (a host's last
    unit is never taken — the thief would only trade places with it),
    the units *least* local to the donor, so the migrated residency
    costs the donor the least, until the thief holds half the donor's
    queue.  Returns ``(donor_host, units)`` or None when no steal is
    worthwhile.  ``queues`` maps a host to its not-yet-dispatched units
    in queue order; ``size_of`` is a unit's invocation count.
    """
    load = {hid: sum(size_of(u) for u in units)
            for hid, units in queues.items()}
    donor = None
    for hid, units in sorted(queues.items()):
        if len(units) > 1 and (donor is None or load[hid] > load[donor]):
            donor = hid
    if donor is None:
        return None
    pool = pools[donor]
    # sorted() is stable: the first enqueued among equally-cold units
    # goes first
    order = sorted(queues[donor],
                   key=lambda u: _page_points(pool, pkey_of(u)))
    taken, stolen = 0, []
    for u in order[:-1]:
        stolen.append(u)
        taken += size_of(u)
        if 2 * taken >= load[donor]:
            break
    return donor, stolen


def apply_variant(arch_name: str, shape_kind: str, d_model: int,
                  variant: str):
    v = VARIANTS[variant]
    rules = rules_for(arch_name, shape_kind, d_model)
    if v.rules_update:
        rules = rules.replace(**v.rules_update)
    return rules, v
