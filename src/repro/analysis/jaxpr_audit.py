"""Pass 1 — jaxpr-level determinism audit of the megabatch programs.

The float-pinning contract (PR 5) says a fused launch is **bitwise**
equal to per-block launches because ``lax.map`` compiles the mapped body
exactly as the single-block program — where ``vmap`` would add a batched
leading axis that lets XLA retile the per-lane reductions (~1e-7
drift).  The parity tests check this by example on sampled inputs; this
pass checks it structurally on the closed jaxpr, for every learner
family and program form the ``ProgramCache`` can build:

  * **fused-lowers-through-scan** — the fused program's top-level jaxpr
    must be exactly one ``scan`` equation (``lax.map`` is scan with no
    carry); any other top-level primitive means a batched lowering
    leaked in.
  * **fused-body-equals-block** — the scan body's primitive sequence
    must equal the single-block program's primitive sequence: the
    mapped body IS the per-block computation, so fused results cannot
    drift from per-block ones.
  * **sharded-wraps-shard-map** — the partitioned form must lower
    through one ``shard_map`` whose body passes the same PRNG/shape
    audit (sharded parity is tolerance-level by contract, so body
    equality is not required there).
  * **sharded-fused-wraps-scan** — the sharded-fused form (ISSUE 8:
    partitioned caches now fuse) must lower through exactly one
    ``shard_map`` whose body is exactly one ``scan`` whose body's
    primitive sequence equals the single-block program: the shard only
    splits the task axis, so each device runs the identical fused
    ``lax.map`` over its B/m lane slice.  This pins the *structure*;
    numeric parity vs the unsharded fused launch is bitwise on a
    1-device mesh and the ~1e-6 sharded float tier on m-way meshes
    (compiled-B retiling below 16 lanes — see the B_BLOCK caveat in
    compile/program.py).
  * **data-axis-wraps-shard-map / data-axis-psums-moments** — the
    in-mesh data@m drain program (ISSUE 9, sharding/gram.py) must be
    one ``shard_map`` whose body reassembles the per-shard partial
    (G, b, nw) moments by ``psum`` and never ``all_gather``s: the N
    split exists to keep rows local, so only O(P^2) statistics may
    cross the wire.
  * **feature-axis-wraps-shard-map / feature-axis-gathers-rows** — the
    in-mesh feature@m program must be one ``shard_map`` whose body
    ``all_gather``s the row matrix (the wire term the axis planner
    prices): a gather-free body means each shard contracted only its
    own columns and the cross-column Gram blocks are wrong.
  * **prng-key-from-runtime-data** — taint analysis over the jaxpr:
    primitives that consume PRNG keys may only be reached from the
    ``key_data`` input (the compile-time ``fold_in`` tables), never
    from the data inputs — a learner that derived randomness from its
    batch would break schedule invariance.
  * **data-dependent-shape** — every intermediate aval must have
    concrete integer dimensions; a data-dependent shape would make the
    compiled program's output depend on bucket composition.
  * **morph-classified** — every family must be in
    ``MORPH_BITWISE_FAMILIES`` (bitwise-proven B-invariant), the only
    set the cross-shape coalescer (ISSUE 7) morphs: a family outside it
    would silently never coalesce.
  * **morph-structural-b-pin** — a bitwise-morphable family's program
    must trace to the identical primitive sequence at two different B
    paddings: padding a tail block up to a neighbor's canonical B may
    never change the computation's structure, only its lane count.

Unlike the other passes this one imports jax and the learner registry —
it audits what actually traces, not what the source says.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.report import Finding
from repro.learners import get_batched_learner, resolve_params

#: the six registry families (kept literal so a silently dropped
#: registry entry fails the audit instead of shrinking its coverage)
FAMILIES: Tuple[str, ...] = ("ols", "ridge", "lasso", "logistic",
                             "kernel_ridge", "mlp")

#: primitives that consume or produce PRNG state
PRNG_PRIMS: Set[str] = {
    "random_wrap", "random_unwrap", "random_seed", "random_bits",
    "random_fold_in", "random_gamma", "threefry2x32",
}

# probe shape: small but structurally faithful (B tasks, N rows, P
# features, G fused blocks).  Tracing only — nothing is compiled or run.
_B, _N, _P, _G = 8, 32, 8, 3


# ---------------------------------------------------------------------------
# taint analysis over (nested) jaxprs
# ---------------------------------------------------------------------------
def _unwrap(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _taint_jaxpr(jaxpr, invar_marks: List[Set[str]], where: str,
                 findings: List[Finding], depth: int = 0) -> None:
    """Propagate {"data", "key"} marks through one jaxpr, flagging PRNG
    primitives that touch data-derived values.  Sub-jaxprs with a known
    1:1 invar mapping (pjit, scan, shard_map, call-like) are recursed
    with per-position marks; unknown higher-order primitives union-taint
    their outputs without recursing (conservative, no false positives).
    """
    if depth > 32:
        return
    marks: Dict[int, Set[str]] = {}
    for var, m in zip(jaxpr.invars, invar_marks):
        marks[id(var)] = set(m)
    for var in jaxpr.constvars:
        marks[id(var)] = set()

    def of(atom) -> Set[str]:
        return marks.get(id(atom), set())

    for eqn in jaxpr.eqns:
        in_marks: Set[str] = set()
        for a in eqn.invars:
            in_marks |= of(a)
        pname = eqn.primitive.name

        if pname in PRNG_PRIMS:
            bad = sorted({m for a in eqn.invars for m in of(a)
                          if m == "data"})
            if bad:
                findings.append(Finding(
                    "jaxpr", "prng-key-from-runtime-data",
                    where,
                    f"primitive {pname!r} consumes a value derived "
                    "from the data inputs — PRNG state must derive "
                    "only from the compile-time fold_in key tables"))
            in_marks = in_marks | {"key"}

        # recurse into sub-jaxprs whose invars map 1:1 onto eqn.invars
        params = eqn.params
        subs: List[Tuple[object, List[Set[str]]]] = []
        eq_marks = [of(a) for a in eqn.invars]
        if pname in ("pjit", "scan", "shard_map", "closed_call",
                     "core_call", "xla_call", "remat", "checkpoint",
                     "custom_jvp_call", "custom_vjp_call"):
            sub = params.get("jaxpr") or params.get("call_jaxpr")
            if sub is not None:
                sub = _unwrap(sub)
                if len(sub.invars) == len(eqn.invars):
                    subs.append((sub, eq_marks))
        elif pname == "cond":
            for br in params.get("branches", ()):
                sub = _unwrap(br)
                if len(sub.invars) == len(eqn.invars) - 1:
                    subs.append((sub, eq_marks[1:]))
        elif pname == "while":
            cn = params.get("cond_nconsts", 0)
            bn = params.get("body_nconsts", 0)
            body = _unwrap(params.get("body_jaxpr"))
            cond = _unwrap(params.get("cond_jaxpr"))
            if body is not None:
                subs.append((body, eq_marks[cn:]))
            if cond is not None:
                subs.append((cond, eq_marks[:cn] + eq_marks[cn + bn:]))
        for sub, sub_marks in subs:
            _taint_jaxpr(sub, sub_marks, where, findings, depth + 1)

        shaped = [v for v in eqn.outvars if hasattr(v, "aval")]
        for v in shaped:
            aval = v.aval
            dims = getattr(aval, "shape", ())
            if not all(isinstance(d, int) for d in dims):
                findings.append(Finding(
                    "jaxpr", "data-dependent-shape", where,
                    f"primitive {pname!r} produces aval {aval} with a "
                    "non-concrete dimension — compiled shapes must be "
                    "pure functions of the bucket spec"))
            marks[id(v)] = set(in_marks)


# ---------------------------------------------------------------------------
# program forms
# ---------------------------------------------------------------------------
def _probe_avals(fused: bool, b: int = _B):
    kw = jax.random.key_data(jax.random.key(0)).shape
    lead = (_G,) if fused else ()
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    return (jax.ShapeDtypeStruct((1, _N, _P), f32),          # pages
            jax.ShapeDtypeStruct(lead + (b,), i32),          # data_idx
            jax.ShapeDtypeStruct(lead + (b, _N), f32),       # y
            jax.ShapeDtypeStruct(lead + (b, _N), f32),       # w
            jax.ShapeDtypeStruct(lead + (b, _N), f32),       # valid
            jax.ShapeDtypeStruct(lead + (b,) + kw, u32))     # key_data


def _program_pair(family: str):
    """(single-block run, lax.map-fused run) for one learner family —
    the exact bodies ``ProgramCache.program`` / ``fused_program`` jit."""
    params = resolve_params(family, None, n_obs=_N, dim_x=_P)
    batched_fn = get_batched_learner(family, params)

    def run(pages, data_idx, y, w, valid, key_data):
        xb = pages[data_idx]
        keys = jax.random.wrap_key_data(key_data)
        return batched_fn(xb, y, w, valid, keys)

    def run_fused(pages, data_idx, y, w, valid, key_data):
        return jax.lax.map(lambda t: run(pages, *t),
                           (data_idx, y, w, valid, key_data))

    return run, run_fused


def _prim_seq(jaxpr) -> List[str]:
    return [e.primitive.name for e in jaxpr.eqns]


def audit_fused_pair(single_jaxpr, fused_jaxpr, where: str,
                     ) -> List[Finding]:
    """The structural fused-launch checks, factored out so the mutation
    tests can feed a deliberately vmap-built fused program."""
    findings: List[Finding] = []
    top = _prim_seq(fused_jaxpr.jaxpr)
    if top != ["scan"]:
        findings.append(Finding(
            "jaxpr", "fused-lowers-through-scan", where,
            f"fused program's top-level jaxpr is {top} — must be "
            "exactly one scan (lax.map); a vmap-batched lowering lets "
            "XLA retile reductions and breaks bitwise block parity"))
        return findings
    body = _unwrap(fused_jaxpr.jaxpr.eqns[0].params["jaxpr"])
    if _prim_seq(body) != _prim_seq(single_jaxpr.jaxpr):
        findings.append(Finding(
            "jaxpr", "fused-body-equals-block", where,
            "fused scan body's primitive sequence differs from the "
            "single-block program — the mapped body must compile to "
            "exactly the per-block computation"))
    return findings


def audit_sharded_fused(single_jaxpr, sharded_fused_jaxpr, where: str,
                        ) -> List[Finding]:
    """Structural checks for the sharded-fused form (ISSUE 8): one
    shard_map, whose body is one scan, whose body is the single-block
    program.  Factored out (like ``audit_fused_pair``) so the mutation
    tests can feed a deliberately vmap-built body and watch it fail."""
    findings: List[Finding] = []
    tops = _prim_seq(sharded_fused_jaxpr.jaxpr)
    if tops != ["shard_map"]:
        findings.append(Finding(
            "jaxpr", "sharded-fused-wraps-scan", where,
            f"sharded-fused program's top-level jaxpr is {tops} — must "
            "be exactly one shard_map so the partition only splits the "
            "task axis"))
        return findings
    body = _unwrap(sharded_fused_jaxpr.jaxpr.eqns[0].params["jaxpr"])
    inner = _prim_seq(body)
    if inner != ["scan"]:
        findings.append(Finding(
            "jaxpr", "sharded-fused-wraps-scan", where,
            f"shard_map body's primitive sequence is {inner} — must be "
            "exactly one scan (lax.map); a vmap-batched body inside the "
            "shard would retile reductions and break the bitwise "
            "sharded-fused contract"))
        return findings
    scan_body = _unwrap(body.eqns[0].params["jaxpr"])
    if _prim_seq(scan_body) != _prim_seq(single_jaxpr.jaxpr):
        findings.append(Finding(
            "jaxpr", "sharded-fused-wraps-scan", where,
            "sharded-fused scan body's primitive sequence differs from "
            "the single-block program — each device's fused lanes must "
            "compile to exactly the per-block computation"))
    return findings


def _sub_jaxprs(eqn):
    """Every sub-jaxpr an equation's params reference (pjit/scan bodies,
    cond branches, ...), unwrapped."""
    for v in eqn.params.values():
        for s in (v if isinstance(v, (tuple, list)) else (v,)):
            s = _unwrap(s)
            if hasattr(s, "eqns"):
                yield s


def _all_prims(jaxpr, depth: int = 0) -> List[str]:
    """Every primitive name in a jaxpr, recursing through sub-jaxprs."""
    if depth > 32:
        return []
    out: List[str] = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            out.extend(_all_prims(sub, depth + 1))
    return out


def audit_data_axis(fit_jaxpr, where: str) -> List[Finding]:
    """Structural checks for the data@m in-mesh fit program (ISSUE 9):
    one shard_map whose body reassembles the per-shard partial moments
    by ``psum`` — never by gathering rows.  Factored out so the mutation
    tests can feed a deliberately broken lowering."""
    findings: List[Finding] = []
    top = _prim_seq(fit_jaxpr.jaxpr)
    if top != ["shard_map"]:
        findings.append(Finding(
            "jaxpr", "data-axis-wraps-shard-map", where,
            f"data-axis fit program's top-level jaxpr is {top} — must "
            "be exactly one shard_map so the layout only splits the N "
            "axis"))
        return findings
    prims = _all_prims(_unwrap(fit_jaxpr.jaxpr.eqns[0].params["jaxpr"]))
    if "psum" not in prims:
        findings.append(Finding(
            "jaxpr", "data-axis-psums-moments", where,
            "data-axis fit body contains no psum — each shard's partial "
            "(G, b, nw) moments are never reassembled into the full-N "
            "statistics, so every device would solve on its rows only"))
    if "all_gather" in prims:
        findings.append(Finding(
            "jaxpr", "data-axis-psums-moments", where,
            "data-axis fit body all-gathers — the N split must move "
            "only O(P^2) moments (psum), never replicate the rows it "
            "exists to shard"))
    return findings


def audit_feature_axis(fit_jaxpr, where: str) -> List[Finding]:
    """Structural checks for the feature@m in-mesh fit program
    (ISSUE 9): one shard_map whose body all-gathers — the row-matrix
    wire term the axis planner prices; a gather-free body means each
    shard contracted only its own columns and the cross-column Gram
    blocks are wrong."""
    findings: List[Finding] = []
    top = _prim_seq(fit_jaxpr.jaxpr)
    if top != ["shard_map"]:
        findings.append(Finding(
            "jaxpr", "feature-axis-wraps-shard-map", where,
            f"feature-axis fit program's top-level jaxpr is {top} — "
            "must be exactly one shard_map so the layout only splits "
            "the P axis"))
        return findings
    prims = _all_prims(_unwrap(fit_jaxpr.jaxpr.eqns[0].params["jaxpr"]))
    if "all_gather" not in prims:
        findings.append(Finding(
            "jaxpr", "feature-axis-gathers-rows", where,
            "feature-axis fit body contains no all_gather — the column "
            "split needs the full row matrix (the priced wire term) to "
            "form its (P, P/m) Gram block; without it the cross-column "
            "blocks are computed from the wrong operand"))
    return findings


def audit_axis_programs() -> List[Finding]:
    """Trace the two in-mesh drain forms (sharding/gram.py fit bodies
    under shard_map, ISSUE 9) for every Gram family and run the
    structural axis pins plus the PRNG/shape audit on each."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.roofline import GRAM_FAMILIES
    from repro.sharding.gram import _data_fit_body, _feature_fit_body
    from jax.sharding import PartitionSpec as P

    findings: List[Finding] = []
    mesh = make_host_mesh()
    avals = _probe_avals(fused=False)
    for family in GRAM_FAMILIES:
        params = tuple(sorted(resolve_params(
            family, None, n_obs=_N, dim_x=_P).items()))
        data_fn = jax.shard_map(
            _data_fit_body("data", family, params), mesh=mesh,
            in_specs=(P(None, "data", None), P(None), P(None, "data"),
                      P(None, "data"), P(None, "data"), P(None, None)),
            out_specs=P(None, "data"), check_vma=False)
        data = jax.make_jaxpr(data_fn)(*avals)
        findings.extend(audit_data_axis(data, f"{family}/data-axis"))
        _taint_jaxpr(data.jaxpr, _data_key_marks(data.jaxpr),
                     f"{family}/data-axis", findings)

        feat_fn = jax.shard_map(
            _feature_fit_body("data", family, params), mesh=mesh,
            in_specs=(P(None, None, "data"), P(None), P(None, None),
                      P(None, None), P(None, None), P(None, None)),
            out_specs=P(None, None), check_vma=False)
        feat = jax.make_jaxpr(feat_fn)(*avals)
        findings.extend(audit_feature_axis(feat,
                                           f"{family}/feature-axis"))
        _taint_jaxpr(feat.jaxpr, _data_key_marks(feat.jaxpr),
                     f"{family}/feature-axis", findings)
    return findings


def _data_key_marks(jaxpr) -> List[Set[str]]:
    """Input marks for the program signature: everything but the
    trailing key_data operand is runtime data."""
    n = len(jaxpr.invars)
    return [{"data"}] * (n - 1) + [{"key"}]


def audit_morph_classification() -> List[Finding]:
    """Every learner family must be bitwise-morphable: a family outside
    MORPH_BITWISE_FAMILIES would quietly opt out of tail coalescing and
    shrink the launch-efficiency win without any test noticing."""
    from repro.compile.program import MORPH_BITWISE_FAMILIES
    return [Finding(
        "jaxpr", "morph-classified", f"{family}/morph",
        f"family {family!r} is not in MORPH_BITWISE_FAMILIES — prove its "
        "bitwise B-invariance (tests/test_compile.py parity gate) so the "
        "coalescer's behavior is an explicit contract")
        for family in FAMILIES if family not in MORPH_BITWISE_FAMILIES]


def audit_family(family: str) -> List[Finding]:
    findings: List[Finding] = []
    run, run_fused = _program_pair(family)

    single = jax.make_jaxpr(run)(*_probe_avals(fused=False))
    fused = jax.make_jaxpr(run_fused)(*_probe_avals(fused=True))

    # structural B-pin: a morphable family's primitive sequence may not
    # depend on the B padding (the bitwise proof's structural shadow)
    from repro.compile.program import MORPH_BITWISE_FAMILIES
    if family in MORPH_BITWISE_FAMILIES:
        wide = jax.make_jaxpr(run)(*_probe_avals(fused=False, b=2 * _B))
        if _prim_seq(wide.jaxpr) != _prim_seq(single.jaxpr):
            findings.append(Finding(
                "jaxpr", "morph-structural-b-pin", f"{family}/morph",
                f"primitive sequence changes between B={_B} and "
                f"B={2 * _B} — a B-dependent computation cannot be "
                "bitwise-morphed; fix the learner"))

    findings.extend(audit_fused_pair(single, fused, f"{family}/fused"))
    _taint_jaxpr(single.jaxpr, _data_key_marks(single.jaxpr),
                 f"{family}/block", findings)
    _taint_jaxpr(fused.jaxpr, _data_key_marks(fused.jaxpr),
                 f"{family}/fused", findings)

    # the partitioned (ShardedBackend) form: shard_map over "data"
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.policy import megabatch_specs
    in_specs, out_specs = megabatch_specs("data")
    sharded_fn = jax.shard_map(run, mesh=make_host_mesh(),
                               in_specs=in_specs, out_specs=out_specs,
                               check_vma=False)
    sharded = jax.make_jaxpr(sharded_fn)(*_probe_avals(fused=False))
    tops = _prim_seq(sharded.jaxpr)
    if "shard_map" not in tops:
        findings.append(Finding(
            "jaxpr", "sharded-wraps-shard-map", f"{family}/sharded",
            f"partitioned program's top-level jaxpr is {tops} — the "
            "sharded form must lower through shard_map"))
    _taint_jaxpr(sharded.jaxpr, _data_key_marks(sharded.jaxpr),
                 f"{family}/sharded", findings)

    # the sharded-FUSED form (ISSUE 8): shard_map around the lax.map
    # fused body, task axis sharded, pages replicated — the form
    # ProgramCache.sharded_fused_program jits for partitioned buckets
    fin_specs, fout_specs = megabatch_specs("data", fused=True)
    sharded_fused_fn = jax.shard_map(
        run_fused, mesh=make_host_mesh(),
        in_specs=fin_specs, out_specs=fout_specs, check_vma=False)
    sharded_fused = jax.make_jaxpr(sharded_fused_fn)(
        *_probe_avals(fused=True))
    findings.extend(audit_sharded_fused(single, sharded_fused,
                                        f"{family}/sharded-fused"))
    _taint_jaxpr(sharded_fused.jaxpr,
                 _data_key_marks(sharded_fused.jaxpr),
                 f"{family}/sharded-fused", findings)
    return findings


def run(root=None) -> List[Finding]:
    """Audit every (family, program form); ``root`` is accepted for
    signature uniformity with the static passes and ignored."""
    findings: List[Finding] = []
    findings.extend(audit_morph_classification())
    for family in FAMILIES:
        findings.extend(audit_family(family))
    findings.extend(audit_axis_programs())
    return findings
