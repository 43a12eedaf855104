"""In-mesh executors for the non-task parallelization axes (ISSUE 8/9).

The axis planner (compile/buckets.py::plan_bucket_axis) prices three
layouts per bucket; this module supplies the two that split *inside* a
task, for the Gram-based families whose fit is a pure function of the
(X'X, X'y) statistics:

``data_parallel_gram``     shards the N axis over the mesh: every
                           device accumulates a partial Gram over its
                           N/m rows (the same masked-moment math as the
                           streaming blocked kernel) and a psum
                           reassembles the exact statistics.  The only
                           layout that can run a bucket whose N exceeds
                           one device page — pair with
                           ``kernels/ops.py::chunk_tall_n`` +
                           ``batched_gram_blocked`` to stream arbitrarily
                           tall N through fixed-size chunks.
``feature_parallel_gram``  shards the P axis (LightGBM's
                           feature-parallel analogue): each device owns
                           P/m columns, gathers the row dimension it
                           needs, and emits its column block of the
                           Gram; the blocks concatenate into the full
                           (P, P) statistics.

ISSUE 9 adds the *drain* forms: ``axis_fit_program`` lowers a whole
bucket launch — the same ``run(pages, data_idx, y, w, valid, key_data)``
signature the ProgramCache programs compile — through these layouts, so
``dispatch_bucket`` (compile/program.py) can execute a data@m/feature@m
``AxisDecision`` instead of ignoring it.  The data form streams each
shard's rows as N-chunks through ``chunk_tall_n`` +
``batched_gram_blocked`` and psums the (G, b) moments; the feature form
shards P with the all-gather row term; the solve epilogue runs
replicated on the reassembled statistics (``gram_solve`` for ridge/OLS,
the FISTA moments form for lasso).

Both agree with the single-device statistics to float tolerance, never
bitwise: the data split changes the N-axis reduction tree, and the
feature split's narrower column blocks let XLA retile the N
contraction — the same explicit tolerance tier as the blocked kernel's
ragged-tail case (kernels/ops.py::BLOCKED_GRAM_TOLERANCE_FAMILIES
documents the bitwise/tolerance split).  The unsharded task-parallel
axis remains the bitwise reference path.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.registry import warm_cache
from repro.runtime import bounded_put

F32 = jnp.float32

#: jitted shard_map programs, one per (mesh, mesh_axis, family, params)
#: — the in-mesh analogue of the ProgramCache, bounded because meshes
#: and hyperparameter bindings churn across sessions (sim-host meshes
#: are rebuilt per Topology) while a drain's repeated calls must hit
#: the warm compiled program instead of re-tracing a fresh shard_map
#: closure every launch
_DATA_GRAM_PROGRAMS: Dict[Tuple, object] = {}
_FEATURE_GRAM_PROGRAMS: Dict[Tuple, object] = {}
_GRAM_PROGRAM_CACHE_MAX = 64


def _chunk_rows(n_local: int, page_rows: int) -> int:
    """Chunk size for streaming ``n_local`` rows through fixed device
    pages: one chunk when the rows fit, else the balanced chunk size
    rounded up to the 8-row sublane multiple (minimizing the ragged
    tail the blocked kernel pads with w == 0 rows)."""
    if n_local <= page_rows:
        return n_local
    n_chunks = -(-n_local // page_rows)
    return min((-(-n_local // n_chunks) + 7) // 8 * 8, page_rows)


def _fit_epilogue(family: str, params: Dict, g, b, nw):
    """The replicated solve epilogue on fully-reassembled raw moments.

    g (B,Pa,Pa), b (B,Pa) are the *unregularized* statistics (augmented
    with the intercept column when the learner asks for one); nw (B,)
    is the global training-weight sum (psummed on the data axis).
    Mirrors learners/linear.py: ridge adds reg to the diagonal and
    un-penalizes the intercept, OLS is ridge at 1e-8, lasso runs the
    FISTA moments form.
    """
    from repro.learners.linear import _fista_beta_moments
    intercept = bool(params.get("intercept", True))
    if family == "lasso":
        return _fista_beta_moments(
            g, b, nw, reg=float(params.get("reg", 0.01)),
            intercept=intercept, n_iter=int(params.get("n_iter", 200)))
    reg = 1e-8 if family == "ols" else float(params.get("reg", 1.0))
    pa = g.shape[-1]
    g = g + reg * jnp.eye(pa, dtype=g.dtype)
    if intercept and reg:
        g = g.at[:, pa - 1, pa - 1].add(-reg + 1e-8)
    return gram_solve(g, b)


def _data_fit_body(mesh_axis: str, family: str, params: Tuple):
    """Per-shard body of the data@m bucket program: the shard sees its
    N/m slice of the pages and task tensors, streams those rows as
    N-chunks through the blocked Gram kernel, psums the (G, b, nw)
    moments into the exact full-N statistics, solves replicated, and
    predicts its local rows (the out_spec reassembles the N axis)."""
    from repro.kernels import ops
    from repro.learners.linear import _augment_b
    p = dict(params)
    p.pop("classify", None)     # linear families fit propensities as
    intercept = bool(p.get("intercept", True))   # regression (base.py)

    def body(pages, data_idx, y, w, valid, key_data):
        del key_data                       # gram families draw no keys
        from repro.launch import roofline
        xb = pages[data_idx].astype(F32)             # (B, Nloc, P)
        yf, wf = y.astype(F32), w.astype(F32)
        xa = _augment_b(xb) if intercept else xb
        chunk = _chunk_rows(int(xa.shape[1]), roofline.DEVICE_PAGE_ROWS)
        xc, wc, yc = ops.chunk_tall_n(xa, wf, yf, chunk)
        g, b = ops.batched_gram_blocked(xc, wc, yc)
        g = jax.lax.psum(g, mesh_axis)
        b = jax.lax.psum(b, mesh_axis)
        nw = jnp.maximum(
            jax.lax.psum(jnp.sum(wf, axis=1), mesh_axis), 1.0)
        beta = _fit_epilogue(family, p, g, b, nw)
        return ops.batched_predict(xa, beta, valid.astype(F32))

    return body


def _feature_fit_body(mesh_axis: str, family: str, params: Tuple):
    """Per-shard body of the feature@m bucket program: the shard owns
    P/m feature columns, all-gathers the full row matrix (the wire term
    the planner prices), computes its (P, P/m) column block of the raw
    Gram, gathers the blocks into the full statistics, assembles the
    intercept row/column from cheap O(NP) moments, and solves/predicts
    replicated."""
    from repro.kernels import ops
    from repro.learners.linear import _augment_b
    p = dict(params)
    p.pop("classify", None)
    intercept = bool(p.get("intercept", True))

    def body(pages, data_idx, y, w, valid, key_data):
        del key_data
        xb = pages[data_idx].astype(F32)             # (B, N, Ploc)
        yf, wf = y.astype(F32), w.astype(F32)
        x_full = jax.lax.all_gather(xb, mesh_axis, axis=2, tiled=True)
        g_blk = jnp.einsum("bnp,bn,bnq->bpq", x_full, wf, xb)
        b_blk = jnp.einsum("bn,bnp->bp", wf * yf, xb)
        g = jax.lax.all_gather(g_blk, mesh_axis, axis=2, tiled=True)
        b = jax.lax.all_gather(b_blk, mesh_axis, axis=1, tiled=True)
        nw = jnp.maximum(jnp.sum(wf, axis=1), 1.0)
        if intercept:
            xw1 = jnp.einsum("bn,bnp->bp", wf, x_full)       # (B, P)
            sw = jnp.sum(wf, axis=1)
            swy = jnp.sum(wf * yf, axis=1)
            g = jnp.concatenate([
                jnp.concatenate([g, xw1[:, :, None]], axis=2),
                jnp.concatenate([xw1[:, None, :],
                                 sw[:, None, None]], axis=2)], axis=1)
            b = jnp.concatenate([b, swy[:, None]], axis=1)
            xa = _augment_b(x_full)
        else:
            xa = x_full
        beta = _fit_epilogue(family, p, g, b, nw)
        return ops.batched_predict(xa, beta, valid.astype(F32))

    return body


# family/params select a pure body-builder closure; the jitted program
# is otherwise a function of (mesh, mesh_axis) only
@warm_cache(name="data_gram_programs",
            key=("mesh", "mesh_axis", "family", "params"))
def _data_gram_fn(mesh, mesh_axis: str, family: Optional[str] = None,
                  params: Tuple = ()):
    """Jitted N-sharded executor, cached per (mesh, mesh_axis, family,
    params) so a drain's repeated calls hit the warm compiled program
    instead of re-tracing a fresh shard_map closure every launch.
    ``family=None`` is the standalone Gram form ((xs, w, y) -> (G, b));
    a Gram family name selects the full bucket fit-predict program
    (ISSUE 9 drain path) at the ProgramCache launch signature."""
    from jax.sharding import PartitionSpec as P

    ck = (mesh, mesh_axis, family, params)
    prog = _DATA_GRAM_PROGRAMS.get(ck)
    if prog is not None:
        return prog

    if family is None:
        def body(xs, w, y):
            xf, wf, yf = xs.astype(F32), w.astype(F32), y.astype(F32)
            g = jnp.einsum("bnp,bn,bnq->bpq", xf, wf, xf)
            b = jnp.einsum("bn,bnp->bp", wf * yf, xf)
            g = jax.lax.psum(g, mesh_axis)
            b = jax.lax.psum(b, mesh_axis)
            return g, b

        prog = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, mesh_axis), P(None, mesh_axis),
                      P(None, mesh_axis)),
            out_specs=(P(), P()), check_vma=False))
    else:
        prog = jax.jit(jax.shard_map(
            _data_fit_body(mesh_axis, family, params), mesh=mesh,
            in_specs=(P(None, mesh_axis, None), P(None),
                      P(None, mesh_axis), P(None, mesh_axis),
                      P(None, mesh_axis), P(None, None)),
            out_specs=P(None, mesh_axis), check_vma=False))
    bounded_put(_DATA_GRAM_PROGRAMS, ck, prog, _GRAM_PROGRAM_CACHE_MAX)
    return prog


def data_parallel_gram(mesh, xs, w, y, reg: float = 0.0,
                       mesh_axis: str = "data"):
    """Per-task normal equations with the N axis sharded over ``mesh``.

    xs: (B, N, P); w/y: (B, N).  N must be a multiple of the axis size
    (callers pad with w == 0 rows, which are arithmetically inert).
    ``mesh_axis`` names the *mesh axis* the N dimension shards over
    (the parallelization axis is always N here — the planner's "data"
    layout).  Each device reduces its local rows — exactly one chunk of
    the streaming blocked Gram — and a psum sums the partials into the
    full (G (B,P,P), b (B,P)) on every device.
    """
    g, b = _data_gram_fn(mesh, mesh_axis)(xs, w, y)
    if reg:
        g = g + reg * jnp.eye(xs.shape[-1], dtype=g.dtype)
    return g, b


@warm_cache(name="feature_gram_programs",
            key=("mesh", "mesh_axis", "family", "params"))
def _feature_gram_fn(mesh, mesh_axis: str, family: Optional[str] = None,
                     params: Tuple = ()):
    """Jitted P-sharded executor — same cache economics and
    ``family=None``/fit-program split as ``_data_gram_fn``."""
    from jax.sharding import PartitionSpec as P

    ck = (mesh, mesh_axis, family, params)
    prog = _FEATURE_GRAM_PROGRAMS.get(ck)
    if prog is not None:
        return prog

    if family is None:
        def body(xs, w, y):
            xf, wf, yf = xs.astype(F32), w.astype(F32), y.astype(F32)
            # full row matrix on every device: the priced all-gather
            x_full = jax.lax.all_gather(xf, mesh_axis, axis=2,
                                        tiled=True)
            g_blk = jnp.einsum("bnp,bn,bnq->bpq", x_full, wf, xf)
            b_blk = jnp.einsum("bn,bnp->bp", wf * yf, xf)
            return g_blk, b_blk

        prog = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, mesh_axis), P(None, None),
                      P(None, None)),
            out_specs=(P(None, None, mesh_axis), P(None, mesh_axis)),
            check_vma=False))
    else:
        prog = jax.jit(jax.shard_map(
            _feature_fit_body(mesh_axis, family, params), mesh=mesh,
            in_specs=(P(None, None, mesh_axis), P(None),
                      P(None, None), P(None, None), P(None, None),
                      P(None, None)),
            out_specs=P(None, None), check_vma=False))
    bounded_put(_FEATURE_GRAM_PROGRAMS, ck, prog,
                _GRAM_PROGRAM_CACHE_MAX)
    return prog


def feature_parallel_gram(mesh, xs, w, y, reg: float = 0.0,
                          mesh_axis: str = "data"):
    """Per-task normal equations with the P axis sharded over ``mesh``.

    xs: (B, N, P); w/y: (B, N).  P must be a multiple of the axis size.
    ``mesh_axis`` names the *mesh axis* the P dimension shards over —
    the default host meshes keep their device axis named "data" even
    when this executor splits features across it (the planner's
    "feature" layout).  Each device holds its P/m columns, all-gathers
    the full row matrix (the wire term the planner prices), computes
    its (P, P/m) column block of the Gram and its slice of X'(w*y), and
    the blocks concatenate back into the full statistics.
    """
    g, b = _feature_gram_fn(mesh, mesh_axis)(xs, w, y)
    if reg:
        g = g + reg * jnp.eye(xs.shape[-1], dtype=g.dtype)
    return g, b


def axis_fit_program(mesh, axis: str, family: str, params: Tuple,
                     mesh_axis: str = "data"):
    """The drain entry point (ISSUE 9): the jitted in-mesh bucket
    program executing a data@m/feature@m ``AxisDecision`` at the
    ProgramCache launch signature ``run(pages, data_idx, y, w, valid,
    key_data) -> preds (B, N_pad)``.  ``params`` is the bucket ident's
    sorted hyperparameter tuple (``BucketKey.learner[1]``)."""
    if axis == "data":
        return _data_gram_fn(mesh, mesh_axis, family, tuple(params))
    if axis == "feature":
        return _feature_gram_fn(mesh, mesh_axis, family, tuple(params))
    raise ValueError(f"no in-mesh executor for axis {axis!r}")


def axis_fit_program_cached(mesh, axis: str, family: str, params: Tuple,
                            mesh_axis: str = "data") -> bool:
    """Whether ``axis_fit_program`` would be a warm hit (compile-stats
    attribution in dispatch_bucket, mirroring ProgramCache hit/compile
    counting)."""
    ck = (mesh, mesh_axis, family, tuple(params))
    cache = _DATA_GRAM_PROGRAMS if axis == "data" \
        else _FEATURE_GRAM_PROGRAMS
    return ck in cache


def gram_solve(g, b):
    """The shared ridge/OLS epilogue on reassembled statistics: solve
    G beta = b per task.  Runs replicated — the planner prices the
    solve as unsplittable (launch/roofline.py::_solve_flops)."""
    return jnp.linalg.solve(g, b[..., None])[..., 0]
