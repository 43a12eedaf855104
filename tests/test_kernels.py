"""Per-kernel shape/dtype sweeps + seeded invariant sweeps vs the jnp
oracles.  All Pallas kernels run in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.crossfit_gram import crossfit_gram_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.megabatch import (
    batched_gram_blocked_pallas, batched_gram_pallas,
    batched_predict_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# crossfit_gram
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,p,t,bn", [
    (256, 8, 8, 64), (512, 16, 16, 128), (1024, 24, 8, 256), (128, 4, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_crossfit_gram_sweep(n, p, t, bn, dtype):
    k = jax.random.key(n + p + t)
    x = jax.random.normal(k, (n, p), jnp.float32).astype(dtype)
    w = (jax.random.uniform(jax.random.fold_in(k, 1), (t, n)) > 0.4) \
        .astype(dtype)
    y = jax.random.normal(jax.random.fold_in(k, 2), (t, n)).astype(dtype)
    g, b = crossfit_gram_pallas(x, w, y, block_t=8, block_n=bn,
                                interpret=True)
    g0, b0 = ref.crossfit_gram_ref(x, w, y)
    scale = max(float(jnp.max(jnp.abs(g0))), 1.0)
    assert float(jnp.max(jnp.abs(g - g0))) / scale < TOL[dtype]
    bscale = max(float(jnp.max(jnp.abs(b0))), 1.0)
    assert float(jnp.max(jnp.abs(b - b0))) / bscale < TOL[dtype]


@pytest.mark.parametrize("seed", [0, 17, 256, 511, 999])
def test_gram_mask_of_ones_equals_plain_gram(seed):
    k = jax.random.key(seed)
    x = jax.random.normal(k, (128, 6), jnp.float32)
    w = jnp.ones((8, 128), jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 1), (8, 128), jnp.float32)
    g, _ = crossfit_gram_pallas(x, w, y, block_t=8, block_n=64,
                                interpret=True)
    plain = x.T @ x
    for t in range(8):
        np.testing.assert_allclose(np.asarray(g[t]), np.asarray(plain),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seed", [1, 42, 300, 777, 1000])
def test_gram_additivity_over_disjoint_masks(seed):
    """G(w1) + G(w2) == G(w1+w2) for disjoint masks — the fold-partition
    structure the paper's grid relies on."""
    k = jax.random.key(seed)
    x = jax.random.normal(k, (128, 5), jnp.float32)
    m = jax.random.uniform(jax.random.fold_in(k, 1), (128,)) > 0.5
    ones = jnp.ones_like(m)
    w = jnp.stack([m, ~m, ones, m, ~m, ones, m, ~m]).astype(jnp.float32)
    y = jnp.ones((8, 128), jnp.float32)
    g, b = crossfit_gram_pallas(x, w, y, block_t=8, block_n=64,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(g[0] + g[1]), np.asarray(g[2]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b[0] + b[1]), np.asarray(b[2]),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# megabatch kernels (per-task feature pages)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,n,p,bn", [
    (8, 128, 8, 8), (16, 256, 16, 128), (8, 64, 24, 8),
])
def test_batched_gram_sweep(b, n, p, bn):
    k = jax.random.key(b + n + p)
    xs = jax.random.normal(k, (b, n, p), jnp.float32)
    w = (jax.random.uniform(jax.random.fold_in(k, 1), (b, n)) > 0.4) \
        .astype(jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 2), (b, n), jnp.float32)
    xs_pad = jnp.pad(xs, ((0, 0), (0, 0), (0, 128 - p)))
    g, bv = batched_gram_pallas(xs_pad, w, y, block_b=8, block_n=bn,
                                interpret=True)
    g0, b0 = ref.batched_gram_ref(xs, w, y)
    scale = max(float(jnp.max(jnp.abs(g0))), 1.0)
    assert float(jnp.max(jnp.abs(g[:, :p, :p] - g0))) / scale < 2e-4
    bscale = max(float(jnp.max(jnp.abs(b0))), 1.0)
    assert float(jnp.max(jnp.abs(bv[:, :p] - b0))) / bscale < 2e-4


def test_batched_gram_matches_crossfit_gram_on_shared_x():
    """A bucket whose tasks all share one dataset must reproduce the
    shared-X crossfit_gram kernel exactly (same math, new layout)."""
    k = jax.random.key(3)
    n, p, t = 128, 8, 8
    x = jax.random.normal(k, (n, p), jnp.float32)
    w = (jax.random.uniform(jax.random.fold_in(k, 1), (t, n)) > 0.3) \
        .astype(jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 2), (t, n), jnp.float32)
    xs = jnp.broadcast_to(x, (t, n, p))
    xs_pad = jnp.pad(xs, ((0, 0), (0, 0), (0, 128 - p)))
    g, bv = batched_gram_pallas(xs_pad, w, y, block_b=8, block_n=8,
                                interpret=True)
    g0, b0 = ref.crossfit_gram_ref(x, w, y)
    np.testing.assert_allclose(np.asarray(g[:, :p, :p]), np.asarray(g0),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(bv[:, :p]), np.asarray(b0),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,n,p,bn", [(8, 128, 8, 8), (16, 256, 32, 128)])
def test_batched_predict_masks_padding(b, n, p, bn):
    k = jax.random.key(b * n + p)
    xs = jax.random.normal(k, (b, n, p), jnp.float32)
    beta = jax.random.normal(jax.random.fold_in(k, 1), (b, p), jnp.float32)
    valid = (jax.random.uniform(jax.random.fold_in(k, 2), (b, n)) > 0.25) \
        .astype(jnp.float32)
    xs_pad = jnp.pad(xs, ((0, 0), (0, 0), (0, 128 - p)))
    beta_pad = jnp.pad(beta, ((0, 0), (0, 128 - p)))
    o = batched_predict_pallas(xs_pad, beta_pad, valid, block_b=8,
                               block_n=bn, interpret=True)
    o0 = ref.batched_predict_ref(xs, beta, valid)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o0), rtol=1e-4,
                               atol=1e-4)
    assert float(jnp.max(jnp.abs(jnp.where(valid == 0, o, 0.0)))) == 0.0


# ---------------------------------------------------------------------------
# streaming blocked Gram (ISSUE 8 tall-N path)
# ---------------------------------------------------------------------------
def _tall_case(b, n, p, seed=0):
    k = jax.random.key(seed)
    xs = jax.random.normal(k, (b, n, p), jnp.float32)
    w = (jax.random.uniform(jax.random.fold_in(k, 1), (b, n)) > 0.3) \
        .astype(jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 2), (b, n), jnp.float32)
    return xs, w, y


@pytest.mark.parametrize("chunk", [256, 512])
def test_blocked_gram_pallas_bitwise_on_exact_tiling(chunk):
    """Exact tiling (chunk divides N at kernel-block boundaries) keeps
    the blocked kernel's partial-sum order identical to the unblocked
    kernel's n-block loop: BITWISE equality, the contract the Gram
    families (BLOCKED_GRAM_BITWISE_FAMILIES) rely on.  chunk == N is
    the single-chunk degenerate case."""
    from repro.kernels import ops
    b, n, p = 8, 512, 16
    xs, w, y = _tall_case(b, n, p, seed=chunk)
    xs_pad = jnp.pad(xs, ((0, 0), (0, 0), (0, 128 - p)))
    g0, b0 = batched_gram_pallas(xs_pad, w, y, block_b=8, block_n=256,
                                 interpret=True)
    xc, wc, yc = ops.chunk_tall_n(xs_pad, w, y, chunk)
    g, bv = batched_gram_blocked_pallas(xc, wc, yc, block_b=8,
                                        block_n=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g0))
    np.testing.assert_array_equal(np.asarray(bv), np.asarray(b0))


@pytest.mark.parametrize("n,chunk", [(1024, 256), (512, 512), (768, 128)])
def test_blocked_gram_ops_bitwise_exact_tiling(n, chunk):
    """The ops-level wrapper pair: chunk_tall_n + batched_gram_blocked
    reproduces batched_gram bitwise whenever the chunk grid tiles N
    exactly (including the reg epilogue)."""
    from repro.kernels import ops
    xs, w, y = _tall_case(4, n, 12, seed=n)
    g0, b0 = ops.batched_gram(xs, w, y, reg=0.5)
    xc, wc, yc = ops.chunk_tall_n(xs, w, y, chunk)
    g, bv = ops.batched_gram_blocked(xc, wc, yc, reg=0.5)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g0))
    np.testing.assert_array_equal(np.asarray(bv), np.asarray(b0))


@pytest.mark.parametrize("n,chunk", [(1000, 384), (700, 256)])
def test_blocked_gram_ragged_tail_tolerance(n, chunk):
    """A ragged tail (chunk does not divide N) re-chunks the N-axis
    reduction tree, so equality is the explicit ~1e-4 tolerance tier —
    never bitwise, and tests must not pretend otherwise."""
    from repro.kernels import ops
    xs, w, y = _tall_case(4, n, 12, seed=n)
    g0, b0 = ops.batched_gram(xs, w, y)
    xc, wc, yc = ops.chunk_tall_n(xs, w, y, chunk)
    assert xc.shape[1] * xc.shape[2] > n          # really padded
    g, bv = ops.batched_gram_blocked(xc, wc, yc)
    scale = max(float(jnp.max(jnp.abs(g0))), 1.0)
    assert float(jnp.max(jnp.abs(g - g0))) / scale < 1e-3
    bscale = max(float(jnp.max(jnp.abs(b0))), 1.0)
    assert float(jnp.max(jnp.abs(bv - b0))) / bscale < 1e-3


def test_blocked_gram_masked_padding_rows_inert():
    """Zero-weight padded rows are exact no-ops: garbage feature values
    in w == 0 rows produce bitwise the same statistics as zero rows —
    the proof obligation for chunk_tall_n's tail padding."""
    from repro.kernels import ops
    xs, w, y = _tall_case(4, 512, 12, seed=7)
    xc, wc, yc = ops.chunk_tall_n(xs, w, y, 256)
    # poison the last 100 rows of the final chunk and zero their weight
    wc = wc.at[:, -1, -100:].set(0.0)
    poisoned = xc.at[:, -1, -100:, :].set(1e6)
    zeroed = xc.at[:, -1, -100:, :].set(0.0)
    yp = yc.at[:, -1, -100:].set(1e6)
    g1, b1 = ops.batched_gram_blocked(poisoned, wc, yp)
    g2, b2 = ops.batched_gram_blocked(zeroed, wc, yc)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))


# ---------------------------------------------------------------------------
# ops wrappers on their Pallas branch (interpret mode): the N tiling the
# wrappers pick (128-row blocks below 256 rows, ragged chunks padded)
# ---------------------------------------------------------------------------
def _interpret_wrapper(fn, **static):
    """An ops wrapper traced with Pallas forced on.  JAX caches traces
    per Python function, and the wrapper may already hold a trace of
    its oracle branch for the same shapes: a new function object gets a
    trace of its own."""
    from repro import runtime
    inner = jax.jit(lambda *a: fn.__wrapped__(*a, **static))

    def call(*args):
        with runtime.flags(force_pallas="interpret"):
            return inner(*args)
    return call


def _assert_close(got, want, tol=2e-4):
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got - want))) / scale < tol


@pytest.mark.parametrize("n", [104, 200])
def test_ops_pallas_small_n_matches_ref(n):
    from repro.kernels import ops
    b, p = 12, 17
    xs, w, y = _tall_case(b, n, p, seed=n)
    g, bv = _interpret_wrapper(ops.batched_gram, reg=0.5)(xs, w, y)
    g0, b0 = ref.batched_gram_ref(xs, w, y, reg=0.5)
    _assert_close(g, g0)
    _assert_close(bv, b0)
    beta = jax.random.normal(jax.random.key(n), (b, p), jnp.float32)
    o = _interpret_wrapper(ops.batched_predict)(xs, beta, w)
    np.testing.assert_allclose(np.asarray(o), np.asarray(
        ref.batched_predict_ref(xs, beta, w)), rtol=1e-4, atol=1e-4)
    gc, bc = _interpret_wrapper(ops.crossfit_gram, reg=0.5)(xs[0], w, y)
    gc0, bc0 = ref.crossfit_gram_ref(xs[0], w, y, reg=0.5)
    _assert_close(gc, gc0)
    _assert_close(bc, bc0)


@pytest.mark.parametrize("n,chunk", [(1000, 300), (700, 256)])
def test_ops_pallas_blocked_ragged_chunks_match_ref(n, chunk):
    """Ragged Nc (not a multiple of 256) runs 128-row blocks over each
    chunk padded with w == 0 rows: the oracle's statistics within the
    tolerance tier."""
    from repro.kernels import ops
    xs, w, y = _tall_case(4, n, 12, seed=n)
    xc, wc, yc = ops.chunk_tall_n(xs, w, y, chunk)
    g, bv = _interpret_wrapper(ops.batched_gram_blocked)(xc, wc, yc)
    g0, b0 = ref.batched_gram_ref(xs, w, y)
    _assert_close(g, g0, 1e-3)
    _assert_close(bv, b0, 1e-3)


@pytest.mark.parametrize("n,chunk", [(1024, 256), (768, 768)])
def test_ops_pallas_blocked_bitwise_on_exact_tiling(n, chunk):
    """On the wrappers' Pallas branch, chunks that tile N in 256-row
    blocks reproduce the unblocked kernel bitwise."""
    from repro.kernels import ops
    xs, w, y = _tall_case(4, n, 12, seed=n + 1)
    g0, b0 = _interpret_wrapper(ops.batched_gram, reg=0.5)(xs, w, y)
    xc, wc, yc = ops.chunk_tall_n(xs, w, y, chunk)
    g, bv = _interpret_wrapper(ops.batched_gram_blocked, reg=0.5)(
        xc, wc, yc)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g0))
    np.testing.assert_array_equal(np.asarray(bv), np.asarray(b0))


def test_ops_never_interpret_on_tpu(monkeypatch):
    """On a TPU backend the wrappers take the Pallas branch compiled by
    Mosaic, whatever ``force_pallas`` says: interpret mode is a CPU
    test device only."""
    from repro import runtime
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    for forced in ("", "interpret"):
        with runtime.flags(force_pallas=forced):
            assert ops._use_pallas() and not ops._interpret()


def test_data_and_feature_parallel_gram_executors():
    """The in-mesh executors for the planner's non-task axes agree with
    the single-device statistics to the documented tolerance tier:
    data-parallel psums row-shard partials (reduction tree changes) and
    feature-parallel's narrower column blocks let XLA retile the N
    contraction — neither is a bitwise path (task-parallel is)."""
    from jax.sharding import Mesh
    from repro.kernels import ref
    from repro.sharding.gram import (
        data_parallel_gram, feature_parallel_gram, gram_solve,
    )
    mesh = Mesh(np.array(jax.devices()), ("data",))
    m = mesh.shape["data"]
    b, n, p = 4, 64 * max(m, 2), 8 * max(m, 2)
    xs, w, y = _tall_case(b, n, p, seed=5)
    g0, b0 = ref.batched_gram_ref(xs, w, y)
    gd, bd = data_parallel_gram(mesh, xs, w, y)
    scale = max(float(jnp.max(jnp.abs(g0))), 1.0)
    assert float(jnp.max(jnp.abs(gd - g0))) / scale < 1e-3
    gf, bf = feature_parallel_gram(mesh, xs, w, y)
    assert float(jnp.max(jnp.abs(gf - g0))) / scale < 1e-3
    bscale = max(float(jnp.max(jnp.abs(b0))), 1.0)
    assert float(jnp.max(jnp.abs(bf - b0))) / bscale < 1e-3
    # reassembled statistics solve to the same coefficients
    beta = gram_solve(gd + 0.1 * jnp.eye(p), bd)
    beta0 = gram_solve(g0 + 0.1 * jnp.eye(p), b0)
    np.testing.assert_allclose(np.asarray(beta), np.asarray(beta0),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sq,skv,d,bq,bk", [
    (128, 128, 32, 64, 64), (256, 256, 64, 64, 128),
    (64, 256, 32, 32, 64),                       # chunked-prefill shape
])
@pytest.mark.parametrize("causal,window", [
    (True, None), (True, 48), (False, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(sq, skv, d, bq, bk, causal, window, dtype):
    k = jax.random.key(sq + skv + d)
    q = jax.random.normal(k, (3, sq, d), jnp.float32).astype(dtype)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (3, skv, d),
                           jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(k, 2), (3, skv, d),
                          jnp.float32).astype(dtype)
    o = flash_attention_pallas(q, kk, v, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=True)
    o0 = ref.flash_attention_ref(q, kk, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - o0.astype(jnp.float32))))
    assert err < TOL[dtype], err


@pytest.mark.parametrize("seed", [0, 5, 123, 888])
def test_flash_attention_batch_permutation_equivariance(seed):
    k = jax.random.key(seed)
    q = jax.random.normal(k, (4, 64, 16), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (4, 64, 16), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (4, 64, 16), jnp.float32)
    perm = jax.random.permutation(jax.random.fold_in(k, 3), 4)
    o1 = flash_attention_pallas(q, kk, v, block_q=32, block_k=32,
                                interpret=True)[perm]
    o2 = flash_attention_pallas(q[perm], kk[perm], v[perm],
                                block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_uniform_values():
    """With identical V rows the output equals V regardless of scores."""
    q = jax.random.normal(jax.random.key(0), (2, 64, 16), jnp.float32)
    kk = jax.random.normal(jax.random.key(1), (2, 64, 16), jnp.float32)
    v = jnp.broadcast_to(jnp.arange(16, dtype=jnp.float32), (2, 64, 16))
    o = flash_attention_pallas(q, kk, v, block_q=32, block_k=32,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,p,n,chunk", [
    (128, 16, 8, 32), (256, 64, 16, 64), (64, 32, 32, 64),
])
def test_ssd_scan_sweep(s, p, n, chunk):
    k = jax.random.key(s + p + n)
    xb = jax.random.normal(k, (2, s, p), jnp.float32)
    la = -jax.random.uniform(jax.random.fold_in(k, 1), (2, s)) * 2.0
    bm = jax.random.normal(jax.random.fold_in(k, 2), (2, s, n), jnp.float32)
    cm = jax.random.normal(jax.random.fold_in(k, 3), (2, s, n), jnp.float32)
    y = ssd_scan_pallas(xb, la, bm, cm, chunk=chunk, interpret=True)
    y0, _ = ref.ssd_scan_ref(xb, la, bm, cm)
    scale = max(float(jnp.max(jnp.abs(y0))), 1.0)
    assert float(jnp.max(jnp.abs(y - y0))) / scale < 2e-4


def test_ssd_zero_decay_is_cumulative_outer_product():
    """la = 0 => S_t = sum_j<=t B_j x_j^T: y_t = C_t . cumsum."""
    s, p, n = 32, 4, 3
    k = jax.random.key(0)
    xb = jax.random.normal(k, (1, s, p), jnp.float32)
    bm = jax.random.normal(jax.random.fold_in(k, 1), (1, s, n), jnp.float32)
    cm = jax.random.normal(jax.random.fold_in(k, 2), (1, s, n), jnp.float32)
    la = jnp.zeros((1, s), jnp.float32)
    y = ssd_scan_pallas(xb, la, bm, cm, chunk=16, interpret=True)
    states = jnp.cumsum(jnp.einsum("bsn,bsp->bsnp", bm, xb), axis=1)
    y0 = jnp.einsum("bsn,bsnp->bsp", cm, states)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("seed", [2, 64, 500, 901])
def test_ssd_strong_decay_forgets(seed):
    """Very negative la: state resets, y_t ~= C_t.(B_t x_t^T) only."""
    k = jax.random.key(seed)
    s = 64
    xb = jax.random.normal(k, (1, s, 8), jnp.float32)
    bm = jax.random.normal(jax.random.fold_in(k, 1), (1, s, 4), jnp.float32)
    cm = jax.random.normal(jax.random.fold_in(k, 2), (1, s, 4), jnp.float32)
    la = jnp.full((1, s), -50.0)
    y = ssd_scan_pallas(xb, la, bm, cm, chunk=16, interpret=True)
    y0 = jnp.einsum("bsn,bsn,bsp->bsp", cm, bm, xb)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-3,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# ops wrappers route to the oracle on CPU
# ---------------------------------------------------------------------------
def test_ops_cpu_routing():
    from repro.kernels import ops
    x = jax.random.normal(jax.random.key(0), (100, 7), jnp.float32)
    w = jnp.ones((3, 100), jnp.float32)
    y = jnp.ones((3, 100), jnp.float32)
    g, b = ops.crossfit_gram(x, w, y, reg=1.0)
    g0, b0 = ref.crossfit_gram_ref(x, w, y, reg=1.0)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0), rtol=1e-5)
