"""Pallas TPU kernels for megabatch (bucketed) cross-fit programs.

The megabatch compiler (repro/compile) stacks tasks from *different*
requests — hence different datasets — into one ``(B, N_pad, P_pad)``
tensor, so unlike ``crossfit_gram`` (one shared X, many masks) each task
here carries its own feature page.  Two kernels cover the hot linear
path:

``batched_gram_pallas``     per-task masked normal equations
                            G_b = X_b' diag(w_b) X_b,  b_b = X_b'(w_b*y_b)
                            accumulated tile-by-tile over the padded N
                            axis; padded rows carry w == 0 so they are
                            arithmetically inert.
``batched_predict_pallas``  the masked GEMV epilogue
                            preds_b = valid_b * (X_b @ beta_b)
                            that scatters fitted coefficients back to
                            per-row predictions, zeroing padding lanes.
``batched_gram_blocked_pallas``
                            the streaming variant (ISSUE 8): the N axis
                            arrives pre-chunked as (B, C, Nc, P) and is
                            streamed chunk by chunk through the Gram
                            kernel's n-block loop, so results are
                            bitwise-identical to the unblocked kernel
                            when the chunks tile N exactly; ragged tails
                            carry w == 0 rows whose FMA terms are exact
                            zeros.

Tiling mirrors crossfit_gram.py: grid (task_blocks, n_blocks); per-task X
tiles (bb, bn, P) live in VMEM; the (bb, P, P) f32 accumulator persists in
the output block across the inner n-block loop.  The ops.py wrapper pads
P to a multiple of 128 (lanes) and N to a multiple of bn, which is itself
a multiple of 128 because bn is the lane dim of the (bb, bn) w/y tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
# f32 contractions on the MXU: without it a dot may run as one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def _accumulate_gram(x_ref, w_ref, y_ref, g_ref, b_ref):
    """Add one (bb, bn) tile's masked moments into the output block.

    One 2-D MXU matmul per task lane: Mosaic lowers no batched
    ``dot_general`` that contracts a non-minor lhs dim, so the lane loop
    is unrolled at trace time (bb is static).  The weights are moved to
    a column once per tile (``w.T``) so each lane scales its rows by a
    lane-broadcast, and the b row comes out of an aligned (bb, bn) x
    (bn, P) matmul whose row t is task t's moment vector.
    """
    w = w_ref[...].astype(F32)                     # (bb, bn)
    wy = w * y_ref[...].astype(F32)                # (bb, bn)
    wt = w.T                                       # (bn, bb)
    for t in range(x_ref.shape[0]):
        x = x_ref[t].astype(F32)                   # (bn, P)
        wx = wt[:, t:t + 1] * x
        g_ref[t] += jax.lax.dot_general(
            wx, x, (((0,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=F32)
        b_ref[t:t + 1, :] += jax.lax.dot_general(
            wy, x, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=F32)[t:t + 1, :]


def _gram_kernel(x_ref, w_ref, y_ref, g_ref, b_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    _accumulate_gram(x_ref, w_ref, y_ref, g_ref, b_ref)


def batched_gram_pallas(xs, w, y, *, block_b: int = 8, block_n: int = 256,
                        interpret: bool = False):
    """xs: (B, N, P); w, y: (B, N) -> (G (B,P,P) f32, b (B,P) f32).

    N must be a multiple of block_n and B of block_b (wrapper pads).
    """
    b_dim, n, p = xs.shape
    assert n % block_n == 0 and b_dim % block_b == 0, \
        (b_dim, n, block_b, block_n)
    grid = (b_dim // block_b, n // block_n)
    g, bv = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, p, p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_b, p), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_dim, p, p), F32),
            jax.ShapeDtypeStruct((b_dim, p), F32),
        ],
        interpret=interpret,
    )(xs, w, y)
    return g, bv


def batched_gram_blocked_pallas(xc, w, y, *, block_b: int = 8,
                                block_n: int = 256,
                                interpret: bool = False):
    """Streaming blocked Gram over N-chunks.

    xc: (B, C, Nc, P) — the N axis pre-chunked into C streamed pieces of
    Nc rows each; w, y: (B, C, Nc).  Returns (G (B,P,P) f32, b (B,P) f32).

    With Nc a multiple of block_n, merging (C, Nc) into one N axis is a
    free relayout, and the unblocked kernel's n-block loop over it walks
    chunk c's blocks in order before chunk c+1's: the (c, j) stream,
    accumulated in one output block.  So the result is bitwise-equal to
    ``batched_gram_pallas`` on the merged tensor by construction.  Nc
    must be a multiple of block_n and B of block_b (wrapper pads).
    """
    b_dim, c_dim, nc, p = xc.shape
    assert nc % block_n == 0 and b_dim % block_b == 0, \
        (b_dim, c_dim, nc, block_b, block_n)
    n = c_dim * nc
    return batched_gram_pallas(
        xc.reshape(b_dim, n, p), w.reshape(b_dim, n), y.reshape(b_dim, n),
        block_b=block_b, block_n=block_n, interpret=interpret)


def _predict_kernel(x_ref, beta_ref, v_ref, o_ref):
    x = x_ref[...].astype(F32)                     # (bb, bn, P)
    beta = beta_ref[...].astype(F32)               # (bb, P)
    v = v_ref[...].astype(F32)                     # (bb, bn)
    # per-task GEMV on the MXU: (bb, bn, P) x (bb, P) -> (bb, bn)
    pred = jax.lax.dot_general(
        x, beta, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=HIGHEST, preferred_element_type=F32)
    o_ref[...] = pred * v                          # mask padding lanes


def batched_predict_pallas(xs, beta, valid, *, block_b: int = 8,
                           block_n: int = 256, interpret: bool = False):
    """xs: (B, N, P); beta: (B, P); valid: (B, N) -> preds (B, N) f32.

    The masked GEMM/predict epilogue: rows with valid == 0 (padding)
    output exactly 0.  N must be a multiple of block_n, B of block_b.
    """
    b_dim, n, p = xs.shape
    assert n % block_n == 0 and b_dim % block_b == 0, \
        (b_dim, n, block_b, block_n)
    grid = (b_dim // block_b, n // block_n)
    return pl.pallas_call(
        _predict_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_b, p), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b_dim, n), F32),
        interpret=interpret,
    )(xs, beta, valid)
