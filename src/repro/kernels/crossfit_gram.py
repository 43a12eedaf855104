"""Pallas TPU kernel: fused per-task masked Gram accumulation.

This is the paper's technique reduced to compute: all T = M*K*L cross-fit
estimation problems share one X, differing only in 0/1 fold masks, so the
per-task normal equations  G_t = X' diag(w_t) X,  b_t = X'(w_t*y_t)  are
accumulated for a *block of tasks at once* in a single tiled pass over X.
One HBM read of X serves bt tasks (vs. T reads in the per-task loop a
serverless worker pool implies) — the arithmetic-intensity win that makes
the TPU adaptation structural rather than concurrency-based (DESIGN.md §2).

Tiling: grid (task_blocks, n_blocks); X tile (bn, P), mask/target tiles
(bt, bn) live in VMEM; the (bt, P, P) f32 accumulator persists in the output
block across the inner n-block loop.  P is padded to a multiple of 128
(lane width) by the wrapper, and N to a multiple of bn, which is a
multiple of 128 because bn is the lane dim of the (bt, bn) mask tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
# f32 contractions on the MXU: without it a dot may run as one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, w_ref, y_ref, g_ref, b_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    x = x_ref[...].astype(F32)                     # (bn, P)
    w = w_ref[...].astype(F32)                     # (bt, bn)
    y = y_ref[...].astype(F32)                     # (bt, bn)
    wx = w[:, :, None] * x[None, :, :]             # (bt, bn, P)
    # batched MXU contraction over the bn axis
    g_ref[...] += jnp.einsum("tnp,nq->tpq", wx, x, precision=HIGHEST,
                             preferred_element_type=F32)
    b_ref[...] += jnp.einsum("tn,np->tp", w * y, x, precision=HIGHEST,
                             preferred_element_type=F32)


def crossfit_gram_pallas(x, w, y, *, block_t: int = 8, block_n: int = 512,
                         interpret: bool = False):
    """x: (N, P); w, y: (T, N) -> (G (T,P,P) f32, b (T,P) f32).

    N must be a multiple of block_n and T of block_t (wrapper pads).
    """
    n, p = x.shape
    t = w.shape[0]
    assert n % block_n == 0 and t % block_t == 0, (n, t, block_n, block_t)
    grid = (t // block_t, n // block_n)
    g, b = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, p), lambda i, j: (j, 0)),
            pl.BlockSpec((block_t, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, block_n), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, p, p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_t, p), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, p, p), F32),
            jax.ShapeDtypeStruct((t, p), F32),
        ],
        interpret=interpret,
    )(x, w, y)
    return g, b
