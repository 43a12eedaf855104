"""Public jit'd wrappers for the Pallas kernels.

Routing: on TPU backends the Pallas kernel runs natively, compiled by
Mosaic.  On any other backend the wrappers route to the jnp oracle, so
XLA HLO (and hence the dry-run roofline) reflects real math, unless
``repro.runtime.force_pallas`` is set: then the kernel runs in Pallas
interpret mode, which the kernel test-suite uses on CPU.  Interpret mode
is never chosen on a TPU.

Tiling: every w/y/mask tile is (8, block_n) with block_n in the lane
dim, so the wrappers pad N to a multiple of a 128-row block (256 or 512
on tall inputs, for the MXU) and P to 128 lanes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import runtime
from repro.kernels import ref
from repro.kernels.crossfit_gram import crossfit_gram_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.megabatch import (
    batched_gram_blocked_pallas, batched_gram_pallas, batched_predict_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas


def _backend() -> str:
    return jax.default_backend()


def _use_pallas() -> bool:
    return _backend() == "tpu" or bool(runtime.force_pallas)


def _interpret() -> bool:
    return _backend() != "tpu"


def _block_rows(n: int, tall: int) -> int:
    """Rows per N-block: ``tall`` once N reaches it, else 128 (the
    smallest block Mosaic accepts as the lane dim of a (8, bn) tile)."""
    return tall if n >= tall else 128


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("reg",))
def crossfit_gram(x, w, y, reg: float = 0.0):
    """Batched masked normal equations (see crossfit_gram.py).

    x: (N, P); w/y: (T, N).  Returns G (T,P,P) f32, b (T,P) f32 — sliced
    back to the true P after lane padding.
    """
    if not _use_pallas():
        return ref.crossfit_gram_ref(x, w, y, reg)
    n, p = x.shape
    block_n = _block_rows(n, 512)
    xp, p0 = _pad_to(x, 1, 128)          # lane-align features
    xp, _ = _pad_to(xp, 0, block_n)      # N to a block multiple
    padn = xp.shape[0] - n
    if padn:                              # padded rows get zero weight
        w = jnp.pad(w, ((0, 0), (0, padn)))
        y = jnp.pad(y, ((0, 0), (0, padn)))
    w, t0 = _pad_to(w, 0, 8)
    y, _ = _pad_to(y, 0, 8)
    g, b = crossfit_gram_pallas(xp, w, y, block_t=8, block_n=block_n,
                                interpret=_interpret())
    g = g[:t0, :p0, :p0]
    b = b[:t0, :p0]
    if reg:
        g = g + reg * jnp.eye(p0, dtype=g.dtype)
    return g, b


@functools.partial(jax.jit, static_argnames=("reg",))
def batched_gram(xs, w, y, reg: float = 0.0):
    """Per-task masked normal equations with per-task features.

    xs: (B, N, P); w/y: (B, N).  Returns G (B,P,P) f32, b (B,P) f32 —
    sliced back to the true P after lane padding.  The megabatch analogue
    of ``crossfit_gram`` for buckets that mix datasets.
    """
    if not _use_pallas():
        return ref.batched_gram_ref(xs, w, y, reg)
    b_dim, n, p = xs.shape
    block_n = _block_rows(n, 256)
    xp, _ = _pad_to(xs, 2, 128)          # lane-align features
    p0 = p
    xp, _ = _pad_to(xp, 1, block_n)      # N to a block multiple
    padn = xp.shape[1] - n
    if padn:                              # padded rows get zero weight
        w = jnp.pad(w, ((0, 0), (0, padn)))
        y = jnp.pad(y, ((0, 0), (0, padn)))
    xp, b0 = _pad_to(xp, 0, 8)           # task-batch to sublane multiple
    w, _ = _pad_to(w, 0, 8)
    y, _ = _pad_to(y, 0, 8)
    g, bv = batched_gram_pallas(xp, w, y, block_b=8, block_n=block_n,
                                interpret=_interpret())
    g = g[:b0, :p0, :p0]
    bv = bv[:b0, :p0]
    if reg:
        g = g + reg * jnp.eye(p0, dtype=g.dtype)
    return g, bv


# Blocked-Gram parity tiers (ISSUE 8).  For families whose fit is a
# pure function of the Gram statistics (X'X, X'y), streaming the N axis
# chunk-by-chunk adds partial sums in the same order as the unblocked
# kernel's n-block loop, so results are bitwise-equal.  Families whose
# iterations re-reduce per-row activations (logistic's sigmoid pass,
# kernel_ridge's kernel matrix, mlp's backprop) genuinely reorder float
# accumulation when N is re-chunked — they get an explicit tolerance
# tier instead of a false bitwise promise.
BLOCKED_GRAM_BITWISE_FAMILIES = frozenset({"ols", "ridge", "lasso"})
BLOCKED_GRAM_TOLERANCE_FAMILIES = frozenset(
    {"logistic", "kernel_ridge", "mlp"})


def chunk_tall_n(xs, w, y, chunk_rows: int):
    """Split a tall (B, N, P) task batch into (B, C, Nc, P) N-chunks for
    the streaming blocked Gram path.

    A ragged tail (N % chunk_rows != 0) is padded with w == 0 rows, which
    the kernel's masked accumulation treats as exact no-ops.  Pure
    relayout otherwise — no float arithmetic.
    """
    b_dim, n, p = xs.shape
    nc = int(chunk_rows)
    pad = (-n) % nc
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
    c = (n + pad) // nc
    return (xs.reshape(b_dim, c, nc, p), w.reshape(b_dim, c, nc),
            y.reshape(b_dim, c, nc))


@functools.partial(jax.jit, static_argnames=("reg",))
def batched_gram_blocked(xc, w, y, reg: float = 0.0):
    """Streaming blocked Gram: per-task normal equations accumulated
    over pre-chunked N.

    xc: (B, C, Nc, P); w/y: (B, C, Nc).  Returns G (B,P,P) f32,
    b (B,P) f32 — the same contract as ``batched_gram`` on the merged
    (B, C*Nc, P) tensor, but each chunk is streamed through the device
    separately so a task's N never has to fit one page.
    """
    if not _use_pallas():
        return ref.batched_gram_blocked_ref(xc, w, y, reg)
    b_dim, c_dim, nc, p = xc.shape
    # prefer the 256-row MXU block only when it tiles Nc exactly: an
    # exactly-tiled chunk grid keeps partial-sum order identical to the
    # unblocked kernel (bitwise); a ragged Nc falls back to 128-row
    # blocks plus zero-weight padding (tolerance tier)
    block_n = 256 if nc % 256 == 0 else 128
    xp, _ = _pad_to(xc, 3, 128)          # lane-align features
    p0 = p
    xp, _ = _pad_to(xp, 2, block_n)      # Nc to a block multiple
    padn = xp.shape[2] - nc
    if padn:                              # padded rows get zero weight
        w = jnp.pad(w, ((0, 0), (0, 0), (0, padn)))
        y = jnp.pad(y, ((0, 0), (0, 0), (0, padn)))
    xp, b0 = _pad_to(xp, 0, 8)           # task-batch to sublane multiple
    w, _ = _pad_to(w, 0, 8)
    y, _ = _pad_to(y, 0, 8)
    g, bv = batched_gram_blocked_pallas(xp, w, y, block_b=8,
                                        block_n=block_n,
                                        interpret=_interpret())
    g = g[:b0, :p0, :p0]
    bv = bv[:b0, :p0]
    if reg:
        g = g + reg * jnp.eye(p0, dtype=g.dtype)
    return g, bv


@jax.jit
def batched_predict(xs, beta, valid):
    """Masked per-task GEMV epilogue: valid_b * (X_b @ beta_b).

    xs: (B, N, P); beta: (B, P); valid: (B, N) -> (B, N) f32 with padding
    rows exactly 0.
    """
    if not _use_pallas():
        return ref.batched_predict_ref(xs, beta, valid)
    b_dim, n, p = xs.shape
    block_n = _block_rows(n, 256)
    xp, _ = _pad_to(xs, 2, 128)
    bp, _ = _pad_to(beta, 1, 128)
    xp, n0 = _pad_to(xp, 1, block_n)
    padn = xp.shape[1] - n
    if padn:
        valid = jnp.pad(valid, ((0, 0), (0, padn)))
    xp, b0 = _pad_to(xp, 0, 8)
    bp, _ = _pad_to(bp, 0, 8)
    valid, _ = _pad_to(valid, 0, 8)
    out = batched_predict_pallas(xp, bp, valid, block_b=8, block_n=block_n,
                                 interpret=_interpret())
    return out[:b0, :n0]


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 256, block_k: int = 256):
    """q: (BH, Sq, D); k/v: (BH, Skv, D)."""
    if not _use_pallas():
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def ssd_scan(xbar, la, bm, cm, *, chunk: int = 256):
    """xbar: (BH,S,P); la: (BH,S); bm/cm: (BH,S,N) -> (y, final_state)."""
    if not _use_pallas():
        return ref.ssd_scan_ref(xbar, la, bm, cm)
    y = ssd_scan_pallas(xbar, la, bm, cm, chunk=chunk,
                        interpret=_interpret())
    # final state from the oracle recurrence on the last chunk only would
    # need the carried state; recompute cheaply via the reference when needed
    _, state = ref.ssd_scan_ref(xbar, la, bm, cm)
    return y, state
