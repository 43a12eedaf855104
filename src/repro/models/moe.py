"""Mixture-of-experts FFN: shared + routed experts, top-k routing.

Three dispatch paths, selected by ``ep_size`` (the physical size of the
``experts`` logical axis) and the token count:

* ``local``   — single-device / smoke tests: sort + capacity scatter, no
                collectives.
* ``a2a``     — expert parallelism: ``shard_map`` + ``lax.all_to_all``;
                tokens are sequence-sharded over the expert axis for the
                dispatch, experts live sharded (GShard/DeepSpeed-MoE style).
* ``dense_ep``— decode (few tokens): every expert shard computes its local
                experts' contribution for all tokens, combined with one psum
                (a2a would move less data than it costs in latency at T≈B).

Routed experts may be padded (qwen2-moe 60 -> 64 for EP=16); the router
masks padded experts to -inf so they are never selected.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.param import PDecl
from repro.models.layers import act_fn, mlp_decls, mlp_forward
from repro.sharding.axes import LogicalRules

F32 = jnp.float32


def padded_experts(m: MoEConfig, ep_size: int) -> int:
    e = m.n_routed
    if ep_size > 1 and e % ep_size:
        e = ((e + ep_size - 1) // ep_size) * ep_size
    return e


def moe_decls(cfg: ArchConfig, ep_size: int = 16) -> Dict[str, PDecl]:
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    e = padded_experts(m, ep_size)
    decls = {
        "router": PDecl((d, e), ("embed", None), dtype=jnp.float32),
        "wi": PDecl((e, d, 2, f), ("experts", "embed_tp", None, "expert_ff")),
        "wo": PDecl((e, f, d), ("experts", "expert_ff", "embed_tp")),
    }
    if m.d_shared:
        decls["shared"] = mlp_decls(d, m.d_shared, glu=True)
        if m.shared_gate:
            decls["shared_gate"] = PDecl((d, 1), ("embed", None), dtype=jnp.float32)
    return decls


def _route(p, m: MoEConfig, x_flat, e_pad: int):
    """Router: top-k probs over true experts; padded experts masked."""
    logits = jnp.einsum("td,de->te", x_flat.astype(F32), p["router"])
    if e_pad > m.n_routed:
        neg = jnp.full((x_flat.shape[0], e_pad - m.n_routed), -1e9, F32)
        logits = jnp.concatenate([logits[:, : m.n_routed], neg], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss.
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(top_e, e_pad, dtype=F32).sum(1), axis=0)
    aux = m.n_routed * jnp.sum(dispatch_frac * jnp.mean(probs, axis=0))
    return top_w, top_e, aux


def _capacity(t: int, m: MoEConfig, e_pad: int) -> int:
    """Per-expert token capacity for a dispatch pool of ``t`` tokens.

    Serving-size pools (t <= 256) are dropless — every token can land on a
    single expert.  One rule shared by the local and EP paths: the a2a
    block used to apply the trained-capacity formula to its *local* shard
    pool, which dropped tokens the dropless oracle kept (the jax-0.4.x
    "a2a mismatch" was never the exchange, it was this).
    """
    if t <= 256:
        return t
    return max(int(np.ceil(t * m.top_k / e_pad * m.capacity_factor)),
               m.top_k)


def _expert_mlp(wi, wo, h, act: str):
    """h: (E, C, d) grouped tokens -> (E, C, d)."""
    uv = jnp.einsum("ecd,edgf->ecgf", h, wi)
    u, v = uv[..., 0, :], uv[..., 1, :]
    return jnp.einsum("ecf,efd->ecd", act_fn(act)(u) * v, wo)


def _capacity_dispatch(x_flat, top_w, top_e, e_pad: int, cap: int):
    """Sort+scatter tokens into an (E, cap, d) buffer.

    Returns (buf, se, pos, st, sw, keep) with the bookkeeping needed to
    gather results back to token order.
    """
    t, k = top_e.shape
    e_flat = top_e.reshape(-1)
    w_flat = top_w.reshape(-1)
    tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(e_flat)
    se, st, sw = e_flat[order], tok[order], w_flat[order]
    counts = jnp.bincount(e_flat, length=e_pad)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k) - starts[se]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)  # out-of-range rows -> dropped by mode
    buf = jnp.zeros((e_pad, cap + 1, x_flat.shape[-1]), x_flat.dtype)
    buf = buf.at[se, pos_c].set(x_flat[st], mode="drop")
    return buf[:, :cap], se, pos_c, st, sw, keep


def _combine(y_buf, se, pos_c, st, sw, keep, t: int, cap: int):
    pad = jnp.zeros((y_buf.shape[0], 1, y_buf.shape[-1]), y_buf.dtype)
    yb = jnp.concatenate([y_buf, pad], axis=1)
    rows = yb[se, pos_c] * (sw * keep)[:, None].astype(y_buf.dtype)
    out = jnp.zeros((t, y_buf.shape[-1]), y_buf.dtype).at[st].add(rows)
    return out


def _moe_local(p, cfg: ArchConfig, x, e_pad: int):
    """Single-shard routed path (also the oracle for the EP paths)."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    top_w, top_e, aux = _route(p, m, xf, e_pad)
    cap = _capacity(t, m, e_pad)
    buf, se, pos_c, st, sw, keep = _capacity_dispatch(xf, top_w, top_e, e_pad, cap)
    y_buf = _expert_mlp(p["wi"], p["wo"], buf, cfg.act)
    y = _combine(y_buf, se, pos_c, st, sw, keep, t, cap)
    return y.reshape(b, s, d), aux


def _moe_a2a(p, cfg: ArchConfig, x, e_pad: int, mesh, ep_axis: str,
             dp_axes=None) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel dispatch: sequence-shard tokens over the expert axis,
    all_to_all token groups to their expert shards, grouped GEMM, reverse."""
    m = cfg.moe
    b, s, d = x.shape
    ep = mesh.shape[ep_axis]
    e_loc = e_pad // ep

    def block(xb, router_w, wi_loc, wo_loc):
        bl, sl, _ = xb.shape
        xf = xb.reshape(-1, d)
        t = xf.shape[0]
        top_w, top_e, aux = _route({"router": router_w}, m, xf, e_pad)
        cap = _capacity(t, m, e_pad)
        buf, se, pos_c, st, sw, keep = _capacity_dispatch(
            xf, top_w, top_e, e_pad, cap)
        # (E, cap, d) -> exchange: every shard keeps rows for its local experts
        recv = jax.lax.all_to_all(
            buf.reshape(ep, e_loc, cap, d), ep_axis, 0, 0, tiled=False)
        # recv: (ep, e_loc, cap, d) — sender-major groups for local experts
        h = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d)
        y = _expert_mlp(wi_loc, wo_loc, h, cfg.act)
        y = y.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, ep_axis, 0, 0, tiled=False)
        y_buf = back.reshape(e_pad, cap, d)
        out = _combine(y_buf, se, pos_c, st, sw, keep, t, cap)
        aux = jax.lax.pmean(aux, ep_axis)
        return out.reshape(bl, sl, d), aux

    in_specs = (
        P(dp_axes, ep_axis, None),        # x: tokens seq-sharded over EP axis
        P(None, None),                    # router replicated
        P(ep_axis, None, None, None),     # wi sharded over experts
        P(ep_axis, None, None),           # wo
    )
    out_specs = (P(dp_axes, ep_axis, None), P())
    fn = jax.shard_map(block, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, p["router"], p["wi"], p["wo"])


def _moe_dense_ep(p, cfg: ArchConfig, x, e_pad: int, mesh, ep_axis: str,
                  dp_axes=None) -> Tuple[jax.Array, jax.Array]:
    """Decode path: T is tiny — each expert shard computes its experts'
    contributions for all local tokens, one psum combines."""
    m = cfg.moe
    b, s, d = x.shape
    ep = mesh.shape[ep_axis]
    e_loc = e_pad // ep

    def block(xb, router_w, wi_loc, wo_loc):
        bl, sl, _ = xb.shape
        xf = xb.reshape(-1, d)
        top_w, top_e, aux = _route({"router": router_w}, m, xf, e_pad)
        shard = jax.lax.axis_index(ep_axis)
        e0 = shard * e_loc
        # weight of each local expert for each token (T, e_loc)
        w_local = jnp.zeros((xf.shape[0], e_loc), F32)
        for j in range(m.top_k):
            idx = top_e[:, j] - e0
            hit = (idx >= 0) & (idx < e_loc)
            w_local = w_local.at[jnp.arange(xf.shape[0]),
                                 jnp.clip(idx, 0, e_loc - 1)].add(
                jnp.where(hit, top_w[:, j], 0.0))
        h = jnp.broadcast_to(xf[None], (e_loc,) + xf.shape)
        y = _expert_mlp(wi_loc, wo_loc, h, cfg.act)       # (e_loc, T, d)
        out = jnp.einsum("etd,te->td", y.astype(F32), w_local)
        out = jax.lax.psum(out, ep_axis)
        aux = jax.lax.pmean(aux, ep_axis)
        return out.astype(xb.dtype).reshape(bl, sl, d), aux

    in_specs = (P(dp_axes, None, None), P(None, None),
                P(ep_axis, None, None, None), P(ep_axis, None, None))
    out_specs = (P(dp_axes, None, None), P())
    fn = jax.shard_map(block, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, p["router"], p["wi"], p["wo"])


def moe_forward(p, cfg: ArchConfig, x, rules: LogicalRules,
                mesh=None, ep_axis: Optional[str] = None):
    """Routed + shared experts. Returns (y, aux_loss)."""
    m = cfg.moe
    ep = mesh.shape[ep_axis] if (mesh is not None and ep_axis) else 1
    e_pad = padded_experts(m, ep)
    b, s, d = x.shape
    if ep == 1:
        y, aux = _moe_local(p, cfg, x, e_pad)
    else:
        # batch must divide the data axes for shard_map; degrade to
        # replicated batch otherwise (long-context cells with batch 1)
        dp_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]
        if b % dp_size:
            dp_axes = None
        if s % ep == 0 and b * s >= 256:
            y, aux = _moe_a2a(p, cfg, x, e_pad, mesh, ep_axis, dp_axes)
        else:
            y, aux = _moe_dense_ep(p, cfg, x, e_pad, mesh, ep_axis, dp_axes)
    if m.d_shared:
        sh = mlp_forward(p["shared"], x, cfg.act, glu=True, rules=rules)
        if m.shared_gate:
            gate = jax.nn.sigmoid(
                jnp.einsum("bsd,dg->bsg", x.astype(F32), p["shared_gate"]))
            sh = sh * gate.astype(sh.dtype)
        y = y + sh
    return y, m.router_aux_coef * aux
