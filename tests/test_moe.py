"""MoE dispatch-path equivalence: the shard_map a2a/dense-EP paths must match
the local sort-scatter oracle (same routing, same outputs) on a small mesh."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models.moe import _moe_local, moe_decls, padded_experts
from repro.models.param import init_tree


def test_local_path_routing_weights_sum():
    cfg = get_arch("qwen2-moe-a2.7b", reduced=True)
    decls = moe_decls(cfg, ep_size=1)
    params = init_tree(decls, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          jnp.bfloat16)
    y, aux = _moe_local(params, cfg, x, padded_experts(cfg.moe, 1))
    assert y.shape == x.shape
    assert np.isfinite(float(aux))


def test_padded_experts_never_selected():
    cfg = get_arch("qwen2-moe-a2.7b", reduced=True)   # 8 routed in reduced
    e_pad = padded_experts(cfg.moe, ep_size=16)       # pads 8 -> 16
    assert e_pad == 16
    decls = moe_decls(cfg, ep_size=16)
    params = init_tree(decls, jax.random.key(0))
    from repro.models.moe import _route
    x = jax.random.normal(jax.random.key(1), (64, cfg.d_model), jnp.bfloat16)
    _, top_e, _ = _route(params, cfg.moe, x, e_pad)
    assert int(jnp.max(top_e)) < cfg.moe.n_routed


_EP_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from repro import runtime
from repro.configs import get_arch
from repro.models.moe import moe_forward, moe_decls, _moe_local, padded_experts
from repro.models.param import init_tree
from repro.sharding.axes import MEGATRON_FSDP

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
runtime.mesh_axes = ("data", "model")
cfg = get_arch("deepseek-v2-lite-16b", reduced=True)
decls = moe_decls(cfg, ep_size=2)
params = init_tree(decls, jax.random.key(0))
params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
x = jax.random.normal(jax.random.key(1), (4, 128, cfg.d_model), jnp.float32)

with mesh:
    y_ep, aux_ep = jax.jit(lambda p, x: moe_forward(
        p, cfg, x, MEGATRON_FSDP, mesh=mesh, ep_axis="model"))(params, x)
y_loc, aux_loc = _moe_local(params, cfg, x, padded_experts(cfg.moe, 2))
if "shared" in params:
    from repro.models.layers import mlp_forward
    sh = mlp_forward(params["shared"], x, cfg.act, glu=True,
                     rules=MEGATRON_FSDP)
    y_loc = y_loc + sh
err = float(jnp.max(jnp.abs(y_ep - y_loc)))
scale = float(jnp.max(jnp.abs(y_loc))) + 1e-6
print(json.dumps({"rel_err": err / scale}))
"""


def test_ep_a2a_matches_local_oracle():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _EP_EQUIV],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # capacity boundaries can drop different tokens across layouts; the
    # overwhelming majority of outputs must agree
    assert rec["rel_err"] < 0.05, rec