"""Sharding rules + a small-mesh dry-run smoke in a subprocess (the full
512-device sweep lives in launch/dryrun.py; here a 8-device reduced-config
version proves the machinery end-to-end inside CI)."""
import json
import os
import subprocess
import sys

import pytest

from repro.sharding.axes import MEGATRON_FSDP, SMALL_DP, rules_for
from jax.sharding import PartitionSpec as P


def test_rules_resolve_basic():
    spec = MEGATRON_FSDP.resolve(("embed", "heads", None))
    assert spec == P("data", "model", None)


def test_rules_no_duplicate_axis():
    # batch=("pod","data") then embed->"data" must drop the duplicate
    spec = MEGATRON_FSDP.resolve(("batch", "embed"))
    assert spec[0] == ("pod", "data") or spec[0] == "data"
    assert spec[1] is None or spec[1] != "data" or spec[0] != ("pod", "data")


def test_mesh_axis_filtering():
    from repro import runtime
    runtime.mesh_axes = ("data", "model")       # single-pod mesh
    try:
        spec = MEGATRON_FSDP.resolve(("batch", None, "act_heads"))
        assert spec == P("data", None, "model")
    finally:
        runtime.mesh_axes = None


def test_rules_for_small_vs_big():
    assert rules_for("xlstm-350m", "train", 1024) is SMALL_DP
    assert rules_for("qwen2.5-32b", "train", 5120) is MEGATRON_FSDP
    # long-context decode (batch 1): batch unsharded, KV over (data, model)
    r = rules_for("h2o-danube-3-4b", "decode", 3840, global_batch=1)
    assert r.resolve(("batch", "kv_seq")) == P(None, ("data", "model"))


_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro import runtime
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.launch.dryrun import lower_cell
from repro.launch.roofline import parse_collective_bytes

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
runtime.mesh_axes = ("data", "model")
cfg = get_arch("{arch}", reduced=True)
shape = ShapeConfig("t", seq_len=64, global_batch=8, kind="{kind}")
compiled, ls, cs = lower_cell(cfg, shape, mesh, attn_chunk=32, remat="none")
ma = compiled.memory_analysis()
colls = parse_collective_bytes(compiled.as_text())
print(json.dumps({{"arg": ma.argument_size_in_bytes,
                   "colls": {{k: int(v) for k, v in colls.items()}}}}))
"""


@pytest.mark.parametrize("arch,kind,expect_coll", [
    ("qwen2.5-32b", "train", "all-reduce"),
    ("deepseek-v2-lite-16b", "train", "all-to-all"),
    ("codeqwen1.5-7b", "decode", "all-reduce"),
])
def test_small_mesh_dryrun_subprocess(arch, kind, expect_coll):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SMOKE.format(arch=arch, kind=kind)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["arg"] > 0
    assert expect_coll in rec["colls"], rec["colls"]
