"""The main-path Pallas kernels compile for a TPU v5e chip.

The chip's compiler is installed with jaxlib, so a kernel can be
compiled for a described (not attached) v5e device on any host.  That
catches what interpret mode cannot: Mosaic refuses tiles that break the
(8, 128) layout rule and contractions it has no lowering for.  Each test
drives the public ``ops`` wrapper's TPU branch (padding included) at a
shape the drain really launches and asserts the compiled program holds
the Mosaic kernel (``tpu_custom_call``), not an XLA fallback.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

F32 = jnp.float32
# W1 (the paper's bonus case study): N=5099 rows -> 5104 after the
# bucket's 8-row alignment; 17 controls -> 32 after pow2 P bucketing,
# +1 intercept column; 32 task lanes per launch
W1_B, W1_N, W1_P = 32, 5104, 33


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_wrappers(one_chip):
    """Steer the wrappers onto their TPU branch, and keep these compiles
    out of JAX's persistent cache: an entry compiled for a described
    chip cannot be read back on this host."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "_backend", lambda: "tpu")
    try:
        yield one_chip
    finally:
        mp.undo()
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(wrapper, shapes, sharding, **static):
    # JAX caches traces per Python function: a new function object gets
    # a trace of the TPU branch even if this process already traced the
    # wrapper's CPU branch at the same shapes
    fn = jax.jit(lambda *a: wrapper.__wrapped__(*a, **static))
    avals = [jax.ShapeDtypeStruct(s, F32, sharding=sharding)
             for s in shapes]
    return fn.lower(*avals).compile()


@pytest.mark.parametrize("n", [W1_N, 104])
def test_batched_gram_compiles(tpu_wrappers, n):
    shapes = [(W1_B, n, W1_P), (W1_B, n), (W1_B, n)]
    c = _compile(ops.batched_gram, shapes, tpu_wrappers, reg=1.0)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("n", [W1_N, 104])
def test_batched_predict_compiles(tpu_wrappers, n):
    shapes = [(W1_B, n, W1_P), (W1_B, W1_P), (W1_B, n)]
    c = _compile(ops.batched_predict, shapes, tpu_wrappers)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("c_dim,nc", [(4, 1024), (4, 1276)])
def test_batched_gram_blocked_compiles(tpu_wrappers, c_dim, nc):
    shapes = [(W1_B, c_dim, nc, W1_P), (W1_B, c_dim, nc), (W1_B, c_dim, nc)]
    c = _compile(ops.batched_gram_blocked, shapes, tpu_wrappers)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("n", [5099, 104])
def test_crossfit_gram_compiles(tpu_wrappers, n):
    t, p = 16, 18
    shapes = [(n, p), (t, n), (t, n)]
    c = _compile(ops.crossfit_gram, shapes, tpu_wrappers, reg=1.0)
    assert "tpu_custom_call" in c.as_text()
