"""Persistent on-disk program cache (ISSUE 7): cross-process reuse,
spec-keyed invalidation, and corruption eviction.

The cross-process tests are the contract the cache exists for: a fresh
process serving the SAME session workload must compile **zero** programs
— every traced executable comes off disk — while any change to the
learner spec (a fingerprint component) must miss.  They run real
subprocesses because in-process tests cannot prove the serialized
executables survive an interpreter boundary.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

# One tiny drain: a single lasso PLR request through the wave backend,
# printing the compiler + persist counters as JSON on the last line.
# Lasso because its coordinate-descent solver is pure XLA (no LAPACK
# custom calls), so its executables are portable across processes —
# see PersistentProgramCache.portable.
_CHILD = """
import json, sys
from repro.core import DMLData, DMLPlan, DMLSession
from repro.data import make_plr_data

reg = float(sys.argv[1])
data = DMLData.from_dict(make_plr_data(n_obs=64, dim_x=5, theta=0.5, seed=3))
plan = DMLPlan.for_model("plr", learner="lasso", learner_params={"reg": reg},
                         n_folds=2, n_rep=1, seed=7)
sess = DMLSession(backend="wave")
rid = sess.submit(plan, data)
sess.run()
theta = float(sess.result(rid).theta)
s = sess.backend.compiler.stats
persist = sess.backend.compiler.persist
print(json.dumps({
    "theta": theta,
    "compiled": s.misses,
    "disk_hits": s.disk_hits,
    "disk_misses": s.disk_misses,
    "persist": persist.summary() if persist is not None else None,
}))
"""


def _run_child(cache_dir, reg=0.01):
    env = dict(os.environ,
               PYTHONPATH=SRC,
               REPRO_PROGRAM_CACHE_DIR=str(cache_dir))
    out = subprocess.run([sys.executable, "-c", _CHILD, str(reg)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_second_process_compiles_zero_programs(tmp_path):
    """Same session workload twice in fresh processes: the first seeds
    the on-disk store, the second's cold drain compiles NOTHING — every
    program deserializes from the persistent cache."""
    cache_dir = tmp_path / "progcache"
    first = _run_child(cache_dir)
    assert first["compiled"] >= 1          # cold process really compiled
    assert first["disk_hits"] == 0
    assert first["persist"] is not None
    assert first["persist"]["disk_stores"] >= 1

    second = _run_child(cache_dir)
    assert second["compiled"] == 0         # THE contract: zero compiles
    assert second["disk_hits"] >= 1
    assert second["persist"]["disk_errors"] == 0
    # and the deserialized executables compute the same estimate
    np.testing.assert_allclose(second["theta"], first["theta"], rtol=0,
                               atol=0)


@pytest.mark.slow
def test_spec_change_invalidates_cache(tmp_path):
    """Bumping a learner spec field (lasso reg) changes the program
    fingerprint: the warm store must MISS and recompile, never serve the
    old executable."""
    cache_dir = tmp_path / "progcache"
    _run_child(cache_dir, reg=0.01)
    changed = _run_child(cache_dir, reg=0.02)
    assert changed["compiled"] >= 1        # spec change → fresh compile
    assert changed["disk_misses"] >= 1
    assert changed["disk_hits"] == 0


def test_roundtrip_and_corruption_eviction(tmp_path):
    """In-process store/lookup round trip, plus the failure mode: a
    corrupted entry is evicted and reported as a miss, never raised."""
    import jax
    import jax.numpy as jnp

    from repro.compile.persist import (PersistentProgramCache,
                                       backend_platform, jax_build)

    cache = PersistentProgramCache(str(tmp_path / "store"))
    build, platform = jax_build(), backend_platform()
    fp = ("test-v1", "ridge", 8, 8, 8, 8, None, (), False)

    compiled = jax.jit(lambda x: x * 2.0).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    assert cache.lookup(build, platform, fp) is None     # cold miss
    cache.store(build, platform, fp, compiled)
    loaded = cache.lookup(build, platform, fp)
    assert loaded is not None
    np.testing.assert_array_equal(
        np.asarray(loaded(jnp.arange(4, dtype=jnp.float32))),
        np.asarray([0.0, 2.0, 4.0, 6.0]))
    # a different fingerprint never hits
    assert cache.lookup(build, platform, fp[:-1] + (True,)) is None
    # corrupt the entry on disk: lookup evicts it instead of raising
    # (clear the in-process tier first so the disk path actually runs)
    PersistentProgramCache._process_programs.clear()
    (entry,) = Path(cache.cache_dir).glob("*.prog")
    entry.write_bytes(b"not a serialized executable")
    assert cache.lookup(build, platform, fp) is None
    assert not entry.exists()
    assert cache.errors >= 1


def test_custom_call_programs_are_not_persisted(tmp_path):
    """A program whose optimized HLO contains custom calls (LAPACK
    cholesky here) must be REFUSED by the store: its serialized form
    embeds host function pointers and segfaults in the next process.
    Measured on this jaxlib build — see PersistentProgramCache.portable."""
    import jax
    import jax.numpy as jnp

    from repro.compile.persist import (PersistentProgramCache,
                                       backend_platform, jax_build)

    def solve_chol(x, y):
        xtx = x.T @ x + jnp.eye(x.shape[1])
        return jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(xtx), x.T @ y)

    compiled = jax.jit(solve_chol).lower(
        jax.ShapeDtypeStruct((16, 4), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.float32)).compile()
    assert "custom-call" in compiled.as_text()     # probe really applies
    cache = PersistentProgramCache(str(tmp_path / "store"))
    fp = ("test-v1", "chol", 16, 4, 8, 8, None, (), False)
    assert not cache.store(jax_build(), backend_platform(), fp, compiled)
    assert cache.skipped_unportable == 1
    assert list(Path(cache.cache_dir).glob("*.prog")) == []   # no disk entry
    # ...but the IN-PROCESS tier still serves it (pointers are valid
    # within the process — recycled-container reuse), operand-pinned
    # like every AOT executable the cache hands out
    served = cache.lookup(jax_build(), backend_platform(), fp)
    assert served._prog is compiled
    assert cache.loads == 0 and cache.process_hits == 1


def test_aot_calls_pin_host_operands(tmp_path):
    """Direct AOT executable calls (fresh or deserialized) read their
    host operands asynchronously WITHOUT retaining them — a temp numpy
    operand freed right after dispatch is a use-after-free the device
    books as garbage predictions (caught as nondeterministic thetas on
    disk-warm resumed drains).  Every executable the persistent cache
    hands out must therefore be operand-pinned: each call's argument
    tuple stays referenced until that call's outputs land."""
    import jax
    import jax.numpy as jnp

    from repro.compile.persist import (PersistentProgramCache,
                                       _PinnedExecutable,
                                       backend_platform, jax_build,
                                       pin_executable)

    cache = PersistentProgramCache(str(tmp_path / "store"))
    build, platform = jax_build(), backend_platform()
    fp = ("test-v1", "pin", 8, 8, 8, 8, None, (), False)
    compiled = jax.jit(lambda x: x * 2.0).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    cache.store(build, platform, fp, compiled)

    # the process tier serves a pinned wrapper; so does a cold
    # deserialize in a cleared process
    assert isinstance(cache.lookup(build, platform, fp),
                      _PinnedExecutable)
    PersistentProgramCache._process_programs.clear()
    loaded = cache.lookup(build, platform, fp)
    assert isinstance(loaded, _PinnedExecutable)

    # the pin itself: the operand tuple is held from dispatch until the
    # outputs land, then released by the next call's lazy drain
    x = np.arange(4, dtype=np.float32)
    out = loaded(x)
    assert any(a is x for (_, args) in loaded._inflight for a in args)
    jax.block_until_ready(out)
    np.testing.assert_array_equal(np.asarray(out), x * 2.0)
    out2 = loaded(np.zeros(4, np.float32))       # drains the landed call
    jax.block_until_ready(out2)
    assert not any(a is x for (_, args) in loaded._inflight for a in args)

    # a raw wrapper over a plain callable still pins and releases
    pinned = pin_executable(lambda *a: np.float32(0.0))
    y = np.ones(3, np.float32)
    pinned(y)
    ((_, args),) = pinned._inflight
    assert args[0] is y
    pinned(np.zeros(1, np.float32))              # landed (numpy: always
    ((_, args2),) = pinned._inflight             # ready) -> released
    assert args2[0] is not y


def test_compilation_cache_placement(tmp_path, monkeypatch):
    """JAX's persistent compilation cache is placed from outside: with
    JAX_COMPILATION_CACHE_DIR set, neither a session nor an AOT program
    store moves it; without it, it resolves to one fixed path in the
    checkout, the same on every call."""
    import tempfile

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.compile import persist
    from repro.compile.persist import PersistentProgramCache
    from repro.core import DMLSession

    was = jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "from_env")
    try:
        # what JAX reads from the variable when it is imported
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)
        DMLSession(backend="wave")
        PersistentProgramCache(str(tmp_path / "aot"))
        assert jax.config.jax_compilation_cache_dir == env_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jax.config.update("jax_compilation_cache_dir", None)
        first = persist.configure_compilation_cache()
        second = persist.configure_compilation_cache()
        assert first == second == persist.XLA_CACHE_DIR
        assert Path(first).parent == Path(__file__).resolve().parent.parent
        assert not first.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()
