"""Persistent on-disk megabatch program cache (ISSUE 7 tentpole).

Cold drains used to pay a full trace+compile per (bucket, B, D[, G])
shape even when an identical session ran seconds earlier in another
process — the in-memory ``ProgramCache`` dies with the process.  This
module persists the *compiled executables* across processes:

  * programs are lowered ahead-of-time against their exact argument
    avals (the megabatch calling convention is shape-total: every
    operand shape is a pure function of the bucket key and the padded
    batch shape), serialized via ``jax.experimental.serialize_executable``
    and written to ``REPRO_PROGRAM_CACHE_DIR``;
  * JAX's own XLA compilation cache covers every other tracing path
    (partitioned programs, probe traces).  It stays where
    ``JAX_COMPILATION_CACHE_DIR`` puts it; without that variable it
    lives at one fixed path in the checkout
    (``configure_compilation_cache``), never under this store.

Deserializing an executable is ~14x cheaper than compiling it on this
backend, which is what flips the BENCH_fusion cold gate: a disk-warm
cold drain re-traces **zero** programs.

**Custom-call portability (measured, this jaxlib/CPU build):** an
executable serialized via ``serialize_executable`` embeds raw host
function pointers for its custom-call targets (LAPACK/BLAS kernels),
even the name-registered ``_ffi`` variants — deserializing one in a
fresh process and calling it segfaults under ASLR.  JAX's own XLA
compilation cache does NOT have this problem (it re-links targets at
load), so the split is: custom-call-bearing programs (ols, ridge,
logistic, kernel_ridge solvers) rely on the XLA cache for cross-process
cold-compile relief, while custom-call-free programs (lasso, mlp — pure
XLA iterative solvers) additionally skip tracing entirely through the
AOT store.  ``store()`` enforces this by scanning the optimized HLO and
refusing to persist non-portable executables (``skipped_unportable``).

A third tier covers the recycled-container case (same process, fresh
backend): ``_process_programs`` is a process-wide map over the same
``(build, platform, fingerprint)`` key, safe for ALL programs —
including custom-call ones — because host pointers stay valid within
the process.  A warm container's "cold" drain therefore compiles zero
programs regardless of portability.

Key discipline (the ninth ``@warm_cache`` contract, audited by
``analysis/cache_keys.py``): a serialized executable is only valid for
the exact jax build, backend platform, and program shape that produced
it, so the lookup key is ``(jax_build, platform, fingerprint)`` — the
fingerprint pins the resolved learner spec (never an object identity),
the padded shapes, the PRNG key-data layout, and the x64 mode.  Opaque
callables have process-local identity and are never persisted.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.registry import warm_cache

# Environment switch: set to a directory path to enable cross-process
# program persistence.  Unset (the default) keeps the compile layer
# purely in-memory — zero behavior change for existing callers.
ENV_CACHE_DIR = "REPRO_PROGRAM_CACHE_DIR"

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed directory in the checkout (git-ignored).  The path is
# part of what makes an entry found again, so it never holds a
# temporary name, a pid or a time.
XLA_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compilation_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself at import) wins,
    as does a directory the caller already configured; otherwise the
    cache goes to ``XLA_CACHE_DIR``.  Called at compiler set-up
    (``ProgramCache``), before the first program compiles.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            and not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def _key_tail() -> Tuple[int, ...]:
    """Trailing shape of one task's PRNG key data under the process's
    configured key implementation (threefry: (2,))."""
    return tuple(jax.random.key_data(jax.random.key(0)).shape)


class _PinnedExecutable:
    """Operand-lifetime guard for direct AOT executable calls.

    ``jit`` dispatch retains the caller's host operands while the
    asynchronous transfer/execution reads them; a ``lower().compile()``
    executable — fresh or deserialized — does NOT.  The dispatch path
    hands these executables temporary numpy operands (morphed batch
    tensors, per-launch ``didx`` lane maps) and drops every reference
    the moment the call returns, so the async read races Python's
    allocator: a freed-and-reused buffer reaches the device as garbage
    inputs and books garbage predictions (observed as nondeterministic
    thetas on disk-warm resumed drains).

    The wrapper pins each call's operand tuple until that call's
    outputs land, releasing landed calls lazily on the next dispatch —
    steady state holds at most the pipeline depth.  Calls happen on
    one drain thread, so no locking.
    """

    __slots__ = ("_prog", "_inflight")

    def __init__(self, prog):
        self._prog = prog
        self._inflight: list = []

    def _release_landed(self) -> None:
        self._inflight[:] = [
            (out, args) for out, args in self._inflight
            if not all(getattr(o, "is_ready", lambda: True)()
                       for o in jax.tree_util.tree_leaves(out))]
        # backstop: a caller that never drains still can't pin
        # unbounded host memory behind un-landed launches
        while len(self._inflight) > 64:
            out, _ = self._inflight.pop(0)
            jax.block_until_ready(out)

    def __call__(self, *args):
        self._release_landed()
        out = self._prog(*args)
        self._inflight.append((out, args))
        return out


def pin_executable(prog) -> _PinnedExecutable:
    """Wrap an AOT executable so every call keeps its host operands
    alive until the outputs land (see ``_PinnedExecutable``)."""
    return _PinnedExecutable(prog)


def jax_build() -> str:
    """The jax build a serialized executable is valid for."""
    import jaxlib
    return f"jax-{jax.__version__}+jaxlib-{jaxlib.__version__}"


def backend_platform() -> str:
    """The backend platform (and device kind) executables target."""
    return f"{jax.default_backend()}:{jax.devices()[0].device_kind}"


def program_fingerprint(key, b_pad: int, d_pad: int,
                        g: Optional[int] = None) -> Optional[Tuple]:
    """Value identity of one compiled megabatch program, stable across
    processes — or None when the program must not be persisted.

    The learner identity must be a resolved spec tuple
    ``(family, params)``: opaque callables key by ``id()`` which is
    process-local, so persisting them would alias unrelated programs.
    """
    ident = key.learner
    if not (isinstance(ident, tuple) and len(ident) == 2
            and isinstance(ident[0], str) and ident[0] != "opaque"):
        return None
    return ("megabatch-v1", repr(ident), int(key.n_pad), int(key.p_pad),
            int(b_pad), int(d_pad), None if g is None else int(g),
            _key_tail(), bool(jax.config.jax_enable_x64))


def program_avals(key, b_pad: int, d_pad: int,
                  g: Optional[int] = None) -> Tuple:
    """Exact argument avals of the megabatch calling convention
    ``run(pages, data_idx, y, w, valid, key_data)`` — single-block when
    ``g`` is None, fused (leading block axis) otherwise."""
    n_pad, p_pad = int(key.n_pad), int(key.p_pad)
    kt = _key_tail()
    lead = () if g is None else (int(g),)
    shapes = ((int(d_pad), n_pad, p_pad),          # pages
              lead + (int(b_pad),),                # data_idx
              lead + (int(b_pad), n_pad),          # y
              lead + (int(b_pad), n_pad),          # w
              lead + (int(b_pad), n_pad),          # valid
              lead + (int(b_pad),) + kt)           # key_data
    dtypes = (jnp.float32, jnp.int32, jnp.float32, jnp.float32,
              jnp.float32, jnp.uint32)
    return tuple(jax.ShapeDtypeStruct(s, d) for s, d in zip(shapes, dtypes))


class PersistentProgramCache:
    """Directory of AOT-serialized megabatch executables.

    One file per ``(jax_build, platform, fingerprint)``; writes are
    atomic (tmp + rename) so concurrent processes sharing a cache
    directory never observe torn blobs, and unreadable/stale entries
    are treated as misses and evicted.
    """

    #: process-wide L1 over the disk tier, shared by every instance:
    #: a recycled container (same process, fresh backend/ProgramCache)
    #: reuses already-compiled executables without re-tracing — and
    #: unlike the disk tier this is safe for custom-call programs too,
    #: because the baked host pointers are valid within the process.
    #: Keyed by the SAME (build, platform, fingerprint) triple as disk.
    _process_programs: dict = {}
    _PROCESS_CAP = 256

    def __init__(self, cache_dir: str):
        self.cache_dir = cache_dir
        self.loads = 0                  # executables deserialized from disk
        self.process_hits = 0           # served from the in-process tier
        self.stores = 0                 # executables serialized to disk
        self.errors = 0                 # unreadable / unserializable entries
        self.skipped_unportable = 0     # custom-call programs not persisted
        os.makedirs(cache_dir, exist_ok=True)

    def _path(self, build: str, platform: str, fingerprint: Tuple) -> str:
        h = hashlib.sha1(
            repr((build, platform, fingerprint)).encode()).hexdigest()
        return os.path.join(self.cache_dir, f"{h}.prog")

    # Both tiers cache under the SAME full triple: the jax build and
    # platform pin the executable format, the fingerprint pins the
    # program (resolved spec + padded shapes + key layout + x64 mode).
    # This is the only insert site of the process-wide tier — lookup's
    # disk path and store both remember through it.
    @warm_cache(name="persistent_program_cache_process_tier",
                key=("build", "platform", "fingerprint"),
                reads=("prog",),
                covers={"fingerprint": ("prog",)},
                ambient=("self",))
    def _process_put(self, build: str, platform: str, fingerprint: Tuple,
                     prog) -> None:
        from repro.runtime import bounded_put
        bounded_put(self._process_programs,
                    (build, platform, fingerprint), prog,
                    self._PROCESS_CAP)

    # The on-disk entry is a pure function of the full lookup key (same
    # triple as the process tier).  The directory handle is instance
    # state (ambient).
    @warm_cache(name="persistent_program_cache",
                key=("build", "platform", "fingerprint"),
                ambient=("self",))
    def lookup(self, build: str, platform: str, fingerprint: Tuple):
        """Serve from the in-process tier, else deserialize a
        previously-stored executable from disk, else None."""
        prog = self._process_programs.get((build, platform, fingerprint))
        if prog is not None:
            self.process_hits += 1
            return prog
        path = self._path(build, platform, fingerprint)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
            from jax.experimental import serialize_executable as se
            prog = pin_executable(
                se.deserialize_and_load(payload, in_tree, out_tree))
        except Exception:
            # stale jax build, torn write, foreign blob: evict and miss
            self.errors += 1
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        self.loads += 1
        self._process_put(build, platform, fingerprint, prog)
        return prog

    @staticmethod
    def portable(compiled) -> bool:
        """A serialized executable only survives a process boundary when
        it contains NO custom calls: XLA:CPU bakes custom-call targets
        in by host address (segfault under ASLR in the next process).
        Conservative on inspection failure: not portable."""
        try:
            return "custom-call" not in compiled.as_text()
        except Exception:                          # pragma: no cover
            return False

    def store(self, build: str, platform: str, fingerprint: Tuple,
              compiled) -> bool:
        """Record one AOT-compiled executable: always into the
        in-process tier; onto disk (atomic write) only when portable —
        custom-call-bearing programs (see ``portable``) lean on the XLA
        compilation cache for cross-process relief instead.  Returns
        whether a disk entry was written."""
        self._process_put(build, platform, fingerprint,
                          pin_executable(compiled))
        if not self.portable(compiled):
            self.skipped_unportable += 1
            return False
        try:
            from jax.experimental import serialize_executable as se
            blob = pickle.dumps(se.serialize(compiled))
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._path(build, platform, fingerprint))
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
        except Exception:                          # pragma: no cover
            self.errors += 1
            return False
        self.stores += 1
        return True

    def summary(self) -> dict:
        return {"cache_dir": self.cache_dir, "disk_loads": self.loads,
                "process_hits": self.process_hits,
                "disk_stores": self.stores, "disk_errors": self.errors,
                "skipped_unportable": self.skipped_unportable}


def default_persist() -> Optional[PersistentProgramCache]:
    """The environment-configured persistent cache, or None."""
    d = os.environ.get(ENV_CACHE_DIR)
    return PersistentProgramCache(d) if d else None
