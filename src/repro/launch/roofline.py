"""Roofline accounting from compiled dry-run artifacts (spec: ROOFLINE
ANALYSIS).

Hardware model: one row of ``DEVICE_PEAKS`` per ``device_kind``, with its
source; a device that has no row is an error.  The dry-run target is a
TPU v5e chip (``TARGET_KIND``):

  compute_term_s    = HLO_FLOPs_per_device / peak flops
  memory_term_s     = HLO_bytes_per_device / HBM bandwidth
  collective_term_s = collective_bytes_per_device / interconnect bandwidth

``cost_analysis()`` counts a while-loop (lax.scan) body ONCE (verified
empirically), so per-cell costs are measured on small probe configs with
every *inner* loop unrolled (runtime.unroll_inner) and the *layer* scans
extrapolated linearly: cost(probe) = c0 + sum_i trips_i(probe) * c_i,
solved from len(dims)+1 probes, then evaluated at the full config.
Collective bytes come from the HLO text with ring-model wire factors.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig



@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks the pricing divides by."""
    flops: float           # dense bf16 matmul FLOP/s
    hbm_bw: float          # HBM bytes/s
    hbm_bytes: float       # HBM capacity
    ici_bw: float          # chip-to-chip interconnect bytes/s
    source: str


DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=1600e9 / 8,
        source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip"),
    # XLA's CPU backend has no published peak.  The row keeps the values
    # the planner has always priced with, so its decisions on a CPU host
    # are the pinned ones; no CPU number is a device measurement.
    "cpu": DevicePeaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9,
        source="no published peak: the planner's historical constants"),
}

#: the chip the dry-run roofline prices a compiled program for
TARGET_KIND = "TPU v5 lite"


def device_peaks(kind: Optional[str] = None) -> DevicePeaks:
    """The peak row of ``kind`` (default: this process's first device).
    A kind with no row raises: pricing never guesses a device."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peak row for device kind {kind!r}; known kinds: "
            f"{sorted(DEVICE_PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective op (ring model).

    all-reduce = 2(n-1)/n x bytes; all-gather / reduce-scatter / all-to-all
    = (n-1)/n x full bytes; collective-permute = bytes.
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        typ, op = m.group(1), m.group(2)
        if op + "-done" in line:
            continue
        size = _shape_bytes(typ)
        g = _GROUPS_RE.search(line)
        n = int(g.group(2)) if g else 2
        if n <= 1:
            continue
        ring = (n - 1) / n
        factor = {"all-reduce": 2 * ring, "all-gather": ring,
                  "reduce-scatter": ring, "all-to-all": ring,
                  "collective-permute": 1.0}[op]
        out[op] = out.get(op, 0.0) + size * factor
    return out


# ---------------------------------------------------------------------------
# Probe configs: layer-scan trip counts per arch family
# ---------------------------------------------------------------------------
@dataclass
class ProbePlan:
    """probes[i] = (cfg_variant, trips vector a_i); full_trips for the real
    config.  cost_full = c0 + full_trips . c  with [c0, c] solved from probes.
    """
    probes: List[Tuple[ArchConfig, Tuple[float, ...]]]
    full_trips: Tuple[float, ...]


def probe_plan(cfg: ArchConfig) -> ProbePlan:
    fam = cfg.family
    if fam in ("dense", "moe"):
        head = cfg.moe.first_dense_layers if fam == "moe" else 0
        full = cfg.n_layers - head
        return ProbePlan(
            probes=[(replace(cfg, n_layers=head + 1), (1.0,)),
                    (replace(cfg, n_layers=head + 2), (2.0,))],
            full_trips=(float(full),))
    if fam == "ssm":                      # xlstm: groups of slstm_every
        per = cfg.ssm.slstm_every or cfg.n_layers
        return ProbePlan(
            probes=[(replace(cfg, n_layers=per), (1.0,)),
                    (replace(cfg, n_layers=2 * per), (2.0,))],
            full_trips=(float(cfg.n_layers // per),))
    if fam == "hybrid":                   # groups of 6 + mamba tail
        per = cfg.shared_attn_every
        return ProbePlan(
            probes=[(replace(cfg, n_layers=per), (1.0, 0.0)),
                    (replace(cfg, n_layers=2 * per), (2.0, 0.0)),
                    (replace(cfg, n_layers=per + 1), (1.0, 1.0))],
            full_trips=(float(cfg.n_layers // per),
                        float(cfg.n_layers % per)))
    if fam == "audio":                    # encoder / decoder stacks
        return ProbePlan(
            probes=[(replace(cfg, n_encoder_layers=1, n_layers=1), (1.0, 1.0)),
                    (replace(cfg, n_encoder_layers=2, n_layers=1), (2.0, 1.0)),
                    (replace(cfg, n_encoder_layers=1, n_layers=2), (1.0, 2.0))],
            full_trips=(float(cfg.n_encoder_layers), float(cfg.n_layers)))
    if fam == "vlm":                      # groups of cross_attn_every
        per = cfg.cross_attn_every
        return ProbePlan(
            probes=[(replace(cfg, n_layers=per), (1.0,)),
                    (replace(cfg, n_layers=2 * per), (2.0,))],
            full_trips=(float(cfg.n_layers // per),))
    raise KeyError(fam)


def solve_extrapolation(plan: ProbePlan,
                        probe_costs: List[Dict[str, float]]) -> Dict[str, float]:
    """Least-squares solve of cost = c0 + trips . c per metric key."""
    keys = set()
    for c in probe_costs:
        keys.update(c)
    a = np.array([[1.0, *trips] for _, trips in plan.probes])
    out = {}
    for k in keys:
        b = np.array([c.get(k, 0.0) for c in probe_costs])
        coef, *_ = np.linalg.lstsq(a, b, rcond=None)
        full = coef[0] + float(np.dot(coef[1:], np.array(plan.full_trips)))
        out[k] = max(full, 0.0)
    return out


# ---------------------------------------------------------------------------
# Analytic corrections for loops that cannot be unrolled (sLSTM time scan)
# ---------------------------------------------------------------------------
def analytic_extra_flops(cfg: ArchConfig, shape: ShapeConfig,
                         n_devices: int) -> float:
    """Per-device FLOPs invisible to cost_analysis (rolled time scans)."""
    if cfg.family != "ssm" or not (cfg.ssm and cfg.ssm.slstm_every):
        return 0.0
    n_slstm = cfg.n_layers // cfg.ssm.slstm_every
    d = cfg.d_model
    nh = cfg.attention.n_heads
    hd = d // nh
    steps = 1 if shape.kind == "decode" else shape.seq_len
    per_step = 2 * nh * hd * 4 * hd + 40 * d      # R matmul + gate flops
    total = n_slstm * steps * shape.global_batch * per_step
    if shape.kind == "train":
        total *= 3.0                              # fwd + bwd
    return total / n_devices


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Useful-model FLOPs for the whole step (all devices)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    return 2.0 * n_active * shape.global_batch    # one token per sequence


# ---------------------------------------------------------------------------
# Megabatch bucket pricing (ISSUE 4: roofline-priced autoscaling)
# ---------------------------------------------------------------------------
def megabatch_task_flops(learner: str, n: int, p: int,
                         params: Dict = None) -> float:
    """Analytic FLOPs of ONE task lane of a megabatch bucket launch at
    the bucket's padded (n, p) — the same counting convention as
    ``model_flops`` (multiply-add = 2 FLOPs), per learner family.

    Padded rows/columns do real arithmetic (that is the padding-waste
    signal's whole point), so the estimate is taken at the *padded*
    shape.  These feed the occupancy autoscaler's candidate pricing
    before any duration has been observed — the "first-wave decision
    cost-accurate too" ROADMAP item — so fidelity to ~2x is plenty;
    ranking candidates only needs relative scale.
    """
    params = dict(params or ())
    gram = 2.0 * n * p * p               # X^T W X
    solve = (2.0 / 3.0) * p ** 3         # cholesky-ish SPD solve
    predict = 2.0 * n * p
    if learner in ("ridge", "ols"):
        return gram + solve + predict
    if learner == "lasso":               # FISTA: one gram, iterated grads
        n_iter = int(params.get("n_iter", 200))
        return gram + n_iter * (4.0 * p * p + 8.0 * p) + predict
    if learner == "logistic":            # IRLS: gram + solve per newton step
        n_iter = int(params.get("n_iter", 32))
        return n_iter * (gram + solve + 4.0 * n * p) + predict
    if learner == "kernel_ridge":        # m landmarks: K_nm, K_mm, solve
        m = int(params.get("n_landmarks", 128))
        return (2.0 * n * m * p + 2.0 * m * m * p
                + (2.0 / 3.0) * m ** 3 + 2.0 * n * m)
    if learner == "mlp":                 # fwd+bwd per step over the widths
        hidden = tuple(params.get("hidden", (64, 64)))
        n_steps = int(params.get("n_steps", 300))
        dims = (p,) + hidden + (1,)
        per_row = sum(2.0 * a * b for a, b in zip(dims, dims[1:]))
        return n_steps * 6.0 * n * per_row + 2.0 * n * per_row
    return gram + solve + predict        # unknown family: linear-ish guess


def megabatch_task_bytes(n: int, p: int) -> float:
    """HBM bytes one task lane moves per launch: its feature page plus
    the y/w/valid rows in, the prediction row out (f32)."""
    return 4.0 * (n * p + 4.0 * n)


# Host-side cost of dispatching ONE compiled program (jit call + runtime
# enqueue), measured ~0.3 ms on the serving hosts.  It dwarfs the
# compute/memory terms for small buckets — which is exactly why the
# dispatcher packs same-shape blocks into one fused launch: the overhead
# is paid once per launch, not once per block.  This constant is the
# FALLBACK; ``measure_launch_overhead_s`` replaces it with a per-session
# measurement on the actual runtime (session init calls it once).
LAUNCH_OVERHEAD_S = 3e-4

# session-measured override; None until measure_launch_overhead_s runs
_MEASURED_LAUNCH_OVERHEAD_S: Optional[float] = None


def launch_overhead_s() -> float:
    """Host dispatch cost of one compiled-program launch: the session
    measurement when one has been taken, else the hardcoded fallback."""
    if _MEASURED_LAUNCH_OVERHEAD_S is not None:
        return _MEASURED_LAUNCH_OVERHEAD_S
    return LAUNCH_OVERHEAD_S


def measure_launch_overhead_s(repeats: int = 30) -> float:
    """Measure the per-launch dispatch overhead with a timed no-op
    program: compile a trivial jit once, then time warm re-dispatches
    and take the median.  Memoized module-globally — sessions call this
    at init so autoscaler pricing uses the runtime actually underneath
    us instead of the serving-host constant.  Clamped to a sane band
    (10 us .. 10 ms).  A probe that fails raises.
    """
    global _MEASURED_LAUNCH_OVERHEAD_S
    if _MEASURED_LAUNCH_OVERHEAD_S is not None:
        return _MEASURED_LAUNCH_OVERHEAD_S
    import time

    import jax
    import jax.numpy as jnp

    noop = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    noop(x).block_until_ready()                # compile outside the timer
    samples = []
    for _ in range(max(int(repeats), 3)):
        t0 = time.perf_counter()
        noop(x).block_until_ready()
        samples.append(time.perf_counter() - t0)
    samples.sort()
    measured = samples[len(samples) // 2]
    _MEASURED_LAUNCH_OVERHEAD_S = min(max(measured, 1e-5), 1e-2)
    return _MEASURED_LAUNCH_OVERHEAD_S


def invocation_roofline_s(learner: str, params, tasks_per_invocation: int,
                          n_pad: int, p_pad: int, *,
                          amortized_launches: float = 0.0) -> float:
    """Roofline lower bound on one invocation's duration: max of the
    compute and memory terms over its task lanes, on the same hardware
    model as the rest of this module.

    ``amortized_launches`` is this invocation's share of its bucket's
    fused program launches (e.g. 1/len(bucket) when the whole bucket
    rides one fused launch): the autoscaler passes it so cold pricing
    reflects the dispatch overhead the fused hot path actually pays.
    The default 0 keeps the pure compute/memory bound."""
    t = max(int(tasks_per_invocation), 1)
    flops = t * megabatch_task_flops(learner, n_pad, p_pad, params)
    byts = t * megabatch_task_bytes(n_pad, p_pad)
    peaks = device_peaks()
    return max(flops / peaks.flops, byts / peaks.hbm_bw) \
        + amortized_launches * launch_overhead_s()


# Hedge-deadline shape (ISSUE 10): a bucket is declared overdue — and a
# duplicate dispatch raced against it — once its in-flight age exceeds
# FACTOR x the roofline estimate of the whole slice, floored so that
# sub-millisecond serving buckets are not hedged on scheduler jitter.
# 4x mirrors the speculative-duplicate threshold used by gg-style
# serverless launchers (stragglers there run 5-10x the median).
HEDGE_DEADLINE_FACTOR = 4.0
HEDGE_DEADLINE_FLOOR_S = 0.05


def bucket_deadline_s(learner: str, params, tasks_per_invocation: int,
                      n_pad: int, p_pad: int, n_entries: int,
                      n_workers: int = 1) -> float:
    """Roofline-derived hedge deadline for one dispatched bucket slice:
    FACTOR x the estimated wall of its ``n_entries`` invocations over
    ``n_workers`` lanes (plus one launch overhead), floored.  Backends
    cap this by ``PoolConfig.timeout_s`` — whichever is tighter drives
    the hedged re-dispatch."""
    per_inv = invocation_roofline_s(learner, params, tasks_per_invocation,
                                    n_pad, p_pad)
    lanes = max(int(n_workers), 1)
    waves = -(-max(int(n_entries), 1) // lanes)      # ceil division
    est = waves * per_inv + launch_overhead_s()
    return max(HEDGE_DEADLINE_FACTOR * est, HEDGE_DEADLINE_FLOOR_S)


# ---------------------------------------------------------------------------
# Parallelization-axis pricing (ISSUE 8: the per-bucket axis planner)
# ---------------------------------------------------------------------------
# Hardware-model ceiling on the rows of one device-resident feature
# page: a bucket whose N_pad exceeds this cannot run the one-page
# task-parallel layout and must stream N-chunks through the blocked
# Gram kernel (kernels/ops.py::batched_gram_blocked).
DEVICE_PAGE_ROWS = 1 << 16

# Dispatch-side tax of an m-way shard_map launch relative to the
# single-device program: extra argument sharding/unsharding and the
# runtime's per-shard bookkeeping, expressed as a fraction of one launch
# overhead per extra shard.  Keeps the planner honest on tiny serving
# buckets, where sharding 8 ways costs more host time than it saves.
# This constant is the FALLBACK; ``measure_shard_overhead_frac``
# replaces it with a per-session probe on the actual runtime (session
# init calls it once, like ``measure_launch_overhead_s``) — BENCH_axisplan
# showed the analytic 0.15 mispricing 1-device meshes, where the
# shard_map wrapper alone ran data-parallel at 0.47x task.
SHARD_OVERHEAD_FRAC = 0.15

# session-measured override; None until measure_shard_overhead_frac runs
_MEASURED_SHARD_OVERHEAD_FRAC: Optional[float] = None


def shard_overhead_frac() -> float:
    """Per-extra-shard dispatch tax (fraction of one launch overhead):
    the session measurement when one has been taken, else the
    hardcoded fallback."""
    if _MEASURED_SHARD_OVERHEAD_FRAC is not None:
        return _MEASURED_SHARD_OVERHEAD_FRAC
    return SHARD_OVERHEAD_FRAC


def measure_shard_overhead_frac(repeats: int = 20) -> float:
    """Measure the shard_map dispatch tax with a timed no-op pair:
    compile a trivial jit and the same body shard_map'd over the host
    mesh's "data" axis, time warm re-dispatches of both (medians), and
    express the extra cost as a fraction of one plain launch per extra
    shard — the exact ``launch_cost`` model ``axis_candidate_costs``
    charges.  A 1-device mesh still measures the wrapper's own tax
    (attributed to one "extra shard" so data@1 rescue pricing stays
    honest).  Memoized module-globally; clamped to [0.02, 2.0].  A probe
    that fails raises."""
    global _MEASURED_SHARD_OVERHEAD_FRAC
    if _MEASURED_SHARD_OVERHEAD_FRAC is not None:
        return _MEASURED_SHARD_OVERHEAD_FRAC
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    m = int(mesh.shape["data"])
    body = lambda x: x + 1.0
    plain = jax.jit(body)
    sharded = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False))
    x = jnp.zeros((8 * m,), jnp.float32)

    def median_s(fn):
        fn(x).block_until_ready()          # compile outside the timer
        samples = []
        for _ in range(max(int(repeats), 3)):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            samples.append(time.perf_counter() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    t_plain = max(median_s(plain), 1e-7)
    t_sharded = median_s(sharded)
    extra = max(t_sharded - t_plain, 0.0)
    frac = extra / (t_plain * max(m - 1, 1))
    _MEASURED_SHARD_OVERHEAD_FRAC = min(max(frac, 0.02), 2.0)
    return _MEASURED_SHARD_OVERHEAD_FRAC

#: families whose fit is a pure function of (X'X, X'y) — the data-
#: parallel blocked-Gram axis reconstructs their exact statistics from
#: per-shard partial sums, and the feature axis can split their
#: coordinate updates.  Everything else prices only the task axis.
GRAM_FAMILIES = ("ols", "ridge", "lasso")


def chunked_gram_flops(n: int, p: int, chunk_rows: int) -> float:
    """FLOPs of accumulating X'X / X'y over ceil(n/chunk) N-chunks (the
    streaming blocked Gram kernel): the same 2np^2 + 2np MACs as the
    unblocked Gram, plus one (p, p) accumulator add per extra chunk —
    the term that prices chunk granularity."""
    n_chunks = max(int(np.ceil(n / max(int(chunk_rows), 1))), 1)
    return 2.0 * n * p * p + 2.0 * n * p + (n_chunks - 1) * float(p) * p


def _solve_flops(learner: str, n: int, p: int, params: Dict) -> float:
    """The non-Gram remainder of a Gram-family fit: the part data-
    parallel sharding cannot split (solve / iterated coordinate
    updates run on the reduced statistics, replicated per shard)."""
    gram = 2.0 * n * p * p
    total = megabatch_task_flops(learner, n, p, params)
    return max(total - gram, 0.0)


def axis_candidate_costs(learner: str, params, n_tasks: int, n_pad: int,
                         p_pad: int, n_devices: int,
                         ) -> List[Tuple[str, int, float, bool]]:
    """Price every parallelization-axis candidate for one bucket.

    Returns ``[(axis, shards, est_s, executable), ...]`` — the roofline
    wall-clock of draining ``n_tasks`` tasks of padded shape
    (n_pad, p_pad) on an ``n_devices`` mesh under each layout:

    * ``task``     — whole tasks round-robin over shards (the fused
                     sharded launch; shards=1 is today's single-device
                     baseline).  No collectives; an m-way launch pays a
                     shard_map dispatch tax.
    * ``data``     — every shard accumulates a partial Gram over N/m
                     rows through the blocked kernel, psums the (P, P)
                     statistics, then solves on the reduced moments.
                     Splits the N axis: the only layout that can run a
                     bucket whose N_pad exceeds DEVICE_PAGE_ROWS.
    * ``feature``  — each shard owns P/m columns (LightGBM's feature-
                     parallel analogue): compute splits by column,
                     iterative families all-gather their coefficient
                     block per sweep, and the final predictions gather
                     the column partials.

    ``executable`` marks candidates the current launch layer can
    actually run (task always; data/feature only for GRAM_FAMILIES,
    through the standalone in-mesh executors in sharding/gram.py).
    Pure pricing over the peak row of this process's device kind
    (``device_peaks``), so planner decisions are deterministic and
    unit-testable.
    """
    params = dict(params or ())
    b = max(int(n_tasks), 1)
    m = max(int(n_devices), 1)
    lo = launch_overhead_s()
    f1 = megabatch_task_flops(learner, n_pad, p_pad, params)
    by1 = megabatch_task_bytes(n_pad, p_pad)
    gram_ok = learner in GRAM_FAMILIES
    fits_page = n_pad <= DEVICE_PAGE_ROWS

    frac = shard_overhead_frac()
    peaks = device_peaks()

    def launch_cost(shards: int) -> float:
        return lo * (1.0 + frac * (shards - 1))

    out: List[Tuple[str, int, float, bool]] = []
    # ---- task axis: ceil(b/m) whole tasks per shard, no collectives
    for shards in sorted({1, m}):
        per_dev = float(int(np.ceil(b / shards)))
        est = max(per_dev * f1 / peaks.flops,
                  per_dev * by1 / peaks.hbm_bw) + launch_cost(shards)
        out.append(("task", shards, est, fits_page))
    if m == 1:
        # chunk-streamed data@1: the page-overflow rescue path — the
        # blocked Gram streams N-chunks through one device, so a tall
        # bucket still drains on a 1-device mesh (ISSUE 9).  Priced
        # with the 1-way shard_map wrapper's own dispatch tax (the
        # measured 0.47x-of-task overhead) and marked executable only
        # when the task layout is NOT (a fitting page always prefers
        # the untaxed task program).
        if gram_ok:
            gram_dev = b * chunked_gram_flops(n_pad, p_pad,
                                              DEVICE_PAGE_ROWS)
            tail = b * _solve_flops(learner, n_pad, p_pad, params)
            est = max((gram_dev + tail) / peaks.flops,
                      by1 * b / peaks.hbm_bw) + lo * (1.0 + frac)
            out.append(("data", 1, est, not fits_page))
        return out

    # ---- data axis: blocked-Gram partials over N/m rows + psum(P^2)
    if gram_ok or learner == "logistic":
        chunk = max(int(np.ceil(n_pad / m)), 1)
        gram_dev = b * chunked_gram_flops(n_pad, p_pad, chunk) / m
        tail = b * _solve_flops(learner, n_pad, p_pad, params)
        psum_rounds = 1.0 if learner != "logistic" \
            else float(params.get("n_iter", 32))
        psum_bytes = b * (p_pad * p_pad + p_pad) * 4.0 * psum_rounds
        coll = psum_bytes * 2.0 * (m - 1) / m / peaks.ici_bw
        est = max((gram_dev + tail) / peaks.flops,
                  by1 * b / m / peaks.hbm_bw) + coll + launch_cost(m)
        out.append(("data", m, est, gram_ok))
    else:
        # no analytic data-parallel decomposition for this family
        out.append(("data", m, float("inf"), False))

    # ---- feature axis: P/m columns per shard + coefficient gathers
    if gram_ok:
        sweeps = float(params.get("n_iter", 200)) \
            if learner == "lasso" else 1.0
        gather_bytes = b * (n_pad * p_pad / m + sweeps * p_pad) * 4.0
        coll = gather_bytes * (m - 1) / m / peaks.ici_bw
        est = max(f1 * b / m / peaks.flops, by1 * b / m / peaks.hbm_bw) \
            + coll + launch_cost(m)
        out.append(("feature", m, est, fits_page))
    else:
        out.append(("feature", m, float("inf"), False))
    return out


@dataclass
class RooflineTerms:
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    n_devices: int
    model_flops_total: float
    coll_detail: Dict[str, float] = field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops_per_dev / DEVICE_PEAKS[TARGET_KIND].flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_dev / DEVICE_PEAKS[TARGET_KIND].hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_dev / DEVICE_PEAKS[TARGET_KIND].ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """No-overlap upper bound on step time."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        hlo_total = self.flops_per_dev * self.n_devices
        return self.model_flops_total / hlo_total if hlo_total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline bound."""
        denom = self.step_s * self.n_devices \
            * DEVICE_PEAKS[TARGET_KIND].flops
        return self.model_flops_total / denom if denom else 0.0

    def to_dict(self) -> Dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops_total": self.model_flops_total,
            "useful_ratio": self.useful_ratio,
            "mfu_bound": self.mfu_bound,
            "coll_detail": self.coll_detail,
        }
