"""One run of one cell: set-up, the measured window, the check.

The service is driven only through ``DMLSession.submit``/``poll``/
``result``.  Every call into it sits in a host span of the benchmark's
own (``bench:<name>``, a ``jax.profiler.TraceAnnotation`` as well), and
the service's own counters (``CompileStats``, ``PageStats``,
``DispatchStats``) are read as deltas over the window.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, registry, traffic
from chipbench import trace as tr

SPAN = "bench:"
WINDOW_SPAN = SPAN + "window"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Spans:
    """Host-clock totals of the benchmark's spans, kept while ``on``."""

    def __init__(self):
        self.total: Dict[str, float] = defaultdict(float)
        self.on = False

    @contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(SPAN + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if self.on:
                    self.total[name] += time.perf_counter() - t0


class CompileCounter:
    """Counts XLA executable builds (compiles and compile-cache loads)."""

    def __init__(self):
        import jax
        self.count = 0

        def listen(event, secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.count += 1
        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


@dataclass
class Record:
    req: traffic.Request
    t_submit: float
    t_due: float
    t_done: Optional[float] = None
    answer: Optional[Dict] = None


@dataclass
class Window:
    """What a per-layer reader reads: the window's completions, counter
    deltas, span totals and, in a traced run, the reduced trace."""
    cell: registry.Cell
    seconds: float
    completed: List[Record]
    fits: int
    counters: Dict[str, float]
    spans: Dict[str, float]
    lags_s: List[float]
    device_kind: str
    unfinished: List[Record] = field(default_factory=list)
    trace: Optional[tr.Trace] = None

    def kernel_names(self, kind: str) -> List[str]:
        return registry.kernel_names(self.cell.bench_dir, kind)


def counters(sess) -> Dict[str, float]:
    """The service's cumulative counters, read at one moment."""
    b = sess.backend
    cs = b.compiler.stats
    pages = b.pages.stats
    return {"launches": cs.launches, "tasks": cs.padding.tasks,
            "true_cells": cs.padding.true_cells,
            "padded_cells": cs.padding.padded_cells,
            "bytes_h2d": pages.bytes_h2d}


class WaitTotal:
    """Sums ``DispatchStats.wait_s`` over drains: a drain retires when the
    session empties and the next starts a fresh stats block."""

    def __init__(self):
        self.total = 0.0
        self._block = None
        self._seen = 0.0

    def update(self, sess) -> None:
        info = sess.last_run_info
        d = None if info is None else info.dispatch
        if d is None:
            return
        if d is not self._block:
            self._block, self._seen = d, 0.0
        self.total += d.wait_s - self._seen
        self._seen = d.wait_s


class Run:
    """One process's run of one cell."""

    def __init__(self, cell: registry.Cell, seed: int, seconds: float):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.cfg, self.mix = cell.config, cell.traffic
        self.gen = registry.generator(cell)
        self.spans = Spans()
        self.waits = WaitTotal()
        self.records: Dict[int, Record] = {}
        self.next_index = 0
        self.shared = None

    # ---- requests -----------------------------------------------------
    def fits_per_request(self) -> int:
        c = self.cfg
        return int(c["n_rep"]) * int(c["n_folds"]) * int(c["n_nuisance"])

    def dataset(self, index: int) -> Dict:
        if self.mix["data"] == "shared" and self.shared is not None:
            return self.shared
        return self.gen.make(self.cfg,
                             traffic.data_stream(self.seed, self.mix, index))

    def plan(self, req: traffic.Request):
        from repro.core import DMLPlan
        c = self.cfg
        return DMLPlan.for_model(
            c["model"], learner=c["learner"],
            learner_params=dict(c["learner_params"]),
            n_folds=int(c["n_folds"]), n_rep=int(c["n_rep"]),
            seed=req.plan_seed, scaling=req.scaling)

    def submit(self, due: float) -> None:
        index = self.next_index
        self.next_index += 1
        with self.spans("client.request"):
            req = traffic.request(self.seed, index, self.cfg)
            data = self.dataset(index)
            plan = self.plan(req)
        with self.spans("session.submit"):
            rid = self.sess.submit(plan, data)
        self.records[rid] = Record(req, time.perf_counter(), due)

    def poll(self) -> List[Record]:
        with self.spans("session.poll"):
            done = self.sess.poll()
        now = time.perf_counter()
        self.waits.update(self.sess)
        out = []
        for rid in done:
            with self.spans("session.result"):
                res = self.sess.result(rid)
                answer = {"theta": float(res.theta), "se": float(res.se),
                          "thetas": np.asarray(res.thetas, np.float64),
                          "ses": np.asarray(res.ses, np.float64)}
                # the session keeps every result and compiled request
                # for the life of the process; a long window would fill
                # the host's memory with them
                self.sess._results.pop(rid, None)
                self.sess._requests.pop(rid, None)
            rec = self.records.pop(rid)
            rec.t_done, rec.answer = now, answer
            out.append(rec)
        return out

    # ---- set-up -------------------------------------------------------
    def setup(self) -> Dict[str, float]:
        from repro.core import DMLSession
        from repro.serverless import PoolConfig
        parts = {}
        t = time.perf_counter()
        self.sess = DMLSession(backend=self.cfg["backend"],
                               pool=PoolConfig(**self.cfg.get("pool", {})))
        parts["session_probe_s"] = time.perf_counter() - t
        t = time.perf_counter()
        if self.mix["data"] == "shared":
            self.shared = self.dataset(0)
        parts["data_gen_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.compiles = CompileCounter()
        if self.mix["loop"] == "closed":
            self._warm_closed()
        else:
            self._warm_open()
        parts["warmup_s"] = time.perf_counter() - t
        parts["warmup_builds"] = self.compiles.count
        return parts

    def _warm_closed(self) -> None:
        """Keep ``outstanding`` requests in the service until
        ``warmup_requests`` completed and the last ``outstanding`` of them
        built no program, or four times as many completed."""
        depth = int(self.mix["outstanding"])
        need = int(self.mix["warmup_requests"])
        for _ in range(depth):
            self.submit(0.0)
        done, quiet, builds = 0, 0, self.compiles.count
        while done < need or (quiet < depth and done < 4 * need):
            for _ in self.poll():
                done += 1
                quiet = quiet + 1 if self.compiles.count == builds else 0
                builds = self.compiles.count
                self.submit(0.0)

    def _warm_open(self) -> None:
        """Bursts of ``warmup_bursts`` requests submitted at once, each
        drained (the compositions a quiet moment of the open loop
        launches), then the mix's arrivals for ``warmup_s`` from a stream
        of their own, drained to idle: the window starts from an empty
        service."""
        for burst in self.mix.get("warmup_bursts", []):
            for _ in range(int(burst)):
                self.submit(time.perf_counter())
            while self.records:
                self.poll()
        self._open_phase(float(self.mix["warmup_s"]), "warmup", 120.0)

    def _open_phase(self, duration: float, phase: str,
                    grace: float) -> tuple:
        """Run one open-loop phase; returns (completed records, start)."""
        dues = traffic.arrivals(self.seed, self.mix, duration, phase)
        t0 = time.perf_counter()
        done: List[Record] = []
        i = 0
        while True:
            now = time.perf_counter() - t0
            while i < len(dues) and dues[i] <= now:
                self.submit(t0 + dues[i])
                i += 1
            if self.records:
                done += self.poll()
                if now > duration + grace:
                    break
            elif i < len(dues):
                with self.spans("client.idle"):
                    time.sleep(max(0.0, dues[i] - (time.perf_counter()
                                                   - t0)))
            else:
                break
        return done, t0

    # ---- the window ---------------------------------------------------
    def measure(self, trace_dir: Optional[str]) -> Window:
        import jax
        c0 = counters(self.sess)
        w0, builds0 = self.waits.total, self.compiles.count
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.spans.on = True
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            if self.mix["loop"] == "closed":
                completed = self._window_closed(t0)
            else:
                completed, t0 = self._open_phase(
                    self.seconds, "window", float(self.mix["grace_s"]))
            t1 = time.perf_counter()
        self.spans.on = False
        if trace_dir is not None:
            jax.profiler.stop_trace()
        c1 = counters(self.sess)
        delta = {k: c1[k] - c0[k] for k in c0}
        delta["dispatch_wait_s"] = self.waits.total - w0
        delta["builds"] = self.compiles.count - builds0
        self.compiles.close()
        lags = [r.t_submit - r.t_due for r in completed] \
            + [r.t_submit - r.t_due for r in self.records.values()] \
            if self.mix["loop"] == "open" else []
        return Window(cell=self.cell, seconds=t1 - t0, completed=completed,
                      fits=self.fits_per_request() * len(completed),
                      counters=delta, spans=dict(self.spans.total),
                      lags_s=lags, device_kind=self.device_kind,
                      unfinished=list(self.records.values()))

    def _window_closed(self, t0: float) -> List[Record]:
        end = t0 + self.seconds
        done: List[Record] = []
        while time.perf_counter() < end:
            for rec in self.poll():
                done.append(rec)
                self.submit(0.0)
        return done

    # ---- the check ----------------------------------------------------
    def sample(self, win: Window) -> List[tuple]:
        """(record, dataset, fold masks) of the completions the check
        compares: ``check_requests`` of them, drawn from the seed."""
        ref = registry.reference(self.cell)
        pool, c = win.completed, self.cfg
        k = min(int(c["check_requests"]), len(pool))
        pick = sorted(traffic.stream(self.seed, "check").choice(
            len(pool), size=k, replace=False)) if k else []
        return [(pool[j], self.dataset(pool[j].req.index),
                 ref.fold_masks(int(c["n_obs"]), int(c["n_folds"]),
                                int(c["n_rep"]), pool[j].req.plan_seed))
                for j in pick]

    def compare(self, win: Window, fn: str = "reference") -> tuple:
        """(pairs, failed): the sampled answers beside those of the plain
        reference (or of its one-precision-down ``control``), and the
        count of requests due in the window that never came."""
        ref = registry.reference(self.cell)
        reg = float(self.cfg["learner_params"]["reg"])
        pairs = []
        for rec, data, masks in self.sample(win):
            r = ref.reference(data["x"], data["y"], data["d"], masks, reg)
            a = rec.answer if fn == "reference" else getattr(ref, fn)(
                data["x"], data["y"], data["d"], masks, reg)
            pairs.append((a, r))
        failed = len(win.unfinished) \
            if self.mix["loop"] == "open" else 0
        return pairs, failed


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(name: str, run: Run, win: Window,
               setup_s: float) -> Optional[float]:
    """An end-to-end metric by name: ``setup_s``, ``fits_per_s`` or
    ``latency_p<q>_s`` (over every request due in the window; one that
    never completed counts as infinitely late)."""
    if name == "setup_s":
        return setup_s
    if name == "fits_per_s":
        return win.fits / win.seconds
    if name.startswith("latency_p") and name.endswith("_s"):
        q = float(name[len("latency_p"):-len("_s")])
        lat = [r.t_done - r.t_due for r in win.completed] \
            + [float("inf")] * len(win.unfinished)
        return percentile(lat, q) if lat else None
    raise KeyError(f"no end-to-end metric {name!r}")


def reduce_trace(trace_dir: str, n_chips: int) -> tr.Trace:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under "
                                f"{trace_dir}")
    return tr.from_xspace(str(paths[-1]), SPAN, WINDOW_SPAN,
                          n_devices=n_chips)


def run_cell(cell: registry.Cell, seed: int, seconds: float, traced: bool,
             device: Dict, t_start: float) -> Dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    run = Run(cell, seed, seconds)
    run.device_kind = device["kind"]
    parts = run.setup()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
        if traced else None
    try:
        setup_s = time.perf_counter() - t_start
        win = run.measure(trace_dir)
        devs = jax.devices()[:cell.chips]
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
        log(f"[setup] " + " ".join(f"{k}={v}" for k, v in parts.items())
            + f" setup_s={setup_s}")
        log(f"[window] seconds={win.seconds} completed={len(win.completed)}"
            f" fits={win.fits} builds_in_window={win.counters['builds']}"
            f" generator_lag_p95_ms="
            f"{percentile(win.lags_s, 95) * 1e3 if win.lags_s else 0.0}"
            f" client_request_s={win.spans.get('client.request', 0.0)}")
        if traced:
            win.trace = reduce_trace(trace_dir, cell.chips)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the service's state goes before the reference runs
    del run.sess
    pairs, failed = run.compare(win)
    correct, checks = check.judge(pairs, cell.config["limits"], failed)

    metrics: Dict[str, Dict] = {}
    dev = dict(device, memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": len(win.completed) + failed,
           "failed": failed, "metrics": metrics, "device": dev}
    if traced:
        for m in cell.per_layer:
            value = registry.layer_reader(cell.bench_dir, m["name"]).read(win)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = tr.mean_busy_s(win.trace)
        dev["window_s"] = win.trace.window_ns / 1e9
        out["breakdown"] = {"device_ops": tr.top_ops(win.trace),
                            "idle_gaps": tr.idle_by_span(win.trace)}
    else:
        for m in cell.end_to_end:
            value = end_to_end(m["name"], run, win, setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, (value, limit) in checks.items():
        log(f"[check] {name} = {value!r} (limit {limit!r})")
    out["checks"] = checks
    return out
