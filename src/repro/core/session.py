"""The serving layer: plans + data in, per-request results out.

``DMLSession`` is the multi-request front door, built around a
**continuous-admission drain engine**: ``submit()`` enqueues a request
immediately; the engine admits queued requests into the backend's live
``DrainState`` (extending the megabatch bucket plan incrementally),
dispatches waves without a global barrier, and completes each request's
``TaskLedger`` the moment its buckets land — early requests deliver their
``DMLResult`` (and fire ``on_complete`` callbacks) while later ones are
still executing.  ``poll()`` advances the engine by one wave; ``run()``
and ``estimate()`` are blocking wrappers over the same event loop, so the
batch-synchronous public API is unchanged.

Dispatch is **non-blocking** (ISSUE 5): a ``step()`` launches its
buckets and returns with the results still in flight on device; the
ledgers are booked by a later step's *harvest-on-poll* (each step first
books any landed buckets, blocking only when nothing is left to
dispatch).  Every host-side phase of the loop — admission, placement,
autoscaling, result assembly, callbacks — therefore overlaps device
execution; ``last_run_info.dispatch`` reports the measured overlap.

On the wave backend the requests' task grids fuse into shared dispatch
waves — many concurrent estimations amortize the same capacity cycles
(the batch-processing throughput lever); on the sharded/inline backends
they reuse the same warm compiled programs.  The backend's device-resident
page pool persists across drains, so steady-state serving re-transfers no
feature pages.

On the topology backend (``backend="topology"``) the same event loop
drives many *host-mesh streams*: each ``step()`` advances one host's
wave round-robin, buckets are placed on the host whose page pool already
holds their data, and ledgers complete out of order across hosts exactly
as they do across waves within one — the session code is unchanged
because multi-host is just more streams behind the same three backend
primitives.  Per-host accounting surfaces as
``last_run_info.topology``.

``estimate(plan, data)`` is the one-shot convenience for a single request.

Determinism: a request's result depends only on its own (plan, data) —
fold draws, learner seeds, and score evaluation are keyed off
``plan.resampling.seed``, and per-task PRNG streams are fixed at compile
time — so a session-batched request returns bitwise the predictions it
would get running alone, regardless of admission order or out-of-order
bucket completion.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Union

import jax
import msgpack
import numpy as np

from repro import obs
from repro.core.aggregation import aggregate_thetas, confint
from repro.core.bootstrap import boot_confint, multiplier_bootstrap
from repro.core.crossfit import (
    TaskGrid, check_partition, draw_fold_masks, stitch_predictions,
    subset_mask,
)
from repro.core.scores import evaluate_score, score_se, solve_theta
from repro.core.spec import DMLData, DMLPlan, _hashable
from repro.learners import resolve_params
from repro.serverless.backends import (
    BackendRunInfo, DrainState, ExecutionBackend, PoolConfig, RunReport,
    Segment, WorkRequest, make_backend,
)
from repro.serverless.ledger import TaskLedger
from repro.serverless.sanitize import check_drained


@dataclass
class DMLResult:
    theta: float
    se: float
    ci: tuple
    thetas: np.ndarray              # per-repetition estimates (M,)
    ses: np.ndarray
    report: RunReport
    boot_ci: Optional[tuple] = None
    request_id: Optional[int] = None

    def summary(self) -> Dict:
        out = {"theta": self.theta, "se": self.se, "ci": self.ci}
        out.update({f"exec_{k}": v for k, v in self.report.summary().items()})
        return out


# ---------------------------------------------------------------------------
# plan + data -> WorkRequest
# ---------------------------------------------------------------------------
def compile_request(plan: DMLPlan, data: DMLData,
                    ledger: Optional[TaskLedger] = None,
                    tag: object = None) -> WorkRequest:
    """Lower a declarative request to executable arrays.

    Builds the fold masks, per-nuisance targets and training weights, and
    groups nuisances that share a (learner, params) pair into one
    ``Segment`` so uniform grids run as a single fused batch while mixed
    grids (IRM/IIVM propensities) get one fused batch per learner.
    """
    data = DMLData.from_dict(data)
    rs = plan.resampling
    n = data.n_obs
    grid = TaskGrid(rs.n_rep, rs.n_folds, plan.n_nuisance)
    masks = draw_fold_masks(n, rs.n_folds, rs.n_rep, rs.seed)
    assert check_partition(masks)

    targets = np.stack([data.role(ns.target) for ns in plan.nuisances])
    train_w = np.empty((rs.n_rep, rs.n_folds, plan.n_nuisance, n), np.float32)
    for l, ns in enumerate(plan.nuisances):
        sub = subset_mask(ns.subset, data)
        w = (~masks).astype(np.float32)          # train on I^c_{m,k}
        if sub is not None:
            w = w * sub.astype(np.float32)[None, None, :]
        train_w[:, :, l, :] = w

    # one segment per distinct (learner, params): uniform grids fuse into a
    # single batch, mixed grids get one fused batch per learner.  Each
    # segment carries the spec the megabatch compiler buckets on —
    # hyperparameters resolved against the *data shape* here (e.g.
    # kernel_ridge's gamma), so padded bucket execution stays
    # padding-invariant — and the base PRNG key tasks fold_in from.
    groups: List[List[int]] = []
    seen: Dict = {}
    for l, ns in enumerate(plan.nuisances):
        gi = seen.get(ns.learner_key)
        if gi is None:
            seen[ns.learner_key] = len(groups)
            groups.append([l])
        else:
            groups[gi].append(l)
    segments = []
    for g in groups:
        ns = plan.nuisances[g[0]]
        params = resolve_params(ns.learner, ns.param_dict,
                                n_obs=n, dim_x=data.dim_x)
        ptuple = tuple(sorted((k, _hashable(v)) for k, v in params.items()))
        segments.append(Segment(l_ids=tuple(g),
                                key=jax.random.key(rs.seed + g[0]),
                                key_ref=("seed", rs.seed + g[0]),
                                cache_key=(ns.learner, ptuple),
                                learner=ns.learner, params=ptuple))

    # content identity of the request's task tensors: fold masks derive
    # from (seed, K, M), targets/train_w from (data CONTENT — all role
    # arrays, not just X — plus roles and subsets), per-task keys from
    # the segment seeds — so this tuple pins every stacked block tensor,
    # letting the compiler reuse them across drains (steady serving
    # re-lowers identical requests every round).  ``content_key`` (not
    # ``fingerprint``) is load-bearing: two datasets sharing one X but
    # different y/d/z must not share cached targets/weights.
    work_key = ("plan-v1", data.content_key(), rs.seed, rs.n_folds,
                rs.n_rep, plan.scaling,
                tuple((ns.target, ns.subset, ns.learner_key)
                      for ns in plan.nuisances))
    req = WorkRequest.create(grid, plan.scaling, data.x, targets, train_w,
                             segments, ledger=ledger, tag=tag,
                             data_key=data.fingerprint(), work_key=work_key)
    req.fold_masks = masks                      # needed for stitching
    return req


def compile_raw_request(grid: TaskGrid, scaling: str, x, targets, train_w,
                        learner_fn, key, *, ledger=None, report=None,
                        tag: object = None) -> WorkRequest:
    """Lower a raw-array request (an opaque user-supplied learner callable
    over explicit grid arrays) onto the same compiled execution path as
    plan-built requests: one opaque-callable segment, executed by the
    megabatch compiler at exact shapes via the vmap adapter."""
    seg = Segment(learner_fn=learner_fn,
                  l_ids=tuple(range(grid.n_nuisance)), key=key)
    return WorkRequest.create(grid, scaling, x, targets, train_w, [seg],
                              ledger=ledger, report=report, tag=tag)


def assemble_result(plan: DMLPlan, data: DMLData, req: WorkRequest,
                    request_id: Optional[int] = None) -> DMLResult:
    """Stitch fold predictions, evaluate the score, run local inference.

    Everything but the bootstrap runs in numpy float32 on the host, where
    the predictions already are: a few thousand flops a repetition cost
    less there than the dispatches and transfers of eager device ops.
    Without a bootstrap, assembly touches no device array (span arg
    ``on_host``).
    """
    n_boot = plan.inference.n_boot
    with obs.span("session.assemble", rid=request_id,
                  on_host=int(not n_boot)):
        data = DMLData.from_dict(data)
        # (M, L, N); DMLData and the ledger hold float32
        fitted = stitch_predictions(req.fold_masks, req.gathered_preds())
        pred_tree = {ns.name: fitted[:, l]
                     for l, ns in enumerate(plan.nuisances)}
        dml_data = {k: v[None] for k, v in data.score_arrays().items()}
        psi_a, psi_b = evaluate_score(plan.model, dml_data, pred_tree,
                                      plan.score)
        thetas = solve_theta(psi_a, psi_b)                  # (M,)
        ses = score_se(psi_a, psi_b, thetas)
        theta, se = aggregate_thetas(thetas, ses, plan.inference.aggregation)
        ci = confint(theta, se, plan.inference.level)

        boot_ci = None
        if n_boot:
            bt, se1 = multiplier_bootstrap(
                psi_a[0], psi_b[0], float(thetas[0]),
                jax.random.key(plan.resampling.seed + 99), n_boot=n_boot)
            boot_ci = boot_confint(float(thetas[0]), se1, bt)

        res = DMLResult(theta=theta, se=se, ci=ci, thetas=thetas, ses=ses,
                        report=req.report, boot_ci=boot_ci,
                        request_id=request_id)
        res.psi = (psi_a, psi_b)
    return res


# ---------------------------------------------------------------------------
# the session: continuous-admission drain engine
# ---------------------------------------------------------------------------
@dataclass
class _Pending:
    request_id: int
    plan: DMLPlan
    data: DMLData
    ledger: Optional[TaskLedger]
    on_complete: Optional[Callable] = None
    req: Optional[WorkRequest] = None       # set at admission
    admitted: bool = False


class DMLSession:
    """Serves many estimation requests from one warm execution backend
    through a continuous-admission drain engine.

    >>> sess = DMLSession(backend="wave", pool=PoolConfig(n_workers=8))
    >>> a = sess.submit(plan_a, data_a)
    >>> b = sess.submit(plan_b, data_b)
    >>> results = sess.run()            # shared waves; [DMLResult, DMLResult]
    >>> sess.result(a).theta

    ``submit()`` only enqueues; admission into the backend's live
    ``DrainState`` happens lazily, so requests submitted while earlier
    ones are draining join the *same* drain (no barrier between batches).
    ``poll()`` advances the drain by one wave and returns the ids of
    requests that completed in that wave — the non-blocking interface;
    ``wait(rid)`` / ``run()`` / ``estimate()`` are blocking wrappers.
    Completion order is recorded in ``completion_order`` and surfaced
    through per-request ``on_complete`` callbacks the moment a request's
    ledger fills, while other requests are still executing.

    The backend persists across ``run()`` calls (warm pools, cached SPMD
    programs, device-resident feature pages).  ``last_run_info`` exposes
    cross-request wave accounting — ``last_run_info.shared_waves > 0`` is
    the fusion at work; ``.pages`` is the page-pool telemetry;
    ``.autoscale`` the autoscaler's decisions; ``.topology`` the
    per-host stream accounting when the backend is a topology (also
    reachable as ``session.topology_info``).

    If the backend aborts mid-drain (e.g. retry budget exhausted), the
    incomplete requests stay queued with their partially-completed
    ledgers; a later ``run()`` resumes exactly the missing invocations —
    including after swapping ``self.backend`` for a healthier pool.

    **Crash resume** (ISSUE 10): pass ``session_dir`` and the session
    becomes durable — every ``submit()`` persists the request's full
    (plan, data) spec (msgpack, atomic), and every admitted request's
    ``TaskLedger`` is bound to a file the backends checkpoint after each
    booking wave.  If the process dies mid-drain,
    ``DMLSession.resume(session_dir)`` in a FRESH process re-submits the
    saved specs in request-id order with their loaded ledgers: DONE
    invocations are never re-executed, RUNNING rows re-dispatch, and the
    determinism contract makes the resumed thetas bitwise-identical to
    an uninterrupted run.
    """

    def __init__(self, backend: Union[str, ExecutionBackend] = "wave",
                 pool: Optional[PoolConfig] = None,
                 session_dir: Optional[str] = None):
        # calibrate roofline launch-overhead and shard-overhead pricing
        # on THIS runtime (memoized no-op dispatch probes) — the
        # analytic SHARD_OVERHEAD_FRAC mispriced 1-device meshes
        # (ISSUE 9).  A probe that fails raises: pricing must not run on
        # a device it could not reach.
        from repro.launch.roofline import (
            measure_launch_overhead_s, measure_shard_overhead_frac,
        )
        measure_launch_overhead_s()
        measure_shard_overhead_frac()
        self.backend = make_backend(backend, pool)
        self.session_dir = session_dir
        if session_dir is not None:
            os.makedirs(session_dir, exist_ok=True)
        self._queue: List[_Pending] = []
        self._results: Dict[int, DMLResult] = {}
        self._requests: Dict[int, WorkRequest] = {}
        self._next_id = 0
        self.completion_order: List[int] = []
        self.last_run_info: Optional[BackendRunInfo] = None
        self._state: Optional[DrainState] = None
        self._state_backend: Optional[ExecutionBackend] = None

    # ---- admission ----------------------------------------------------
    def submit(self, plan: DMLPlan, data, *,
               ledger: Optional[TaskLedger] = None,
               on_complete: Optional[Callable] = None) -> int:
        """Queue one estimation request; returns its request id.

        ``on_complete(result)`` fires the moment the request's ledger
        completes — possibly waves before the whole drain finishes.
        """
        with obs.span("session.submit", rid=self._next_id):
            data = DMLData.from_dict(data)
            rid = self._next_id
            self._next_id += 1
            self._queue.append(_Pending(rid, plan, data, ledger,
                                        on_complete=on_complete))
            if self.session_dir is not None:
                self._persist_spec(rid, plan, data)
        return rid

    # ---- durability ---------------------------------------------------
    def _spec_path(self, rid: int) -> str:
        return os.path.join(self.session_dir, f"request_{rid:05d}.msgpack")

    def _ledger_path(self, rid: int) -> str:
        return os.path.join(self.session_dir, f"ledger_{rid:05d}.msgpack")

    def _persist_spec(self, rid: int, plan: DMLPlan, data: DMLData):
        """Durably record one admitted request (atomic, like the ledger:
        a crash never leaves a half-written spec)."""
        payload = {"rid": rid, "plan": plan.to_payload(),
                   "data": data.to_payload()}
        path = self._spec_path(rid)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(msgpack.packb(payload, use_bin_type=True))
        os.replace(tmp, path)

    @classmethod
    def resume(cls, session_dir: str, *,
               backend: Union[str, ExecutionBackend] = "wave",
               pool: Optional[PoolConfig] = None) -> "DMLSession":
        """Rebuild a durable session in a fresh process: re-submit every
        persisted request spec in request-id order with its checkpointed
        ledger, so the next ``run()``/``poll()`` re-dispatches exactly
        the not-DONE invocations (RUNNING rows orphaned by the crash
        included — ``TaskLedger.load`` resets them) and completes every
        admitted request with bitwise-identical thetas."""
        sess = cls(backend=backend, pool=pool, session_dir=session_dir)
        for path in sorted(glob.glob(
                os.path.join(session_dir, "request_*.msgpack"))):
            with open(path, "rb") as f:
                p = msgpack.unpackb(f.read(), raw=False)
            ledger = None
            lpath = os.path.join(
                session_dir, f"ledger_{p['rid']:05d}.msgpack")
            if os.path.exists(lpath):
                ledger = TaskLedger.load(lpath)
                ledger.path = lpath         # keep checkpointing here
            rid = sess.submit(DMLPlan.from_payload(p["plan"]),
                              DMLData.from_payload(p["data"]),
                              ledger=ledger)
            assert rid == p["rid"], \
                f"resume id drift: re-submitted as {rid}, saved {p['rid']}"
        return sess

    def _drain_state(self) -> DrainState:
        """The live drain, rebuilt if the backend was swapped (previously
        admitted-but-incomplete requests re-enter with their ledgers, so
        the new drain resumes instead of restarting)."""
        if self._state is None or self._state_backend is not self.backend:
            self._state = self.backend.begin_drain()
            self._state_backend = self.backend
            for p in self._queue:
                p.admitted = False
        return self._state

    def _admit_queued(self):
        if not self._queue and self._state is None:
            return                          # idle: keep last drain's info
        with obs.span("session.admit", queued=len(self._queue)) as sp:
            state = self._drain_state()
            admitted = 0
            for p in self._queue:
                if p.admitted:
                    continue
                with obs.span("session.compile_request", rid=p.request_id):
                    req = compile_request(p.plan, p.data, ledger=p.ledger,
                                          tag=p.request_id)
                p.ledger = req.ledger       # keep completed rows on failure
                p.req = req
                if self.session_dir is not None and req.ledger.path is None:
                    # bind the durable checkpoint file: backends call
                    # ledger.checkpoint() after every booking wave
                    req.ledger.path = self._ledger_path(p.request_id)
                    req.ledger.checkpoint()
                self.backend.admit(state, req)
                p.admitted = True
                admitted += 1
            sp.set(admitted=admitted)
            self.last_run_info = state.info

    # ---- the event loop -----------------------------------------------
    def _harvest(self) -> List[int]:
        """Assemble results for every admitted request whose ledger just
        completed; fires callbacks; removes them from the queue."""
        finished: List[int] = []
        with obs.span("session.harvest") as sp:
            for p in list(self._queue):
                if not (p.admitted and p.req.ledger.complete):
                    continue
                res = assemble_result(p.plan, p.data, p.req,
                                      request_id=p.request_id)
                self._results[p.request_id] = res
                self._requests[p.request_id] = p.req
                self.completion_order.append(p.request_id)
                self._queue.remove(p)
                finished.append(p.request_id)
                if p.on_complete is not None:
                    p.on_complete(res)
            sp.set(completed=len(finished))
        return finished

    def _retire_idle_state(self):
        """Drop the drain state once nothing is queued: the next submit
        starts a fresh drain (warm caches live on the *backend* — program
        cache and page pool survive; only the admission bookkeeping and
        its telemetry, already exposed via ``last_run_info``, retire)."""
        if not self._queue and self._state is not None:
            check_drained(self._state, "session retire")
            self._state = None
            self._state_backend = None

    def poll(self) -> List[int]:
        """Admit anything queued, advance the drain by one step (book
        any landed in-flight buckets, then dispatch the next wave
        without blocking), and return the ids of requests that completed
        in that step."""
        with obs.span("session.poll"):
            if not self._queue and self._state is None:
                return []
            self._admit_queued()
            self.backend.step(self._drain_state())
            done = self._harvest()
            self._retire_idle_state()
        return done

    def wait(self, request_id: int) -> DMLResult:
        """Drive the drain until one request completes; requests admitted
        behind it keep executing in the shared waves meanwhile."""
        if request_id in self._results:
            return self._results[request_id]
        if all(p.request_id != request_id for p in self._queue):
            raise KeyError(f"unknown request id {request_id}")
        self._admit_queued()
        state = self._drain_state()
        self._harvest()                     # resumed-complete ledgers
        while request_id not in self._results:
            progressed = self.backend.step(state)
            self._harvest()
            if not progressed and request_id not in self._results:
                raise RuntimeError(
                    f"drain stalled with request {request_id} incomplete")
        self._retire_idle_state()
        return self._results[request_id]

    def run(self) -> List[DMLResult]:
        """Drain every currently-queued request; returns their results in
        submission order (also retrievable via ``result(id)``).  Requests
        submitted *during* the drain (e.g. from callbacks) are admitted
        into the same drain and may complete here too."""
        self._admit_queued()
        targets = [p.request_id for p in self._queue]
        if not targets:
            return []
        state = self._drain_state()
        self._harvest()                     # resumed-complete ledgers
        while any(rid not in self._results for rid in targets):
            progressed = self.backend.step(state)
            self._harvest()
            self._admit_queued()            # continuous admission
            if not progressed and \
                    any(rid not in self._results for rid in targets):
                raise RuntimeError("drain stalled with incomplete requests")
        self._retire_idle_state()
        return [self._results[rid] for rid in targets]

    # ---- results ------------------------------------------------------
    @property
    def topology_info(self):
        """Per-host stream accounting of the last drain (placements,
        steals, per-host waves) — None on single-stream backends."""
        info = self.last_run_info
        return None if info is None else info.topology

    def result(self, request_id: int) -> DMLResult:
        with obs.span("session.result", rid=request_id):
            return self._results[request_id]

    def request(self, request_id: int) -> WorkRequest:
        """The compiled WorkRequest of a completed request (its
        ``gathered_preds()`` is the full prediction tensor — used by the
        parity benchmarks)."""
        return self._requests[request_id]

    def estimate(self, plan: DMLPlan, data, *,
                 ledger: Optional[TaskLedger] = None) -> DMLResult:
        """Submit + drain a single request on this session's backend."""
        rid = self.submit(plan, data, ledger=ledger)
        return self.wait(rid)


def estimate(plan: DMLPlan, data, *,
             ledger: Optional[TaskLedger] = None,
             backend: Union[str, ExecutionBackend, None] = None) -> DMLResult:
    """One-shot estimation: plan + data -> result, backend from the plan."""
    b = backend if backend is not None else plan.backend
    sess = DMLSession(backend=b, pool=plan.pool)
    return sess.estimate(plan, data, ledger=ledger)
