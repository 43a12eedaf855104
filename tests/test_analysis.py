"""repro.analysis auditor: clean on HEAD, and each pass demonstrably
catches its seeded mutation (red) that the pristine tree passes (green).

The static passes are pure-AST, so mutations are applied textually to a
copy of the source tree in tmp_path — nothing broken is ever imported.
"""
import shutil

import jax
import numpy as np
import pytest

from repro.analysis import astutil, cache_keys, deadcode, protocol


def _mutated_tree(tmp_path, rel, old, new):
    root = astutil.default_root()
    tmp = tmp_path / "repro"
    shutil.copytree(root, tmp)
    src = (tmp / rel).read_text()
    assert old in src, f"mutation anchor missing from {rel}"
    (tmp / rel).write_text(src.replace(old, new))
    return tmp


# ---------------------------------------------------------------------------
# green: HEAD is clean
# ---------------------------------------------------------------------------
def test_static_passes_clean_on_head():
    assert cache_keys.run() == []
    assert protocol.run() == []
    assert deadcode.run() == []


def test_registry_covers_expected_caches():
    from repro.analysis import REGISTRY
    import repro.compile.buckets     # noqa: F401  (decorators register
    import repro.compile.pages       # noqa: F401   on import)
    import repro.compile.program     # noqa: F401
    import repro.serverless.backends  # noqa: F401
    import repro.sharding.gram       # noqa: F401
    assert set(cache_keys.EXPECTED_CACHES) <= set(REGISTRY)
    spec = REGISTRY["block_tensors"]
    assert "req.work_key" in spec.key
    assert "req.wave_arrays" in spec.covers["req.work_key"]


# ---------------------------------------------------------------------------
# red: cache-key pass vs seeded staleness mutations
# ---------------------------------------------------------------------------
def test_content_key_role_drop_fails_cache_pass(tmp_path):
    """Dropping role arrays from DMLData.content_key re-creates the PR 5
    staleness bug — the pass must turn it into a lint failure."""
    tmp = _mutated_tree(tmp_path, "core/spec.py",
                        "for r in _ROLES if", "for r in _ROLES[:2] if")
    rules = {f.rule for f in cache_keys.run(tmp)}
    assert "content-key-covers-roles" in rules


def test_key_component_drop_fails_cache_pass(tmp_path):
    """Removing work_key from the block-tensor contract leaves its reads
    unjustified and the key unable to pin the cached tensors."""
    tmp = _mutated_tree(
        tmp_path, "compile/program.py",
        'key=("req.work_key", "seg_idx", "blk.members", "blk.b_pad",',
        'key=("seg_idx", "blk.members", "blk.b_pad",')
    found = [f for f in cache_keys.run(tmp) if "program" in f.where]
    rules = {f.rule for f in found}
    assert rules & {"cover-not-a-key", "uncovered-read",
                    "unkeyed-parameter"}


def test_undeclared_bounded_put_fails_cache_pass(tmp_path):
    """A new bounded cache insert without a @warm_cache contract."""
    tmp = _mutated_tree(
        tmp_path, "serverless/backends.py",
        "@warm_cache(name=\"fold_in_key_tables\",\n"
        "            key=(\"base_key\", \"n_tasks\", \"key_ref\"))\n", "")
    rules = {f.rule for f in cache_keys.run(tmp)}
    assert "unregistered-bounded-put" in rules
    assert "missing-cache" in rules


# ---------------------------------------------------------------------------
# red: protocol pass vs seeded scheduler mutations
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rel,old,new", [
    # the bucket-stream policy's one-shot sync + grouping
    ("serverless/backends.py",
     "groups = state.plan.pending_by_bucket(\n"
     "            exclude=state.in_flight_entries(), gated=state.retry_at)",
     "groups = state.plan.pending_by_bucket(gated=state.retry_at)"),
    # the wave policy's sync before its capacity-bounded fill
    ("serverless/backends.py",
     "view.sync(exclude=state.in_flight_entries(), gated=state.retry_at)",
     "view.sync(gated=state.retry_at)"),
    # the topology policy's grouping before routing
    ("serverless/topology.py",
     "groups = state.plan.pending_by_bucket(\n"
     "            exclude=state.in_flight_entries(), gated=state.retry_at)",
     "groups = state.plan.pending_by_bucket(gated=state.retry_at)"),
])
def test_unexcluded_pending_view_fails_protocol_pass(tmp_path, rel, old,
                                                     new):
    tmp = _mutated_tree(tmp_path, rel, old, new)
    rules = {f.rule for f in protocol.run(tmp)}
    assert "pending-view-excludes-in-flight" in rules


def test_rogue_booking_site_fails_protocol_pass(tmp_path):
    tmp = _mutated_tree(
        tmp_path, "serverless/backends.py",
        "    def _checkpoint(self, state: DrainState, booked):",
        "    def _checkpoint(self, state: DrainState, booked):\n"
        "        state.requests[0].ledger.record_failure(0)")
    rules = {f.rule for f in protocol.run(tmp)}
    assert "booking-performer" in rules


def test_rogue_cancel_site_fails_protocol_pass(tmp_path):
    """A ``.cancel()`` outside HedgePair.settle could cancel BOTH legs
    of a race (bucket never booked) or cancel after booking (double
    accounting) — the single-cancel-performer rule must catch it."""
    tmp = _mutated_tree(
        tmp_path, "serverless/backends.py",
        "    def _checkpoint(self, state: DrainState, booked):",
        "    def _checkpoint(self, state: DrainState, booked):\n"
        "        state.queues[-1].cancel(state.queues[-1]._pending[0])")
    rules = {f.rule for f in protocol.run(tmp)}
    assert "cancel-performer" in rules


def test_rogue_abandon_site_fails_protocol_pass(tmp_path):
    """An ``.abandon()`` outside TopologyBackend.kill_host silently
    drops in-flight work without the ledger/pending-view bookkeeping
    that re-dispatches it."""
    tmp = _mutated_tree(
        tmp_path, "serverless/backends.py",
        "    def _checkpoint(self, state: DrainState, booked):",
        "    def _checkpoint(self, state: DrainState, booked):\n"
        "        state.queues[-1].abandon()")
    rules = {f.rule for f in protocol.run(tmp)}
    assert "abandon-performer" in rules


def test_identity_equality_regression_fails_protocol_pass(tmp_path):
    tmp = _mutated_tree(
        tmp_path, "serverless/dispatch.py",
        "@dataclass(eq=False)\nclass PendingBucket:",
        "@dataclass\nclass PendingBucket:")
    rules = {f.rule for f in protocol.run(tmp)}
    assert "identity-equality" in rules


# ---------------------------------------------------------------------------
# red: jaxpr audit vs a vmap-built fused program
# ---------------------------------------------------------------------------
def test_vmap_fused_program_fails_jaxpr_audit():
    from repro.analysis import jaxpr_audit as ja
    run, _ = ja._program_pair("ols")

    def run_vmapped(pages, data_idx, y, w, valid, key_data):
        return jax.vmap(lambda *t: run(pages, *t))(
            data_idx, y, w, valid, key_data)

    single = jax.make_jaxpr(run)(*ja._probe_avals(fused=False))
    bad = jax.make_jaxpr(run_vmapped)(*ja._probe_avals(fused=True))
    rules = {f.rule for f in ja.audit_fused_pair(single, bad, "ols/mut")}
    assert "fused-lowers-through-scan" in rules
    # and the real lax.map build passes the same check
    _, run_fused = ja._program_pair("ols")
    good = jax.make_jaxpr(run_fused)(*ja._probe_avals(fused=True))
    assert ja.audit_fused_pair(single, good, "ols/fused") == []


def test_vmap_sharded_fused_fails_jaxpr_audit():
    """The ISSUE 8 sharded-fused contract: shard_map(lax.map body) is
    bitwise because each device runs the per-block program unchanged —
    a vmap-built body inside the shard must still be rejected."""
    from repro.analysis import jaxpr_audit as ja
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.policy import megabatch_specs

    run, run_fused = ja._program_pair("ols")
    single = jax.make_jaxpr(run)(*ja._probe_avals(fused=False))
    in_specs, out_specs = megabatch_specs("data", fused=True)
    mesh = make_host_mesh()

    def run_vmapped(pages, data_idx, y, w, valid, key_data):
        return jax.vmap(lambda *t: run(pages, *t))(
            data_idx, y, w, valid, key_data)

    bad_fn = jax.shard_map(run_vmapped, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    bad = jax.make_jaxpr(bad_fn)(*ja._probe_avals(fused=True))
    rules = {f.rule for f in ja.audit_sharded_fused(single, bad,
                                                    "ols/mut")}
    assert "sharded-fused-wraps-scan" in rules
    # and the real shard_map(lax.map) build passes the same check
    good_fn = jax.shard_map(run_fused, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    good = jax.make_jaxpr(good_fn)(*ja._probe_avals(fused=True))
    assert ja.audit_sharded_fused(single, good, "ols/sf") == []
    # a bare (unsharded) fused program must also be rejected: the
    # sharded-fused cache's contract is shard_map at the top
    bare = jax.make_jaxpr(run_fused)(*ja._probe_avals(fused=True))
    assert {f.rule for f in ja.audit_sharded_fused(single, bare,
                                                   "ols/bare")} \
        == {"sharded-fused-wraps-scan"}


def test_data_derived_prng_fails_taint_analysis():
    from repro.analysis import jaxpr_audit as ja
    run, _ = ja._program_pair("ols")

    def run_leaky(pages, data_idx, y, w, valid, key_data):
        # derive PRNG state from a runtime data value: schedule-variant
        leaked = jax.random.fold_in(
            jax.random.key(0), data_idx[0].astype(np.uint32))
        _ = jax.random.uniform(leaked)
        return run(pages, data_idx, y, w, valid, key_data)

    bad = jax.make_jaxpr(run_leaky)(*ja._probe_avals(fused=False))
    findings = []
    ja._taint_jaxpr(bad.jaxpr, ja._data_key_marks(bad.jaxpr),
                    "ols/leak", findings)
    assert any(f.rule == "prng-key-from-runtime-data" for f in findings)


def test_mutated_axis_programs_fail_jaxpr_audit():
    """The ISSUE 9 in-mesh drain-form pins: a data-axis body whose psum
    was dropped (each shard would solve on its local rows only) and a
    feature-axis body whose row all-gather was dropped (cross-column
    Gram blocks from the wrong operand) must be rejected; the real
    lowered forms pass."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.analysis import jaxpr_audit as ja
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.learners.linear import _augment_b
    from repro.sharding.gram import (
        _data_fit_body, _feature_fit_body, gram_solve,
    )

    mesh = make_host_mesh()
    avals = ja._probe_avals(fused=False)
    params = (("intercept", True), ("reg", 1.0))
    data_specs = dict(
        in_specs=(P(None, "data", None), P(None), P(None, "data"),
                  P(None, "data"), P(None, "data"), P(None, None)),
        out_specs=P(None, "data"))
    feat_specs = dict(
        in_specs=(P(None, None, "data"), P(None), P(None, None),
                  P(None, None), P(None, None), P(None, None)),
        out_specs=P(None, None))

    # the real lowered forms pass their pins
    good_d = jax.make_jaxpr(jax.shard_map(
        _data_fit_body("data", "ridge", params), mesh=mesh,
        **data_specs, check_vma=False))(*avals)
    assert ja.audit_data_axis(good_d, "ridge/data") == []
    good_f = jax.make_jaxpr(jax.shard_map(
        _feature_fit_body("data", "ridge", params), mesh=mesh,
        **feat_specs, check_vma=False))(*avals)
    assert ja.audit_feature_axis(good_f, "ridge/feature") == []

    # mutation: shard-local statistics, no psum reassembly
    def local_fit(pages, data_idx, y, w, valid, key_data):
        xa = _augment_b(pages[data_idx].astype(jnp.float32))
        g, b = ops.batched_gram(xa, w, y, 1.0)
        return ops.batched_predict(xa, gram_solve(g, b), valid)

    bad_d = jax.make_jaxpr(jax.shard_map(
        local_fit, mesh=mesh, **data_specs, check_vma=False))(*avals)
    assert {f.rule for f in ja.audit_data_axis(bad_d, "ridge/mut")} \
        == {"data-axis-psums-moments"}

    # mutation: column-local Gram, no row all-gather
    bad_f = jax.make_jaxpr(jax.shard_map(
        local_fit, mesh=mesh, **feat_specs, check_vma=False))(*avals)
    assert {f.rule for f in ja.audit_feature_axis(bad_f, "ridge/mut")} \
        == {"feature-axis-gathers-rows"}

    # mutation: the shard_map wrapper itself dropped
    bare = jax.make_jaxpr(local_fit)(*avals)
    assert {f.rule for f in ja.audit_data_axis(bare, "ridge/bare")} \
        == {"data-axis-wraps-shard-map"}
    assert {f.rule for f in ja.audit_feature_axis(bare, "ridge/bare")} \
        == {"feature-axis-wraps-shard-map"}


# ---------------------------------------------------------------------------
# runtime sanitizer (REPRO_SANITIZE=1)
# ---------------------------------------------------------------------------
def _dispatched_bucket():
    from repro.compile import plan_buckets
    from repro.compile.program import ProgramCache, dispatch_bucket
    from repro.core import DMLData, DMLPlan
    from repro.core.session import compile_request
    from repro.data import make_plr_data

    data = DMLData.from_dict(
        make_plr_data(n_obs=40, dim_x=3, theta=0.5, seed=0))
    plan = DMLPlan.for_model("plr", learner="ridge",
                             learner_params={"reg": 1.0},
                             n_folds=2, n_rep=1, seed=7)
    req = compile_request(plan, data)
    mp = plan_buckets([req])
    key, entries = next(iter(mp.pending_by_bucket().items()))
    return req, dispatch_bucket(mp, ProgramCache(), key, entries)


def test_sanitizer_trips_on_double_harvest(monkeypatch):
    from repro.serverless.sanitize import ProtocolError
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    _, bd = _dispatched_bucket()
    bd.harvest()
    with pytest.raises(ProtocolError, match="harvested twice"):
        bd.harvest()


def test_sanitizer_off_allows_double_harvest(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    _, bd = _dispatched_bucket()
    first = bd.harvest()
    again = bd.harvest()
    assert set(first) == set(again)


def test_sanitizer_trips_on_booking_done_rows(monkeypatch):
    from repro.serverless.sanitize import ProtocolError, check_booking
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    req, bd = _dispatched_bucket()
    results = bd.harvest()
    invs = sorted({inv for _, inv in bd.entries})
    req.ledger.record_successes(
        invs, np.stack([results[(0, inv)] for inv in invs]))
    with pytest.raises(ProtocolError, match="record_successes"):
        check_booking(req.ledger, invs, "record_successes")


def test_sanitizer_trips_on_lost_bucket(monkeypatch):
    from repro.serverless.dispatch import DispatchQueue, PendingBucket
    from repro.serverless.sanitize import ProtocolError, check_drained
    monkeypatch.setenv("REPRO_SANITIZE", "1")

    class _State:
        queues = {-1: DispatchQueue(), 0: DispatchQueue()}
        waves_inflight = []

    _, bd = _dispatched_bucket()
    for q in _State.queues.values():          # each stream is checked
        q._pending.append(PendingBucket(dispatch=bd))
        with pytest.raises(ProtocolError, match="in\\s?flight"):
            check_drained(_State, "test retire")
        q._pending.clear()
    check_drained(_State, "test retire")     # empty queues pass


def test_sanitizer_trips_on_double_hedge(monkeypatch):
    """Hedging an already-HEDGED bucket would launch a third leg the
    settle logic doesn't know about."""
    from repro.serverless.dispatch import PendingBucket
    from repro.serverless.sanitize import ProtocolError, check_hedge
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    _, bd = _dispatched_bucket()
    pb = PendingBucket(dispatch=bd)
    check_hedge(pb)                          # DISPATCHED: legal
    pb.state = "HEDGED"
    with pytest.raises(ProtocolError, match="hedge .* HEDGED"):
        check_hedge(pb)


def test_sanitizer_trips_on_booking_cancelled_bucket(monkeypatch):
    """Booking a CANCELLED bucket means a losing hedge leg's results
    are entering the ledger alongside the winner's — double-booking.
    Cancelling it again means two settle sites fired."""
    from repro.serverless.dispatch import PendingBucket
    from repro.serverless.sanitize import (
        ProtocolError, check_bucket_bookable, check_cancel,
    )
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    _, bd = _dispatched_bucket()
    pb = PendingBucket(dispatch=bd)
    check_bucket_bookable(pb)                # DISPATCHED: legal
    check_cancel(pb)
    pb.state = "CANCELLED"
    with pytest.raises(ProtocolError, match="harvest .* CANCELLED"):
        check_bucket_bookable(pb)
    with pytest.raises(ProtocolError, match="cancel .* CANCELLED"):
        check_cancel(pb)


def test_transition_table_matches_ledger():
    """The table the sanitizer and static checker share names real
    TaskLedger methods and the module's state constants."""
    from repro.serverless import ledger as L
    for name in protocol.LEDGER_TRANSITIONS:
        assert callable(getattr(L.TaskLedger, name))
    for sname, code in protocol.INVOCATION_STATES.items():
        assert getattr(L, sname) == code
    # the bucket lifecycle table only names declared states, and every
    # non-initial state is reachable
    reached = set()
    for action, (srcs, dst) in protocol.BUCKET_TRANSITIONS.items():
        assert set(srcs) <= set(protocol.BUCKET_STATES), action
        assert dst in protocol.BUCKET_STATES, action
        reached.add(dst)
    assert reached == set(protocol.BUCKET_STATES) - {"PLANNED"}
