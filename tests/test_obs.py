"""Program spans (repro.obs): recorded only when asked for, nested by
parent, bounded, written into the profiler's trace, and placed at every
layer boundary of a session drain."""
import time
from pathlib import Path

import jax
import pytest

from repro import obs
from repro.core import DMLData, DMLPlan, DMLSession
from repro.data import make_plr_data
from repro.serverless import PoolConfig

# the spans a session poll may open, by layer
TREE = {
    "session.poll": {"session.admit", "backend.step", "session.harvest"},
    "session.admit": {"session.compile_request", "planner.admit"},
    "backend.step": {"planner.fill", "program.dispatch",
                     "dispatch.harvest", "topology.route",
                     "topology.steal", "topology.wave"},
    "topology.wave": {"program.dispatch"},
    "program.dispatch": {"program.stage", "program.build",
                         "program.launch"},
    "program.stage": {"pages.fetch", "keys.table"},
    "dispatch.harvest": {"program.harvest", "ledger.book"},
    "program.harvest": {"program.wait"},
    "session.harvest": {"session.assemble"},
}


@pytest.fixture(autouse=True)
def empty_buffer():
    obs.clear()
    yield
    obs.clear()


def self_ns(spans, i):
    """Duration of span i less the union of its children's intervals
    (children of one span do not overlap: one thread)."""
    s = spans[i]
    kids = sum(c.end_ns - c.start_ns for c in spans if c.parent == i)
    return s.end_ns - s.start_ns - kids


def test_inactive_spans_record_nothing():
    with obs.span("a", rid=1):
        with obs.span("b") as sp:
            sp.set(n=3)
    assert obs.spans() == [] and obs.dropped() == 0


def test_recording_nests_parents_depths_and_self_time():
    with obs.recording():
        with obs.span("a", rid=5, k=1):
            time.sleep(0.002)
            with obs.span("b"):
                time.sleep(0.003)
            with obs.span("c") as sp:
                sp.set(bytes=8)
                time.sleep(0.001)
    with obs.span("after"):
        pass
    a, b, c = obs.spans()
    assert [s.name for s in (a, b, c)] == ["a", "b", "c"]
    assert (a.depth, a.parent, b.depth, b.parent, c.depth, c.parent) == \
        (0, -1, 1, 0, 1, 0)
    assert a.rid == b.rid == c.rid == 5            # children inherit
    assert a.args == {"k": 1} and c.args == {"bytes": 8}
    assert a.start_ns <= b.start_ns < b.end_ns <= c.start_ns \
        < c.end_ns <= a.end_ns
    spans = obs.spans()
    assert self_ns(spans, 0) == (a.end_ns - a.start_ns) \
        - (b.end_ns - b.start_ns) - (c.end_ns - c.start_ns)
    assert 1.5e6 < self_ns(spans, 0) < (a.end_ns - a.start_ns) - 3.5e6
    assert self_ns(spans, 1) == b.end_ns - b.start_ns >= 3e6


def test_overflow_counts_dropped(monkeypatch):
    monkeypatch.setattr(obs, "CAPACITY", 3)
    with obs.recording():
        with obs.span("root"):
            for _ in range(4):
                with obs.span("leaf"):
                    pass
    assert [s.name for s in obs.spans()] == ["root", "leaf", "leaf"]
    assert obs.dropped() == 2
    obs.clear()
    assert obs.spans() == [] and obs.dropped() == 0


def test_spans_follow_the_profiler_into_its_trace(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer", rid=3):
            with obs.span("inner") as sp:
                jax.numpy.ones(4).block_until_ready()
                sp.set(staged_bytes=7)
    finally:
        jax.profiler.stop_trace()
    with obs.span("untraced"):
        pass
    outer, inner = obs.spans()
    assert (outer.name, inner.name, inner.parent) == ("outer", "inner", 0)

    path, = Path(tmp_path).rglob("*.xplane.pb")
    events = {e.name: e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(obs.PREFIX)}
    assert set(events) == {"repro:outer", "repro:inner"}
    eo, ei = events["repro:outer"], events["repro:inner"]
    assert eo.start_ns <= ei.start_ns \
        and ei.start_ns + ei.duration_ns <= eo.start_ns + eo.duration_ns
    assert dict(ei.stats)["staged_bytes"] == 7
    assert dict(eo.stats)["rid"] == 3
    # one clock offset maps every program span inside its trace event
    off = eo.start_ns - outer.start_ns
    assert abs((ei.start_ns - inner.start_ns) - off) < 1e6


def _drain(backend: str):
    """Two requests through a small pool, every poll recorded."""
    plan = lambda s: DMLPlan.for_model(
        "plr", learner="ridge", learner_params={"reg": 1.0}, n_folds=3,
        n_rep=2, seed=s)
    sess = DMLSession(backend=backend,
                      pool=PoolConfig(n_workers=2, memory_mb=256))
    rids = [sess.submit(plan(7 + i), DMLData.from_dict(make_plr_data(
        n_obs=150 - 40 * i, dim_x=5, theta=0.5, seed=i))) for i in range(2)]
    done = []
    with obs.recording():
        while len(done) < 2:
            done += sess.poll()
    assert sorted(done) == rids
    return rids, obs.spans()


def test_session_drain_spans_every_layer_boundary():
    rids, spans = _drain("wave")
    assert obs.dropped() == 0
    names = {s.name for s in spans}
    assert names >= {"session.poll", "session.admit", "backend.step",
                     "planner.fill", "program.dispatch", "program.stage",
                     "program.launch", "dispatch.harvest",
                     "program.harvest", "program.wait", "ledger.book",
                     "session.harvest", "session.assemble",
                     "session.compile_request", "planner.admit"}
    for i, s in enumerate(spans):
        if s.parent == -1:
            assert s.name == "session.poll" and s.depth == 0
            assert any(c.parent == i for c in spans)      # never a leaf
            continue
        p = spans[s.parent]
        assert s.name in TREE[p.name], (p.name, s.name)
        assert s.depth == p.depth + 1
        assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns

    key_width = jax.random.key_data(jax.random.key(0)).shape[-1]
    launches = [i for i, s in enumerate(spans) if s.name == "program.launch"]
    assert launches
    for i in launches:
        s = spans[i]
        assert s.args["rids"] and set(s.args["rids"]) <= set(rids)
        # the stage span just before the launch staged its operands:
        # y, w, valid (float32, b_pad x n_pad), key data (uint32,
        # b_pad x key width) and the page index (int32, b_pad), per block
        stage = max((j for j, t in enumerate(spans[:i])
                     if t.name == "program.stage"
                     and t.parent == s.parent))
        n_pad = spans[s.parent].args["n_pad"]
        b_pad, g = s.args["b_pad"], s.args["g"]
        assert spans[stage].args["staged_bytes"] == \
            g * b_pad * (3 * 4 * n_pad + 4 * key_width + 4)
    assembled = [s.rid for s in spans if s.name == "session.assemble"]
    assert sorted(assembled) == rids
    harvested = [s for s in spans if s.name == "program.harvest"]
    assert harvested and all(s.args["d2h_bytes"] > 0 for s in harvested)


@pytest.mark.parametrize("backend", ["inline", "topology"])
def test_every_backend_steps_inside_spans(backend):
    rids, spans = _drain(backend)
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    for name in ("backend.step", "planner.fill", "program.launch",
                 "ledger.book", "session.assemble"):
        assert by.get(name), name
    # the topology backend dispatches inside one host's wave
    dispatcher = "topology.wave" if backend == "topology" \
        else "backend.step"
    assert all(spans[s.parent].name == dispatcher
               for s in by["program.dispatch"])
    assert all(spans[s.parent].name == "backend.step"
               for s in by.get("topology.wave", []))
    assert sorted(s.rid for s in by["session.assemble"]) == rids
