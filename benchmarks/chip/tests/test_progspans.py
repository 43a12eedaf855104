"""The service's spans aligned to the trace's clock, and the per-layer
readers over them, on a hand-built window with known values."""
import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import progspans, registry
from chipbench import trace as tr
from conftest import BENCH_DIR

DATA = Path(__file__).parent / "data"
FIXTURE = json.loads((DATA / "progspans_small.json").read_text())
US = 1e-3                                   # one microsecond in ms


def window(fixture=FIXTURE):
    return SimpleNamespace(trace=tr.Trace.from_json(fixture["trace"]),
                           completed=[None] * fixture["completed"])


@pytest.fixture
def program(monkeypatch):
    """Serve a copy of the fixture's program spans as the recorded ones;
    the test may edit it."""
    spans = [tuple(s) for s in copy.deepcopy(FIXTURE["program"])]
    monkeypatch.setattr(progspans, "recorded", lambda: spans)
    return spans


def read(metric, w):
    return registry.layer_reader(BENCH_DIR, metric).read(w)


def test_offset_and_residual_recovered(program):
    prog = progspans.load(window())
    assert prog.offset_ns == FIXTURE["offset_ns"]
    assert prog.residual_ns == FIXTURE["residual_ns"]
    # every program root span lands inside its bench: span
    (s0, e0), = prog.intervals(["session.submit"])
    assert (s0, e0) == (42200.0, 43800.0)


def test_mismatched_roots_return_none(program):
    del program[16]                         # the one session.result
    assert progspans.load(window()) is None
    assert read("admit_ms_per_estimate.backlog", window()) is None


def test_dropped_spans_return_none(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert progspans.recorded() is None
    assert progspans.load(window()) is None


def test_disjoint_clocks_return_none(program):
    name, s, e, *rest = program[15]         # session.submit, 100 us late
    program[15] = (name, s + 100_000, e + 100_000, *rest)
    assert progspans.load(window()) is None


def test_untraced_window_and_no_completions_return_none(program):
    assert progspans.load(SimpleNamespace(trace=None, completed=[1])) \
        is None
    w = window()
    w.completed = []
    assert read("book_ms_per_estimate.backlog", w) is None


def test_self_time_by_name(program):
    self_ns = progspans.load(window()).self_ns()
    # step 1: 27000 less fill 1000, dispatch 20000, harvest 4000;
    # step 2: 28500 less dispatch 18000
    assert self_ns["backend.step"] == 2000 + 10500
    # poll 1: 39600 less admit 2000, step 27000, harvest 9000;
    # poll 2: 39600 less admit 1000, step 28500, harvest 8000
    assert self_ns["session.poll"] == 1600 + 2100
    assert self_ns["program.harvest"] == 1000
    assert self_ns["program.stage"] == 20000


@pytest.mark.parametrize("metric, value", [
    ("admit_ms_per_estimate.backlog", (2000 + 1000) / 2 * US / 1000),
    ("admit_ms_per_estimate.open", (2000 + 1000) / 2 * US / 1000),
    ("step_self_ms_per_estimate.backlog", 12500 / 2 * US / 1000),
    ("step_self_ms_per_estimate.open", 12500 / 2 * US / 1000),
    ("stage_ms_per_launch.backlog", (12000 + 14000) / 2 * US / 1000),
    ("stage_ms_per_launch.open", (12000 + 14000) / 2 * US / 1000),
    ("staged_mb_per_estimate.backlog", (4e6 + 6e6) / 2 / 1e6),
    ("harvest_ms_per_estimate.backlog", (2000 - 1000) / 2 * US / 1000),
    ("book_ms_per_estimate.backlog", 1500 / 2 * US / 1000),
    ("assemble_ms_per_estimate.backlog", (8000 + 7000) / 2 * US / 1000),
    ("assemble_ms_per_estimate.open", (8000 + 7000) / 2 * US / 1000),
    # chip 0 idle in 5000-17200, 26000-66200, 75000-100000; the host in
    # stage/launch 5100-15100, 15200-17200, 52100-62100, 62200-66200:
    # 26000 of 100000 ns; chip 1 never idle
    ("idle_under_stage_frac.backlog", (0.26 + 0.0) / 2),
])
def test_readers_give_hand_computed_values(program, metric, value):
    assert read(metric, window()) == pytest.approx(value, rel=1e-12)


def test_readers_return_none_on_a_service_without_spans(monkeypatch):
    monkeypatch.setattr(progspans, "recorded", lambda: None)
    for path in sorted((BENCH_DIR / "layers").glob("*.py")):
        mod = registry.load_module(path)
        if "progspans" in path.read_text():
            assert mod.read(window()) is None, path.name
