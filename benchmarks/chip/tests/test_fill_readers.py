"""The pending-view readers on the small hand-built window: the fill's
time per estimate, and the entries a fill read, present only where the
service records ``scanned``."""
import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import progspans, registry
from chipbench import trace as tr
from conftest import BENCH_DIR

FIXTURE = json.loads(
    (Path(__file__).parent / "data" / "progspans_small.json").read_text())


def window():
    return SimpleNamespace(trace=tr.Trace.from_json(FIXTURE["trace"]),
                           completed=[None] * FIXTURE["completed"])


def read(metric, w):
    return registry.layer_reader(BENCH_DIR, metric).read(w)


@pytest.fixture
def program(monkeypatch):
    spans = [tuple(s) for s in copy.deepcopy(FIXTURE["program"])]
    monkeypatch.setattr(progspans, "recorded", lambda: spans)
    return spans


def test_fill_ms_per_estimate(program):
    # one planner.fill of 1000 ns over the window's completed estimates
    assert read("fill_ms_per_estimate.backlog", window()) == \
        pytest.approx(1000 / FIXTURE["completed"] / 1e6)


def test_fill_scanned_absent_without_the_arg(program):
    # the fixture's fill records only ``entries``: a service without the
    # live pending view reports nothing, and does not raise
    assert read("fill_scanned_per_wave.backlog", window()) is None


def test_fill_scanned_per_wave(program):
    i = next(k for k, s in enumerate(program) if s[0] == "planner.fill")
    name, s0, e0, depth, parent, rid, args = program[i]
    program[i] = (name, s0, e0, depth, parent, rid,
                  dict(args, live=3, scanned=7))
    # a second fill inside the second step, 4 entries read
    j = next(k for k, s in enumerate(program)
             if s[0] == "backend.step" and s[6]["wave"] == 1)
    program.append(("planner.fill", program[j][1] + 100,
                    program[j][1] + 400, 2, j, None,
                    {"entries": 2, "live": 2, "scanned": 4}))
    assert read("fill_scanned_per_wave.backlog", window()) == 5.5
    assert read("fill_ms_per_estimate.backlog", window()) == \
        pytest.approx(1300 / FIXTURE["completed"] / 1e6)


def test_untraced_window_reads_nothing(program):
    w = SimpleNamespace(trace=None, completed=[1])
    assert read("fill_ms_per_estimate.backlog", w) is None
    assert read("fill_scanned_per_wave.backlog", w) is None
