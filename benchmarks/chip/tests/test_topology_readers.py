"""The topology and page-fetch readers on a hand-built window of the
topology backend's spans, with known values."""
import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import progspans, registry
from chipbench import trace as tr
from conftest import BENCH_DIR

DATA = Path(__file__).parent / "data"
FIXTURE = json.loads((DATA / "topology_spans_small.json").read_text())


def window():
    return SimpleNamespace(trace=tr.Trace.from_json(FIXTURE["trace"]),
                           completed=[None] * FIXTURE["completed"])


@pytest.fixture
def program(monkeypatch):
    """Serve a copy of the fixture's program spans as the recorded ones;
    the test may edit it."""
    spans = [tuple(s) for s in copy.deepcopy(FIXTURE["program"])]
    monkeypatch.setattr(progspans, "recorded", lambda: spans)
    return spans


def read(metric, w):
    return registry.layer_reader(BENCH_DIR, metric).read(w)


def test_fixture_aligns(program):
    prog = progspans.load(window())
    assert prog.offset_ns == FIXTURE["offset_ns"]
    assert prog.residual_ns == FIXTURE["residual_ns"]


@pytest.mark.parametrize("metric, value", [
    # host 0's waves took 6 of the window's 8 dispatched invocations
    ("busiest_host_share.backlog", 6 / 8),
    # route 1000 + 500 ns, steal 1000 ns, over 2 estimates
    ("route_ms_per_estimate.backlog", (1000 + 500 + 1000) / 2 / 1e6),
    # two d2d fetches of 64,512 bytes (N_pad 504 x P_pad 32 x 4); the
    # h2d one does not count
    ("d2d_kb_per_estimate.backlog", 2 * 64512 / 1024 / 2),
])
def test_readers_give_hand_computed_values(program, metric, value):
    assert read(metric, window()) == pytest.approx(value, rel=1e-12)


def test_no_d2d_fetch_reads_zero(program):
    for i, s in enumerate(program):
        if s[0] == "pages.fetch":
            program[i] = s[:6] + (dict(s[6], source="h2d"),)
    assert read("d2d_kb_per_estimate.backlog", window()) == 0.0


@pytest.mark.parametrize("metric", ["busiest_host_share.backlog",
                                    "route_ms_per_estimate.backlog",
                                    "d2d_kb_per_estimate.backlog"])
def test_a_service_without_the_spans_reads_none(program, metric):
    """A program that records spans but none of the router's or page
    fetches (the wave backend, or a service before them) gives no
    value, and raises nothing."""
    program[:] = [s for s in program
                  if not s[0].startswith(("topology.", "pages."))]
    assert progspans.load(window()) is not None
    assert read(metric, window()) is None
