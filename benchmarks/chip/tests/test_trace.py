"""Trace reduction on small recorded traces."""
from pathlib import Path

import pytest

from chipbench import trace as tr

DATA = Path(__file__).parent / "data"


def small_trace():
    # window [0, 100) ns; chip 0 runs two ops, overlapping, and a gram
    # kernel; chip 1 runs one op; the host polls, then builds requests
    return tr.Trace(
        window=(0.0, 100.0),
        devices={"/device:TPU:0": [("fusion.1", 10.0, 20.0),
                                   ("fusion.2", 20.0, 20.0),
                                   ("batched_gram", 60.0, 10.0),
                                   ("late", 95.0, 20.0)],
                 "/device:TPU:1": [("fusion.1", 0.0, 50.0)]},
        spans=[("bench:session.poll", 0.0, 50.0),
               ("bench:client.request", 50.0, 30.0)])


def test_union_merges_and_clips():
    assert tr.union([(5, 10), (0, 3), (8, 20), (30, 40)], 2, 35) == \
        [(2, 3), (5, 20), (30, 35)]


def test_busy_and_idle():
    t = small_trace()
    busy = tr.busy_ns(t)
    assert busy == {"/device:TPU:0": 30.0 + 10.0 + 5.0,
                    "/device:TPU:1": 50.0}
    assert tr.idle_frac(t) == pytest.approx(((1 - 0.45) + (1 - 0.5)) / 2)
    assert tr.mean_busy_s(t) == pytest.approx(47.5e-9)


def test_kernel_time_by_registered_name():
    t = small_trace()
    assert tr.kernel_ns(t, ["batched_gram"]) == 10.0
    assert tr.kernel_ns(t, ["batched"]) == 0.0        # whole names only
    assert tr.kernel_ns(t, ["late"]) == 5.0           # clipped to window


def test_op_names_from_hlo():
    assert tr.op_name('%batched_gram.7 = (f32[32,128,128]) custom-call('
                      'f32[32,5120,128] %pad.70), custom_call_target='
                      '"tpu_custom_call"') == "batched_gram"
    assert tr.op_name('%custom-call.23 = f32[32,33,33] custom-call(f32[32,'
                      '33,33] %fusion.11), custom_call_target="Cholesky"') \
        == "custom-call:Cholesky"
    assert tr.op_name("%pad_select_fusion.2 = f32[32] fusion()") == \
        "pad_select_fusion"


def test_gaps_by_covering_span():
    t = small_trace()
    assert tr.gaps(t, "/device:TPU:0") == [(0.0, 10.0), (40.0, 60.0),
                                           (70.0, 95.0)]
    by = dict(tr.idle_by_span(t))
    # chip 0: poll covers 0-10 and 40-50; request covers 50-60, 70-80;
    # 80-95 has no span.  chip 1: poll nothing, request 50-80, 80-100
    # none.  Averaged over the two chips.
    assert by["bench:session.poll"] == pytest.approx(20e-9 / 2)
    assert by["bench:client.request"] == pytest.approx((20e-9 + 30e-9) / 2)
    assert by[tr.NO_SPAN] == pytest.approx((15e-9 + 20e-9) / 2)


def test_top_ops():
    top = tr.top_ops(small_trace(), k=2)
    assert [n for n, _ in top] == ["fusion.1", "fusion.2"]
    assert top[0][1] == pytest.approx((20e-9 + 50e-9) / 2)


def test_json_round_trip(tmp_path):
    t = small_trace()
    tr.save_json(t, tmp_path / "t.json")
    assert tr.load_json(tmp_path / "t.json").to_json() == t.to_json()


# ---------------------------------------------------------------------------
# a 60 ms cut of a bonus_backlog trace recorded on one TPU v5e chip
# ---------------------------------------------------------------------------
def recorded():
    return tr.load_json(DATA / "v5e_bonus_backlog_60ms.json")


def sweep_busy_ns(ops, lo, hi):
    """Busy time by a sweep over sorted endpoints: the reduction's
    union computed another way."""
    points = []
    for _, s, d in ops:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace_busy_matches_a_sweep():
    t = recorded()
    (dev, ops), = t.devices.items()
    assert len(ops) > 100 and t.window_ns == pytest.approx(60e6)
    busy = tr.busy_ns(t)[dev]
    assert busy == pytest.approx(sweep_busy_ns(ops, *t.window), rel=1e-12)
    assert 0.0 < busy < t.window_ns
    assert tr.idle_frac(t) == pytest.approx(1 - busy / t.window_ns)


def test_recorded_trace_gram_kernel_and_gaps():
    t = recorded()
    (dev, ops), = t.devices.items()
    gram = tr.kernel_ns(t, ["batched_gram"])
    assert 0.0 < gram <= tr.busy_ns(t)[dev]
    assert gram == pytest.approx(sum(
        min(s + d, t.window[1]) - max(s, t.window[0])
        for n, s, d in ops if n == "batched_gram"))
    # every idle nanosecond is attributed once, to a span or to none
    idle_s = (t.window_ns - tr.busy_ns(t)[dev]) / 1e9
    by = tr.idle_by_span(t, k=100)
    assert sum(v for _, v in by) == pytest.approx(idle_s, rel=1e-9)
    assert by[0][0] == "bench:session.poll"


def test_recorded_trace_top_ops_skip_loops():
    top = dict(tr.top_ops(recorded(), k=100))
    assert "while" not in top and "batched_gram" in top
