"""A whole run with the device check skipped, on the CPU, and with the
timed path broken underneath: ``correct`` has to come out false."""
import json

import numpy as np
import pytest

import run
from repro.compile import program

CELL = ["--workload", "mc_backlog", "--seconds", "1.5", "--trace", "0"]


@pytest.fixture
def bench(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})

    def go(seed):
        assert run.main(CELL + ["--seed", str(seed)]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def broken_harvest(monkeypatch, alter):
    """Wrap ``BucketDispatch.harvest`` so every prediction buffer it
    books passes through ``alter(entry, preds)`` first."""
    harvest = program.BucketDispatch.harvest

    def wrapped(self):
        res = harvest(self)
        for i, key in enumerate(sorted(res)):
            res[key] = alter(i, res[key])
        return res
    monkeypatch.setattr(program.BucketDispatch, "harvest", wrapped)


def test_sound_run_is_correct(bench):
    out = bench(3000000011)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["checks"]["failed_requests"] == [0, 0]


def test_answer_altered_where_produced(bench, monkeypatch):
    # one fold's predictions of every invocation scaled by 1e-3
    def alter(i, preds):
        preds = preds.copy()
        preds[0] *= 1.001
        return preds
    broken_harvest(monkeypatch, alter)
    assert bench(3000000012)["correct"] is False


def test_half_the_batch_left_out(bench, monkeypatch):
    # every other invocation of a launch never computed: zeros booked
    broken_harvest(monkeypatch,
                   lambda i, preds: np.zeros_like(preds) if i % 2 else preds)
    assert bench(3000000013)["correct"] is False
