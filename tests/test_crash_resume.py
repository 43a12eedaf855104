"""Crash-resumable sessions (ISSUE 10): kill a durable drain mid-flight
and resume it in a FRESH process.

A real subprocess boundary is the contract here (as in test_persist.py):
in-process resume tests cannot prove the persisted specs + checkpointed
ledgers carry everything a cold interpreter needs.  The resumed process
must re-execute zero DONE invocations, warm its programs from the
persistent cache the crashed process seeded, pass the runtime protocol
sanitizer over the recovered history, and land bitwise-identical thetas.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

# Three lasso PLR requests on one durable single-lane session.  Lasso
# because its executables are portable across processes (pure XLA, no
# LAPACK custom calls — see PersistentProgramCache.portable), so the
# resumed process can prove it warms from the crashed process's cache.
_CASE = """
from repro.core import DMLData, DMLPlan
from repro.data import make_plr_data

SEEDS = (3, 4, 5)

def cases():
    for s in SEEDS:
        data = DMLData.from_dict(
            make_plr_data(n_obs=96, dim_x=5, theta=0.5, seed=s))
        plan = DMLPlan.for_model("plr", learner="lasso",
                                 learner_params={"reg": 0.01},
                                 n_folds=3, n_rep=3, seed=s + 4)
        yield plan, data
"""

# Child 1: submit all three, poll until the drain is mid-flight — at
# least one request COMPLETE, at least one not — then hard-crash via
# os._exit so no atexit/cleanup runs: only the atomic spec files and
# ledger checkpoints survive.
_CRASH = _CASE + """
import json, os, sys
from repro.core import DMLSession
from repro.serverless import PoolConfig

sess = DMLSession(backend="wave", pool=PoolConfig(n_workers=1),
                  session_dir=sys.argv[1])
for plan, data in cases():
    sess.submit(plan, data)
for _ in range(200):
    sess.poll()
    if sess.completion_order and sess._queue:
        break
assert sess.completion_order and sess._queue     # genuinely mid-flight
done = {p.request_id: p.req.ledger.n_done for p in sess._queue}
n_inv = {p.request_id: p.req.ledger.n_invocations for p in sess._queue}
for rid in sess.completion_order:
    led = sess.request(rid).ledger
    done[rid] = led.n_done
    n_inv[rid] = led.n_invocations
print(json.dumps({"done": done, "n_inv": n_inv,
                  "completed": sess.completion_order}), flush=True)
os._exit(0)      # the simulated crash: skip interpreter teardown entirely
"""

# Child 2: resume from the session dir in a cold process and finish the
# drain, reporting what it re-executed and what it computed.
_RESUME = _CASE + """
import json, sys
from repro.core import DMLSession
from repro.serverless import PoolConfig

sess = DMLSession.resume(sys.argv[1], backend="wave",
                         pool=PoolConfig(n_workers=1))
resumed_done = {p.request_id: p.ledger.n_done for p in sess._queue}
results = sess.run()
print(json.dumps({
    "resumed_done": resumed_done,
    "billed": {r.request_id: r.report.bill.n_invocations for r in results},
    "thetas": {r.request_id: [float(t) for t in r.thetas]
               for r in results},
    "disk_hits": sess.backend.compiler.stats.disk_hits,
}), flush=True)
"""


def _run_child(script, session_dir, cache_dir, sanitize=False):
    env = dict(os.environ,
               PYTHONPATH=SRC,
               REPRO_PROGRAM_CACHE_DIR=str(cache_dir))
    if sanitize:
        env["REPRO_SANITIZE"] = "1"
    out = subprocess.run([sys.executable, "-c", script, str(session_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_crash_mid_drain_resumes_bitwise(tmp_path):
    """The satellite gate: crash a durable drain mid-flight, resume in a
    new process under the runtime sanitizer — zero DONE invocations
    re-execute, programs warm from the crashed process's persistent
    cache, and the thetas are bitwise-identical to an uninterrupted
    in-process run."""
    from repro.core import DMLSession
    from repro.serverless import PoolConfig

    session_dir = tmp_path / "session"
    cache_dir = tmp_path / "progcache"
    first = _run_child(_CRASH, session_dir, cache_dir)
    total_inv = sum(first["n_inv"].values())
    total_done = sum(first["done"].values())
    assert first["completed"]                       # some request finished
    assert 0 < total_done < total_inv               # ...and some did not
    for rid in ("0", "1", "2"):
        assert (session_dir / f"request_0000{rid}.msgpack").exists()
        assert (session_dir / f"ledger_0000{rid}.msgpack").exists()

    second = _run_child(_RESUME, session_dir, cache_dir, sanitize=True)
    # the checkpointed ledgers carried every completed row across the
    # crash — including the fully-DONE request, which resumes complete
    assert second["resumed_done"] == first["done"]
    # zero re-executed DONE invocations: the resumed drain bills exactly
    # the invocations the crash orphaned, request by request
    for rid, billed in second["billed"].items():
        assert billed == first["n_inv"][rid] - first["done"][rid]
    assert second["billed"][str(first["completed"][0])] == 0
    # the resumed process warmed at least one program from the
    # persistent cache the crashed process seeded (ISSUE 7)
    assert second["disk_hits"] >= 1

    # bitwise vs an uninterrupted run of the same specs (determinism
    # contract: results depend only on (plan, data), never the schedule)
    ns = {}
    exec(_CASE, ns)
    for rid, (plan, data) in enumerate(ns["cases"]()):
        ref = DMLSession(backend="inline",
                         pool=PoolConfig(n_workers=2)).estimate(plan, data)
        np.testing.assert_array_equal(
            np.asarray(second["thetas"][str(rid)]),
            np.asarray([float(t) for t in ref.thetas]))


def test_durable_session_checkpoints_each_final_booking(tmp_path,
                                                         monkeypatch):
    """A durable session writes a request's ledger when a wave books it,
    and only then: each file holds its request's final booking, written
    once at admission and once per wave that booked it — never again for
    waves that carried only other requests."""
    from collections import Counter

    from repro.core import DMLSession
    from repro.serverless import PoolConfig, TaskLedger

    saves = Counter()
    save = TaskLedger.save

    def counted(self, path):
        saves[path] += 1
        return save(self, path)

    monkeypatch.setattr(TaskLedger, "save", counted)
    ns = {}
    exec(_CASE, ns)
    sess = DMLSession(backend="wave", pool=PoolConfig(n_workers=1),
                      session_dir=str(tmp_path))
    rids = [sess.submit(plan, data) for plan, data in ns["cases"]()]
    sess.run()
    waves = sess.last_run_info.waves
    for rid in rids:
        req = sess.request(rid)
        path = sess._ledger_path(rid)
        on_disk = TaskLedger.load(path)
        assert on_disk.complete
        np.testing.assert_array_equal(on_disk.preds, req.ledger.preds)
        assert saves[path] == 1 + req.report.waves
        assert req.report.waves < waves     # others' waves wrote nothing
