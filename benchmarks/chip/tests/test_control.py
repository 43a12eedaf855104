"""The control, the reference one precision step down (bf16_3x products,
float32 solves), has to come out not correct against the float64
reference under each configuration's limits, at the configuration's
own size; the float64 reference itself comes out correct."""
import numpy as np
import pytest

from chipbench import check, registry, traffic

CELLS = ["mc_backlog", "bonus_backlog"]


def pairs(cell_name, fn, n_requests):
    cell = registry.find_cell(registry.load_benchmark(), cell_name)
    cfg, gen = cell.config, registry.generator(cell)
    ref = registry.reference(cell)
    reg = float(cfg["learner_params"]["reg"])
    out = []
    for i in range(n_requests):
        req = traffic.request(3000000041, i, cfg)
        data = gen.make(cfg, traffic.data_stream(3000000041, cell.traffic, i))
        masks = ref.fold_masks(int(cfg["n_obs"]), int(cfg["n_folds"]),
                               int(cfg["n_rep"]), req.plan_seed)
        r = ref.reference(data["x"], data["y"], data["d"], masks, reg)
        a = getattr(ref, fn)(data["x"], data["y"], data["d"], masks, reg)
        out.append((a, r))
    return cell.config["limits"], out


@pytest.mark.parametrize("cell,n_requests", [("mc_backlog", 32),
                                             ("bonus_backlog", 2)])
def test_control_is_not_correct(cell, n_requests):
    limits, ps = pairs(cell, "control", n_requests)
    ok, checks = check.judge(ps, limits, failed=0)
    assert not ok, checks
    assert checks["compared_requests_min1"][0] == n_requests


@pytest.mark.parametrize("cell", CELLS)
def test_reference_against_itself_is_correct(cell):
    limits, ps = pairs(cell, "reference", 2)
    ok, checks = check.judge(ps, limits, failed=0)
    assert ok, checks
    assert all(v == 0.0 for k, (v, _) in checks.items()
               if k in limits)


def test_failed_request_or_none_compared_is_not_correct():
    limits, ps = pairs("mc_backlog", "reference", 1)
    assert not check.judge(ps, limits, failed=1)[0]
    assert not check.judge([], limits, failed=0)[0]
    assert np.isfinite(check.judge(ps, limits, failed=0)[1]
                       ["theta_gap_se"][0])
