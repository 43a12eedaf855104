"""Cross-fitting grid properties (partitions, scaling bijections, stitching).

Formerly hypothesis property tests; now seeded parametrize sweeps so the
tier-1 suite collects on a clean environment (no hypothesis dependency).
"""
import numpy as np
import pytest

from repro.core.crossfit import (
    TaskGrid, check_partition, draw_fold_masks, stitch_predictions,
)


@pytest.mark.parametrize("n,k,m,seed", [
    (11, 2, 1, 0), (23, 3, 2, 7), (57, 5, 3, 123), (100, 7, 5, 2**19),
    (128, 4, 2, 31337), (199, 6, 4, 1), (200, 2, 5, 999983), (64, 7, 1, 42),
])
def test_fold_masks_partition(n, k, m, seed):
    masks = draw_fold_masks(n, k, m, seed)
    assert masks.shape == (m, k, n)
    assert check_partition(masks)
    sizes = masks.sum(axis=2)
    assert (np.abs(sizes - n / k) <= 1).all()      # balanced folds


def test_fold_masks_deterministic():
    a = draw_fold_masks(100, 5, 3, seed=7)
    b = draw_fold_masks(100, 5, 3, seed=7)
    assert (a == b).all()
    c = draw_fold_masks(100, 5, 3, seed=8)
    assert (a != c).any()


@pytest.mark.parametrize("m,k,l", [
    (1, 2, 1), (2, 3, 2), (3, 5, 3), (6, 2, 4), (4, 6, 1), (5, 4, 5),
])
@pytest.mark.parametrize("scaling", ["n_rep", "n_folds*n_rep"])
def test_invocation_mapping_bijection(m, k, l, scaling):
    grid = TaskGrid(m, k, l)
    seen = set()
    for inv in range(grid.n_invocations(scaling)):
        for key in grid.tasks_of_invocation(inv, scaling):
            assert grid.invocation_of(key, scaling) == inv
            flat = key.flat(k, l)
            assert flat not in seen
            seen.add(flat)
    assert len(seen) == grid.n_tasks


@pytest.mark.parametrize("m,k,l", [(2, 3, 2), (3, 5, 1), (4, 2, 5)])
@pytest.mark.parametrize("scaling", ["n_rep", "n_folds*n_rep"])
def test_invocation_task_ids_matches_scalar_mapping(m, k, l, scaling):
    """The vectorized mapping used by the backends must agree with the
    per-key reference."""
    grid = TaskGrid(m, k, l)
    inv = np.arange(grid.n_invocations(scaling))
    mat = grid.invocation_task_ids(inv, scaling)
    assert mat.shape == (len(inv), grid.tasks_per_invocation(scaling))
    for i in inv:
        expect = [key.flat(k, l) for key in grid.tasks_of_invocation(int(i),
                                                                     scaling)]
        assert list(mat[i]) == expect
    tm, tk, tl = grid.task_coords()
    for key in grid.keys():
        flat = key.flat(k, l)
        assert (tm[flat], tk[flat], tl[flat]) == (key.rep, key.fold,
                                                  key.nuisance)


def test_paper_invocation_counts():
    """PLR with K=5, M=100, L=2: 200 vs 1000 invocations (paper §4.2)."""
    grid = TaskGrid(100, 5, 2)
    assert grid.n_invocations("n_rep") == 200
    assert grid.n_invocations("n_folds*n_rep") == 1000
    assert grid.n_tasks == 1000


def test_stitch_predictions():
    masks = draw_fold_masks(30, 3, 2, seed=0)
    preds = np.random.default_rng(0).normal(size=(2, 3, 30)).astype(np.float32)
    out = stitch_predictions(masks, preds)
    assert out.shape == (2, 30)
    m, k, i = 1, 2, int(np.where(masks[1, 2])[0][0])
    assert out[m, i] == pytest.approx(preds[m, k, i])


@pytest.mark.parametrize("m", [1, 7])
@pytest.mark.parametrize("k", [2, 5])
def test_stitch_gather_is_bitwise_the_masked_sum(m, k):
    """The fold-index gather against the masked sum over folds it
    replaced, on random fold draws, with and without a nuisance axis."""
    n, n_nuis = 61, 3
    rng = np.random.default_rng(10 * m + k)
    masks = draw_fold_masks(n, k, m, seed=int(rng.integers(1 << 30)))
    preds = rng.normal(size=(m, k, n_nuis, n)).astype(np.float32)

    def masked_sum(p):
        return np.einsum("mkn,mkn->mn", masks.astype(p.dtype), p)
    out = stitch_predictions(masks, preds)
    assert out.shape == (m, n_nuis, n) and out.dtype == np.float32
    for l in range(n_nuis):
        ref = masked_sum(preds[:, :, l])
        one = stitch_predictions(masks, preds[:, :, l])
        assert np.array_equal(one.view(np.uint32), ref.view(np.uint32))
        assert np.array_equal(out[:, l].view(np.uint32), ref.view(np.uint32))
