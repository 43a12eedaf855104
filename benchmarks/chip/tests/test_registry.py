"""The harness finds a configuration, a traffic mix and a layer reader
from new files alone."""
import json

import pytest

from chipbench import registry


def write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


@pytest.fixture
def checkout(tmp_path):
    bench_dir = tmp_path / "bench"
    write(bench_dir / "configs" / "new_cfg.json",
          json.dumps({"name": "new_cfg", "n_obs": 7}))
    write(bench_dir / "traffic" / "new_mix.json",
          json.dumps({"loop": "closed", "outstanding": 3}))
    write(bench_dir / "layers" / "new_metric.py",
          "def read(w):\n    return 2.0 * w\n")
    write(bench_dir / "layers" / "exact.name.py",
          "def read(w):\n    return -w\n")
    write(bench_dir / "kernels" / "gram" / "new_kernel.json",
          json.dumps({"match": ["my_gram"]}))
    bench = {
        "configs": [{"name": "new_cfg",
                     "file": "bench/configs/new_cfg.json"}],
        "workloads": [{"name": "new_cell", "config": "new_cfg",
                       "traffic": "new_mix", "chips": 1},
                      {"name": "other", "config": "new_cfg",
                       "traffic": "new_mix", "chips": 1}],
        "end_to_end": [{"name": "fits_per_s", "workloads": ["new_cell"]},
                       {"name": "latency_p50_s", "workloads": ["other"]},
                       {"name": "setup_s"}],
        "per_layer": [{"name": "new_metric.sfx", "moves": "fits_per_s"},
                      {"name": "listed", "moves": "setup_s",
                       "workloads": ["other"]}],
    }
    write(tmp_path / "BENCHMARK.json", json.dumps(bench))
    return tmp_path, bench_dir


def test_cell_found_from_new_files(checkout):
    root, bench_dir = checkout
    cell = registry.find_cell(registry.load_benchmark(root), "new_cell",
                              root=root, bench_dir=bench_dir)
    assert cell.config == {"name": "new_cfg", "n_obs": 7}
    assert cell.traffic == {"loop": "closed", "outstanding": 3}
    assert [m["name"] for m in cell.end_to_end] == ["fits_per_s", "setup_s"]
    # unlisted metrics go to every cell reporting what they move; a
    # metric with a workloads list only to those cells
    assert [m["name"] for m in cell.per_layer] == ["new_metric.sfx"]
    other = registry.find_cell(registry.load_benchmark(root), "other",
                               root=root, bench_dir=bench_dir)
    assert [m["name"] for m in other.per_layer] == ["listed"]


def test_reader_found_by_name_or_stem(checkout):
    _, bench_dir = checkout
    assert registry.layer_reader(bench_dir, "new_metric.sfx").read(3) == 6.0
    assert registry.layer_reader(bench_dir, "exact.name").read(3) == -3
    with pytest.raises(FileNotFoundError):
        registry.layer_reader(bench_dir, "absent.sfx")


def test_kernel_names_from_new_file(checkout):
    _, bench_dir = checkout
    assert registry.kernel_names(bench_dir, "gram") == ["my_gram"]


def test_unknown_cell_is_an_error(checkout):
    root, bench_dir = checkout
    with pytest.raises(KeyError):
        registry.find_cell(registry.load_benchmark(root), "absent",
                           root=root, bench_dir=bench_dir)


def test_committed_cells_resolve():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cell = registry.find_cell(bench, w["name"])
        assert cell.end_to_end and cell.per_layer
        registry.generator(cell)
        registry.reference(cell)
        for m in cell.per_layer:
            registry.layer_reader(cell.bench_dir, m["name"])


def test_every_seed_offers_the_same_arrivals():
    import numpy as np

    from chipbench import traffic
    mix = {"rate_per_s": 2.4}
    runs = [np.diff(traffic.arrivals(s, mix, 45.0, "window") + [45.0])
            for s in (1, 2, 2 ** 31 + 5)]
    for gaps in runs:
        assert len(gaps) == 108
        assert np.allclose(np.sort(gaps), np.sort(runs[0]))
        assert gaps.sum() == pytest.approx(45.0)
    assert not np.allclose(runs[0], runs[1])
