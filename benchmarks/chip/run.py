#!/usr/bin/env python3
"""The chip benchmark of the DML estimation service.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine holding the TPU chips the
cell asks for; the process is the only one that touches them.  It
checks the device (no TPU, or fewer chips than the cell asks for, exits
non-zero with no result), makes its data from ``--seed``, builds one
``DMLSession``, warms every shape the cell's traffic uses, measures for
``--seconds``, then compares sampled answers of the window with a plain
float64 reference.  Set-up parts, then the compared numbers beside
their limits, go to standard error; the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics from a profiler trace of the window), ``device`` and, last,
``checks``.

Cells, configurations, traffic mixes and per-layer readers are found by
the names in ``BENCHMARK.json`` (see ``chipbench/registry.py``).
JAX's compilation cache is kept at ``.jax_cache/`` in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import registry  # noqa: E402
from chipbench.harness import log, run_cell  # noqa: E402

CACHE_DIR = registry.ROOT / ".jax_cache"


def check_device(chips: int) -> dict:
    """The device as JAX reports it; raises without a TPU or with fewer
    chips than the cell asks for."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{dev['count']}")
    return dev


def configure_jax() -> None:
    """Compilation cache in the checkout, every program kept in it."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = registry.find_cell(registry.load_benchmark(), args.workload)
        configure_jax()
        device = check_device(cell.chips)
        log(f"[setup] jax_init_s={time.perf_counter() - T_START}")
        sys.path.insert(0, str(registry.ROOT / "src"))
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       device, T_START)
    except Exception:                      # report, then fail the run
        import traceback
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
