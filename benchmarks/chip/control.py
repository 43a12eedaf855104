#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 5 --out <json>

In one process, for each seed: one run of the cell's timed path (set-up,
a window of ``--seconds`` at the cell's own load), then every number of
``chipbench.check.NUMBERS`` for the sampled answers against the float64
reference (the lower readings).  For each control seed, the same
requests computed by the reference's ``control`` (one precision step
below what the configuration states) against the float64 reference
(the upper readings).  Writes one JSON object of all readings; the
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from chipbench import check, registry  # noqa: E402
from chipbench.harness import Run, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    cell = registry.find_cell(registry.load_benchmark(), args.workload)
    run.configure_jax()
    device = run.check_device(cell.chips)
    sys.path.insert(0, str(registry.ROOT / "src"))
    names = list(check.NUMBERS)
    out = {"workload": cell.name, "device": device, "seconds": args.seconds,
           "program": {}, "control": {}}
    for seed in seeds:
        r = Run(cell, seed, args.seconds)
        r.device_kind = device["kind"]
        r.setup()
        win = r.measure(None)
        del r.sess
        pairs, failed = r.compare(win)
        out["program"][seed] = dict(check.numbers(pairs, names),
                                    compared=len(pairs), failed=failed,
                                    completed=len(win.completed))
        log(f"[program] seed={seed} {out['program'][seed]}")
        if seed in ctl:
            t = time.perf_counter()
            cpairs, _ = r.compare(win, fn="control")
            out["control"][seed] = dict(check.numbers(cpairs, names),
                                        compared=len(cpairs),
                                        seconds=time.perf_counter() - t)
            log(f"[control] seed={seed} {out['control'][seed]}")
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
