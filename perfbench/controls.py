"""Readings that set the limits of ``correct``, on the chip.

    python3 perfbench/controls.py --workload NAME --seeds 11,12,13 [--control]

For each seed, in one process: the cell's set-up, as many calls of the
timed path as a run checks, then the number the run compares (the
program against the plain reference) and, with ``--control``, the same
number for the reference computed one precision below the
configuration's (float8 for bfloat16 activations, bfloat16 for
float32), in the program's place. Prints one JSON line per seed. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv) -> int:
    from perfbench import device, harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell, _, config, traffic = harness.lookup(bench, args.workload)
    try:
        devices = device.require(cell["chips"])
    except device.DeviceError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = harness.load_module(harness.HERE / "drivers"
                                 / f"{config['driver']}.py")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        c = driver.setup(config, traffic, seed, devices)
        samples = [(i, c.call(i)) for i in range(traffic["check_calls"])]
        c.release()
        row = {"workload": args.workload, "seed": seed}
        if hasattr(c, "gaps"):
            row["logit_gap"], ctl = c.gaps(samples, control=args.control)
            if args.control:
                row["control_logit_gap"] = ctl
        else:
            row["logit_err"] = c.logit_err(samples)
            if args.control:
                row["control_logit_err"] = c.control_err(samples)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del c, samples
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
