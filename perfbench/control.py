"""Readings that set a cell's limits, on the chip at the cell's size.

    python3 perfbench/control.py --workload NAME --seeds 1 2 3 [--program]

For each seed it prints the compared numbers of the control: the plain
reference computed one precision below the configuration's (bfloat16 for
float32), against the reference. With ``--program`` it prints, beside
them, the numbers of the timed path's first steps against the reference
(the lower readings); with ``--faults`` those of the reference with each
planted fault (half the batch left out, the exchange left out). A step
that returns its state unchanged reads 1 by construction of the update
gap and needs no run. All seeds run in one process, so the programs
compile once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


FAULTS = ("half_batch", "no_exchange")


def readings(found: dict, seed: int, *, program: bool, devices,
             faults: bool = False, driver=None) -> dict:
    """{"control": checks, "program": checks or None} for one seed."""
    import run as harness

    config, traffic = found["config"], found["traffic"]
    if driver is None:
        driver = harness.load_module(
            os.path.join(BENCH, "drivers", config["driver"] + ".py"),
            "perfbench_driver_" + config["driver"])
    out = {"seed": seed, "program": None}
    got = driver.first_steps(config, traffic, seed, devices)
    want = driver.reference(config, traffic, seed, got)
    if program:
        out["program"] = driver.compare(config, got, want)
    lower = LOWER[config["reference_dtype"]]
    ctl = driver.reference(config, traffic, seed, got, dtype=lower)
    out["control"] = driver.compare(config, driver.control_as_got(got, ctl),
                                    want)
    for fault in FAULTS if faults else ():
        bad = driver.reference(config, traffic, seed, got,
                               fault=fault)
        out[fault] = driver.compare(config, driver.control_as_got(got, bad),
                                    want)
    return out


def main(argv=None) -> int:
    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    found = harness.find_cell(harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    devices = harness.require_chips(int(found["cell"]["chips"]))
    harness.enable_cache()
    for seed in args.seeds:
        print(json.dumps(readings(found, seed, program=args.program,
                                  faults=args.faults, devices=devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
