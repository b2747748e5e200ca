"""Readings of planted faults beyond ``control.py``'s, for a cell whose
reference plants more (``FAULTS`` of its reference module).

    python3 perfbench/control_faults.py --workload NAME --seeds 1 2 3

For each seed it prints, as one JSON line, the compared numbers of the
program's first steps and of the control (the reference one precision
below the configuration's), as ``control.py --program`` does, then those
of the reference with each of its module's planted faults (``FAULTS``):
the lower and upper readings that set the limits. All seeds run in one
process.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def readings(found: dict, seed: int, *, devices, driver=None) -> dict:
    """The program's checks and the control's (as ``control.readings``),
    then each of the reference's planted faults' checks."""
    import control
    import run as harness

    config, traffic = found["config"], found["traffic"]
    if driver is None:
        driver = harness.load_module(
            os.path.join(BENCH, "drivers", config["driver"] + ".py"),
            "perfbench_driver_" + config["driver"])
    got = driver.first_steps(config, traffic, seed, devices)
    want = driver.reference(config, traffic, seed, got)
    out = {"seed": seed, "program": driver.compare(config, got, want)}
    lower = control.LOWER[config["reference_dtype"]]
    for name, kw in [("control", {"dtype": lower})] + [
            (f, {"fault": f}) for f in importlib.import_module(
                "reference." + config["reference"]).FAULTS]:
        bad = driver.reference(config, traffic, seed, got, **kw)
        out[name] = driver.compare(config, driver.control_as_got(got, bad),
                                   want)
        print(f"[perfbench] seed {seed} {name}: {out[name]}",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    found = harness.find_cell(harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    devices = harness.require_chips(int(found["cell"]["chips"]))
    harness.enable_cache()
    for seed in args.seeds:
        print(json.dumps(readings(found, seed, devices=devices)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
