"""Readings that set the limits of the comparison with the reference.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 3

For each seed, in one process, runs the cell's set-up and a short window
at the cell's own sizes and load, then the comparison twice on the same sample:
with the program's answers, and with the control in their place,
the reference computed one precision lower (dot products in three
bfloat16 passes against the configuration's float32 at HIGHEST).  Prints
one JSON line per seed and reading.  The benchmark's own runs never run
the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def report(workload, seed, reading, checks):
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    print(json.dumps({"workload": workload, "seed": seed, "reading": reading,
                      "correct": ok, "checks": checks}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import harness
    from yardstick.compare import checks_line

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    try:
        devices = harness.check_devices(cell["chips"])
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    sys.path.insert(0, str(harness.ROOT / "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(
            cell=cell["name"], config=harness.load_config(spec, cell["config"]),
            traffic=harness.load_traffic(cell["traffic"]), chips=cell["chips"],
            seed=seed, seconds=args.seconds, trace=False, devices=devices)
        out = harness.run_cell(run, time.monotonic())
        report(cell["name"], seed, "program", out["checks"])
        # the same window's sample, answered by the control
        run.checks, run.control = [], True
        harness.load_module("drivers", run.traffic["driver"]).check(run)
        report(cell["name"], seed, "control", checks_line(run.checks)[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
