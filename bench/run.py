"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks`` last: each number compared with the reference,
beside its limit).  Exits 3 with no result when JAX finds no TPU or fewer
chips than the cell asks for.

``--traffic <name>`` runs the cell's configuration under another traffic
mix of ``bench/traffic/`` (the knee sweep, ``serve-knee-sweep``);
``--keep-trace <dir>`` keeps the traced window's ``.xplane.pb`` there.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the traced window's xplane into")
    args = ap.parse_args(argv)

    # the TPU runtime otherwise logs to /tmp/tpu_logs whatever TMPDIR says
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import harness

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(args.traffic or cell["traffic"])
    try:
        devices = harness.check_devices(cell["chips"])
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under test at {src}", file=sys.stderr)
        return 4
    sys.path.insert(0, str(src))
    run = harness.Run(cell=cell["name"], config=config, traffic=traffic,
                      chips=cell["chips"], seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices)
    out = harness.run_cell(run, T_START, spec, keep_trace=args.keep_trace)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
