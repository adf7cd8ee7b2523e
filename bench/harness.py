"""One benchmark cell in one process: set-up, a measured window, the
comparison with the plain reference, and one JSON result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name:

  bench/configs/<config>.json      sizes, guarantees, limits, reference
  bench/traffic/<traffic>.json     a traffic mix; its "driver" names
  bench/drivers/<driver>.py        the general generator that runs it
  bench/references/<name>.py       the plain reference a config names
  bench/layers/<metric>.py         the reader of one per-layer metric
  bench/rooflines/<kernel>.py      one kernel's operation and byte count

so a later change adds a cell, a configuration or a metric by adding
files and an entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
GIB = float(1 << 30)


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ discovery

def load_spec(root: Path = ROOT) -> dict:
    """The parsed ``BENCHMARK.json``."""
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in spec['workloads']]}")


def load_config(spec: dict, name: str, root: Path = ROOT) -> dict:
    for entry in spec["configs"]:
        if entry["name"] == name:
            return json.loads((Path(root) / entry["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, cell: str, section: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``; an entry without that key goes to
    every cell (end-to-end) or to every cell that reports the end-to-end
    metric it ``moves`` (per-layer)."""
    e2e = [m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


# ------------------------------------------------------------------ the run

class Run:
    """What a driver, a per-layer reader and a roofline count see of one
    run: the cell's data files, the devices, and what the window did.

    Drivers fill ``counters`` (program counters, over the window),
    ``work`` (facts of the work done, such as builds or launches) and
    ``e2e`` (end-to-end metric values by name) and ``checks`` ((name,
    value, limit) of the comparison with the reference)."""

    def __init__(self, *, cell: str, config: dict, traffic: dict,
                 chips: int, seed: int, seconds: float, trace: bool,
                 devices: list, control: bool = False):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.chips = chips
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        # the control: the check compares the reference computed one
        # precision lower, in the program's place (never in a benchmark run)
        self.control = control
        self.device_kind = devices[0].device_kind
        self.counters: Dict[str, float] = {}
        self.work: Dict[str, float] = {}
        self.e2e: Dict[str, float] = {}
        self.checks: list = []
        self.attempted = 0
        self.failed = 0
        self.window_s: Optional[float] = None
        self.trace_view = None
        self.notes: Dict[str, object] = {}     # scalars for the result
        self.keep: Dict[str, object] = {}      # what the check needs

    def reference(self):
        """The plain reference module the configuration names."""
        return load_module("references", self.config["reference"])

    def limit(self, name: str) -> float:
        return float(self.config["limits"][name])

    def roofline(self, kernel: str) -> Optional[float]:
        """Share (%) of the roofline reached by ``kernel``'s device events
        in the traced window: the larger of its operations over the peak
        for its operand dtype and its bytes over HBM bandwidth, divided by
        its summed device time.  None where the trace has no such event."""
        from yardstick import peaks
        if self.trace_view is None:
            return None
        mod = load_module("rooflines", kernel)
        secs, n = self.trace_view.op_seconds(mod.matches)
        if n == 0 or secs <= 0:
            return None
        flops, nbytes, dtype = mod.work(self, n)
        t_min = max(flops / peaks.flops_peak(self.device_kind, dtype),
                    nbytes / peaks.peaks(self.device_kind)["hbm_bytes_per_s"])
        return 100.0 * t_min / secs

    def idle_share(self) -> Optional[float]:
        """Share (%) of the traced window in which the devices ran nothing."""
        if (self.trace_view is None or not self.trace_view.devices
                or not self.window_s):
            return None
        return 100.0 * (1.0 - self.trace_view.busy_s() / self.window_s)


def check_devices(chips: int, require_tpu: bool = True) -> list:
    """The first ``chips`` devices; raises :class:`NoDevice` unless they
    are TPUs (a measurement never falls back to the CPU)."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class _Profiler:
    """The profiler around the window, writing under TMPDIR; ``keep`` is a
    directory to copy the ``.xplane.pb`` into."""

    def __init__(self, on: bool, keep: Optional[str] = None):
        self.on = on
        self.keep = keep
        self.dir = None

    def start(self):
        if self.on:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if not self.on:
            return None
        import jax
        from yardstick import trace
        jax.profiler.stop_trace()
        try:
            files = sorted(Path(self.dir).glob("**/*.xplane.pb"))
            if files and self.keep:
                Path(self.keep).mkdir(parents=True, exist_ok=True)
                shutil.copy(files[-1], self.keep)
            return trace.load(files[-1]) if files else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(run: Run, t_start: float, spec: Optional[dict] = None,
             keep_trace: Optional[str] = None) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    from yardstick.clock import CompileClock
    from yardstick.compare import checks_line

    clock = CompileClock()
    driver = load_module("drivers", run.traffic["driver"]).Driver(run)
    driver.setup()
    # what set-up made lives on; later collections scan only the window's
    gc.collect()
    gc.freeze()
    prof = _Profiler(run.trace, keep_trace)
    prof.start()
    compiles0 = clock.events
    t0 = time.monotonic()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        driver.window(t0)
    t1 = time.monotonic()
    run.window_s = t1 - t0
    run.trace_view = prof.stop()
    compiles = clock.events - compiles0
    peak = memory_peak_bytes(run.devices)
    if peak is not None:
        run.e2e["hbm_peak_gib"] = peak / GIB
    run.e2e["setup_s"] = setup_s
    driver.release()
    del driver
    gc.unfreeze()
    gc.collect()
    # the reference runs after the peak was read and the program's state
    # was freed; the driver left what it needs in run.keep
    load_module("drivers", run.traffic["driver"]).check(run)
    checks, ok = checks_line(run.checks)

    metrics = {}
    if spec is not None:
        section = "per_layer" if run.trace else "end_to_end"
        for m in metrics_for(spec, run.cell, section):
            if run.trace:
                value = load_module("layers", m["name"]).read(run)
            else:
                value = run.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": ok, "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace and run.trace_view is not None:
        device["busy_s"] = run.trace_view.busy_s()
        device["window_s"] = run.window_s
        out["breakdown"] = {"device_ops": run.trace_view.top_ops(),
                            "idle_gaps": run.trace_view.idle_gaps()}
    out["run"] = {"setup_s": setup_s, "window_s": run.window_s,
                  "compiles_in_window": compiles, **run.work,
                  **{k: v for k, v in run.notes.items()
                     if isinstance(v, (int, float, str))}}
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    """The checks as the last lines on stderr, then the result line as
    the last line of stdout."""
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
