"""The benchmark harness: one run of one cell on the chip.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Everything a cell needs is found by name. ``BENCHMARK.json`` (at the root
of the checkout) names the cell's configuration and traffic mix; the
configuration's file names its driver (``perfbench/drivers/<driver>.py``)
and its plain reference; the traffic mix is ``perfbench/traffic/<traffic>
.json``; each per-layer metric is read by ``perfbench/metrics/<name>.py``.
A later cell, configuration or metric is new files and entries, not edits.

A run warms up every shape it uses (set-up), measures for ``--seconds``,
then checks what the timed path produced against the plain reference and
prints one JSON line last on stdout. With ``--trace 1`` the window is
traced by the JAX profiler and the metrics are the cell's per-layer ones.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT_DIR = os.path.join(ROOT, "perfbench_out")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# the TPU runtime otherwise writes its logs outside the checkout, and a
# run writes only inside it
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(SystemExit):
    """Raised before any work when the chips the cell needs are absent."""


def log(msg: str):
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def find_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration and traffic files, and the
    metrics it reports, all resolved by name from ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "perfbench", "traffic",
                                     cell["traffic"] + ".json"))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in moved]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def require_chips(count: int):
    """The chips or nothing: the accelerator JAX found, with at least
    ``count`` devices, else ``NoChip`` before any work is done."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"perfbench: JAX found no TPU (platform "
                     f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise NoChip(f"perfbench: the cell needs {count} chips, JAX found "
                     f"{len(devs)}; nothing was run")
    return devs[:count]


def enable_cache():
    """JAX's persistent compile cache at the checkout's fixed directory
    (or ``JAX_COMPILATION_CACHE_DIR``), holding every program."""
    import jax

    from repro.cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_layer_metrics(layer_metrics, ctx: dict, bench: str = BENCH) -> dict:
    """Each per-layer metric from its own reader
    (``<bench>/metrics/<name>.py``); a reader that finds nothing returns
    None and the metric is left out."""
    out = {}
    for m in layer_metrics:
        path = os.path.join(bench, "metrics", m["name"] + ".py")
        value = load_module(path, "perfbench_metric_" + m["name"]
                            .replace(".", "_")).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Window:
    """The measured window and the harness's own host spans. ``open``
    ends set-up (and starts the profiler under ``--trace 1``), ``span``
    times a piece of host work by name (a profiler annotation too, so the
    trace can label idle gaps with it), ``close`` ends the window and
    reads the device memory peak, before the reference runs."""

    def __init__(self, trace_dir, t_start: float, devices=()):
        self.trace_dir = trace_dir
        self.t_start = t_start
        self.devices = devices
        self.t_open = self.t_close = None
        self.peak_bytes = 0
        self.spans: dict = {}
        self.marks = []

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_open

    def mark(self, name: str):
        """Note the end of a set-up phase (printed when the window opens)."""
        self.marks.append((name, time.perf_counter()))

    def open(self):
        t, parts = self.t_start, []
        for name, at in self.marks:
            parts.append(f"{name} {at - t:.1f}")
            t = at
        log("set-up (s): " + ", ".join(parts + [
            f"rest {time.perf_counter() - t:.1f}"]))
        if self.trace_dir:
            import jax
            jax.profiler.start_trace(self.trace_dir)
        self.t_open = time.perf_counter()

    def close(self):
        self.t_close = time.perf_counter()
        self.peak_bytes = max(
            (int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in self.devices), default=0)
        if self.trace_dir:
            import jax
            jax.profiler.stop_trace()

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, window: Window, name: str):
        self.window, self.name, self.note = window, name, None

    def __enter__(self):
        if self.window.trace_dir:
            import jax
            self.note = jax.profiler.TraceAnnotation("bench:" + self.name)
            self.note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.note is not None:
            self.note.__exit__(*exc)
        if self.window.t_open is not None and self.window.t_close is None:
            self.window.spans.setdefault(self.name, []).append(dt)
        return False


def run_cell(found: dict, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float = T_START, driver=None) -> dict:
    """Drive one run of the cell and return its result line (a dict).
    ``driver`` replaces the configuration's driver module (tests)."""
    config, traffic = found["config"], found["traffic"]
    if driver is None:
        driver = load_module(os.path.join(BENCH, "drivers",
                                          config["driver"] + ".py"),
                             "perfbench_driver_" + config["driver"])
    trace_dir = None
    if trace:
        import shutil
        trace_dir = os.path.join(OUT_DIR, "trace", found["cell"]["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = Window(trace_dir, t_start, devices)
    res = driver.run(config, traffic, seed=seed, seconds=seconds,
                     window=window, devices=devices)
    # res: attempted, failed, e2e {name: value}, checks
    # [(name, value, limit)], ctx {...} for the per-layer readers
    correct = all(v <= lim for _, v, lim in res["checks"])
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": window.peak_bytes}
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    if trace:
        import trace_reduce
        tr = trace_reduce.reduce_dir(trace_dir, devices=len(devices))
        ctx = dict(res["ctx"], trace=tr, window=window, config=config,
                   traffic=traffic, device_kind=dev0.device_kind,
                   chips=len(devices))
        line["metrics"] = read_layer_metrics(found["per_layer"], ctx)
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["device"] = device
        line["breakdown"] = tr["breakdown"]
    else:
        units = {m["name"]: m["unit"] for m in found["end_to_end"]}
        e2e = dict(res["e2e"], setup_s=window.setup_s)
        line["metrics"] = {name: {"value": e2e[name], "unit": unit}
                           for name, unit in units.items()}
        line["device"] = device
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in res["checks"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        log(f"no BENCHMARK.json at {ROOT}")
        return 2
    found = find_cell(load_json(bench_path), args.workload)
    try:
        devices = require_chips(int(found["cell"]["chips"]))
    except NoChip as e:
        log(str(e))
        return 2
    enable_cache()
    line = run_cell(found, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
