"""Runs one cell of ``BENCHMARK.json`` once and prints one JSON line.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by name:

* ``BENCHMARK.json`` names each cell's configuration (``file``) and its
  traffic mix, ``traffic/<traffic>.json``;
* a configuration file names its driver, ``drivers/<driver>.py``, which
  builds the cell from the configuration, the traffic and the seed
  (``setup``) and knows the program's entry points;
* every metric, end-to-end or per-layer, is ``metrics/<name>.py`` with
  a ``read(ctx)`` that returns a number, or None where the run has
  nothing for it to read (the metric is then left out of the line).

A run: set-up (weights and inputs from the seed, plans, warm-up of the
cell's own shapes) -> a window of back-to-back calls, ``--seconds``
long and ending at a call boundary (``--trace 1``: a short traced
window instead) -> the peak device memory -> the program's state freed
-> the plain reference over a sample of the window's calls, drawn from
the seed -> the numbers compared, each with its limit, as the last
lines of standard error and under ``checks`` in the result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
import types

import numpy as np

from perfbench import device as device_lib
from perfbench.clock import CompileClock

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (the run is
    correct only where every value is at most its limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""

    cell: str
    config: dict
    traffic: dict
    peaks: dict
    work: dict  # the driver's counts (see each driver's ``work``)
    setup_s: float
    calls: int = 0
    units: float = 0.0  # work units the window completed
    elapsed_s: float = 0.0  # window length, ending at a call boundary
    trace: object | None = None  # trace.TraceView of a --trace 1 run


def load_module(path: pathlib.Path) -> types.ModuleType:
    """Import a file by path (names may hold dots and dashes)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    return json.loads(path.read_text())


def lookup(bench: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration entry, configuration, traffic) by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    entries = {c["name"]: c for c in bench["configs"]}
    entry = entries[cell["config"]]
    config = load_json(ROOT / entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, entry, config, traffic


def metrics_for(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def read_metrics(specs: list[dict], ctx: Context) -> dict:
    out = {}
    for m in specs:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def sample_calls(seed: int, n_calls: int, n_sample: int) -> list[int]:
    """Indices of the calls whose outputs the reference checks."""
    rng = np.random.default_rng(seed)
    n = min(n_sample, n_calls)
    return sorted(int(i) for i in rng.choice(n_calls, size=n, replace=False))


def run_window(cell_obj, seconds: float, clock: CompileClock):
    """Back-to-back calls until ``seconds`` have passed; the window ends
    at the boundary of the call that crosses it."""
    outputs = []
    c0 = clock.compiles
    t0 = time.perf_counter()
    while True:
        outputs.append(cell_obj.call(len(outputs)))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return outputs, elapsed, clock.compiles - c0


def run_traced(cell_obj, n_calls: int, clock: CompileClock):
    """A short window of ``n_calls`` calls under the profiler."""
    import jax

    from perfbench import trace as trace_lib

    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        c0 = clock.compiles
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        outputs = [cell_obj.call(i) for i in range(n_calls)]
        elapsed = time.perf_counter() - t0
        jax.profiler.stop_trace()
        events = trace_lib.load_device_events(trace_lib.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return outputs, elapsed, clock.compiles - c0, \
        trace_lib.TraceView(events, elapsed)


def parse(argv: list[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: list[str], t_start: float) -> int:
    args = parse(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, _, config, traffic = lookup(bench, args.workload)
    try:
        devices = device_lib.require(cell["chips"])
        peaks = device_lib.peaks_for(devices[0].device_kind)
    except device_lib.DeviceError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    result, checks = execute(bench, args, config, traffic, devices, peaks,
                             t_start)
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def execute(bench, args, config, traffic, devices, peaks, t_start):
    """Set-up, window, reference check of one run; returns the result
    object and the checks."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = device_lib.describe(devices)
    print(f"device: {dev}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # Every program goes to the cache, the small eager ones of set-up
    # too, so that only a checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.monitoring.register_event_listener(clock.on_event)

    driver = load_module(HERE / "drivers" / f"{config['driver']}.py")
    cell_obj = driver.setup(config, traffic, args.seed, devices)
    setup_s = time.perf_counter() - t_start
    print(f"set-up: {setup_s:.3f} s, of which compile {clock.total:.3f} s "
          f"({clock.compiles} backend compiles; persistent cache "
          f"{clock.cache_hits} hits, {clock.cache_misses} misses)",
          flush=True)

    ctx = Context(cell=args.workload, config=config, traffic=traffic,
                  peaks=peaks, work=cell_obj.work(), setup_s=setup_s)
    if args.trace:
        outputs, elapsed, compiles, view = run_traced(
            cell_obj, traffic["trace_calls"], clock)
        ctx.trace = view
    else:
        outputs, elapsed, compiles = run_window(cell_obj, args.seconds, clock)
    ctx.calls, ctx.elapsed_s = len(outputs), elapsed
    ctx.units = cell_obj.units_per_call * len(outputs)
    print(f"window: {len(outputs)} calls in {elapsed:.3f} s, "
          f"{compiles} backend compiles inside", flush=True)
    dev["memory_peak_bytes"] = device_lib.memory_peak_bytes(devices)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(metrics_for(bench, args.workload, kind), ctx)
    result = {"correct": False, "attempted": int(ctx.units), "failed": 0,
              "metrics": metrics, "device": dev}
    if args.trace:
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()

    picked = sample_calls(args.seed, len(outputs), traffic["check_calls"])
    samples = [(i, outputs[i]) for i in picked]
    del outputs
    cell_obj.release()
    t0 = time.perf_counter()
    checks = cell_obj.check(samples)
    checks.append(Check("compiles_in_window", float(compiles), 0.0))
    print(f"reference check of calls {picked}: "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    result["correct"] = all(c.ok for c in checks)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks
