"""One cell's device time by the program's own scopes, and its idle time
by the serving loop's host spans.

    python3 perfbench/scope_report.py --workload NAME --seed N [--fixture F]

From the root of a checkout, on the chip, like ``run.py``. Runs the
cell's set-up and one traced window as ``run.py --trace 1`` does, with
XLA writing each compiled program's optimized HLO, the source of every
operation's scope (``scopes.dumped_op_names``), and with the
compilation cache off: a program loaded from the cache is not compiled,
so not dumped. Prints, for each of the cell's programs, its device
time per execution by scope and the share under a scope; for the LM
cells, the clock offset of the host spans, the device-idle time inside
each call, and the longest idle gaps with the host span open in each.
``--fixture`` saves the window's first execution of each program with
its operations, every execution, the host spans and the op names of
the kept operations, as ``tests/trace_scoped.json`` holds them.
"""

import argparse
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import device as device_lib  # noqa: E402
from perfbench import harness, scopes  # noqa: E402
from perfbench.clock import CompileClock  # noqa: E402


def report(view, programs: dict, names: dict, spans, n_calls: int) -> None:
    for module in programs.values():
        by = scopes.scope_ms(view, module, names[module])
        if not by:
            continue
        total = sum(by.values())
        print(f"{module}: {view.program_ms(module):.4f} ms per execution "
              f"({len(view.modules(module))} executions); leaf ops by "
              "scope, ms: " + ", ".join(
                  f"{k or 'unscoped'} {v:.4f}" for k, v in sorted(
                      by.items(), key=lambda kv: -kv[1]))
              + f"; scoped {100 * (1 - by.get('', 0.0) / total):.2f}%",
              flush=True)
    aligned = scopes.aligned(view, spans or [], n_calls, programs)
    if aligned is None:
        print("host spans: none that pair with the window's executions")
        return
    shift = scopes.device_offset_ns(
        view, scopes.traced_calls(spans, n_calls), programs)
    calls = [s for s in aligned if s.name == scopes.CALL_SPAN]
    idle = scopes.idle_within_ns(view, calls)
    last = max(m.end_ns for m in view.modules())
    fetch = max((s for s in aligned if s.name == "serve.fetch"),
                key=lambda s: s.end_ns)
    print(f"host spans: offset {shift} ns; device idle inside "
          f"{scopes.CALL_SPAN} {idle / len(calls) / 1e6:.4f} ms per call "
          f"({len(calls)} calls); last execution ends "
          f"{(fetch.end_ns - last) / 1e3:.1f} us before serve.fetch ends")
    for name, s in scopes.idle_gaps(view, aligned):
        print(f"idle gap {s * 1e6:.1f} us: {name}")


def fixture(view, programs: dict, names: dict, spans: list) -> dict:
    """The first execution of each program with its operations, every
    execution, the host spans as the program keeps them, and the op
    names of the kept operations."""
    mods = view.modules()
    first = [min(view.modules(m), key=lambda e: e.start_ns)
             for m in programs.values() if view.modules(m)]
    ops = [e for e in view.ops()
           if any(f.plane == e.plane and f.start_ns <= e.start_ns
                  and e.end_ns <= f.end_ns for f in first)]
    kept = {e.name for e in ops}
    return {
        "events": [dataclasses.astuple(e) for e in mods + ops],
        "op_names": {m: {k: v for k, v in names[m].items() if k in kept}
                     for m in programs.values()},
        "spans": [[s.name, s.start_ns, s.end_ns] for s in spans],
    }


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fixture", default="")
    args = ap.parse_args(argv)
    dump = tempfile.mkdtemp(prefix="scope-report-")
    # before JAX starts its backend, which reads the flags once
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + f" --xla_"
                               f"dump_to={dump} --xla_dump_hlo_as_text")

    import jax

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell, _, config, traffic = harness.lookup(bench, args.workload)
    devices = device_lib.require(cell["chips"])
    jax.config.update("jax_enable_compilation_cache", False)
    driver = harness.load_module(
        harness.HERE / "drivers" / f"{config['driver']}.py")
    cell_obj = driver.setup(config, traffic, args.seed, devices)
    n = traffic["trace_calls"]
    _, _, _, view = harness.run_traced(cell_obj, n, CompileClock())
    programs = cell_obj.work()["programs"]
    names = {m: scopes.dumped_op_names(dump, m) for m in programs.values()}
    spans = scopes.program_spans()
    report(view, programs, names, spans, n)
    if args.fixture:
        calls = scopes.traced_calls(spans, n)
        with open(args.fixture, "w") as f:
            json.dump(fixture(view, programs, names, calls), f)
    shutil.rmtree(dump, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
