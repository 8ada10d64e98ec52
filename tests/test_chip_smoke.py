"""``chip_smoke.py`` keeps one compile accounting, the benchmark's."""

import importlib.util
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_timed_uses_the_benchmark_clock(capsys):
    import sys

    sys.path.insert(0, str(ROOT))
    try:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", ROOT / "chip_smoke.py")
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from perfbench.clock import CompileClock
    finally:
        sys.path.remove(str(ROOT))
    assert smoke.CompileClock is CompileClock
    clock = CompileClock()
    with smoke.timed("step", clock):
        clock("/jax/core/compile/backend_compile_duration", 0.25)
    out = capsys.readouterr().out
    assert "step: wall" in out and "of which compile 0.250 s" in out
    assert clock.compiles == 1
