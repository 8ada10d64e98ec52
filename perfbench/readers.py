"""Arithmetic shared by the metric files under ``metrics/``.

Each function takes the harness's ``Context`` and returns a number, or
None where the run has nothing to read (no trace, no such program or
kernel in it). A share of a peak or a roofline is never reported as 0
for lack of data.
"""

from __future__ import annotations

from perfbench import trace as trace_lib
from perfbench import work as work_lib

# The GPQ Pallas kernels of the program, by the names they carry into a
# TPU trace: the jitted wrappers ``gpq_matmul``, ``adder_tree_gpq_matmul``
# and ``cell_adc_gpq_matmul`` name their custom calls (``gpq_matmul.47``).
GPQ_KERNELS = ("gpq_matmul",)


def rate(ctx):
    """Work units completed per second over the whole window."""
    if ctx.trace is not None or ctx.elapsed_s <= 0:
        return None
    return ctx.units / ctx.elapsed_s


def idle_share(ctx):
    """Percent of the traced window in which no device operation ran."""
    t = ctx.trace
    if t is None or not t.planes or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def program_ms(ctx, program: str):
    """Mean device time of one execution of a jitted program."""
    if ctx.trace is None:
        return None
    return ctx.trace.program_ms(ctx.work["programs"][program])


def mfu(ctx):
    """Nominal model FLOPs of the traced calls over the traced window,
    as a percent of the chip's bf16 peak."""
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    flops = ctx.work["flops_per_call"] * ctx.calls
    return 100.0 * flops / t.window_s / ctx.peaks["bf16_flops"]


def _gpq(ctx, phase: str):
    """(kernel events, executions) of the GPQ kernels inside the
    executions of the ``phase`` program of the traced window."""
    t = ctx.trace
    if t is None:
        return None, 0
    prog = ctx.work["programs"][phase]
    runs = len(t.modules(prog))
    ev = trace_lib.kernel_events(t.ops_within(prog), GPQ_KERNELS)
    return (ev, runs) if ev and runs else (None, 0)


def gpq_ms(ctx, phase: str):
    """GPQ kernel device time per execution of the phase's program."""
    ev, runs = _gpq(ctx, phase)
    if ev is None:
        return None
    return sum(e.dur_ns for e in ev) / runs / 1e6


def gpq_roofline(ctx, phase: str):
    """Least time the chip needs for the phase's GPQ matmuls (unpadded
    shapes of the projections routed to Pallas), as a percent of the
    GPQ kernels' measured time."""
    ev, runs = _gpq(ctx, phase)
    shapes = ctx.work.get("gpq_shapes", {}).get(phase)
    if ev is None or not shapes:
        return None
    bits = ctx.work["weight_bits"]
    least = sum(work_lib.roofline_seconds(*work_lib.gpq_work(m, k, n, bits),
                                          ctx.peaks)[0]
                for m, k, n in shapes) * runs
    return 100.0 * least / (sum(e.dur_ns for e in ev) / 1e9)
