"""The reduction from trace events to busy time, program and kernel
times, on a small hand-made trace and a recorded chip trace."""

import pathlib

import pytest

from perfbench import readers, trace
from perfbench.harness import Context

E = trace.Event
DEV = "/device:TPU:0"


def small():
    return [
        E(DEV, trace.MODULES_LINE, "jit_decode(1)", 0, 100),
        E(DEV, trace.OPS_LINE, "fusion.1", 0, 30),
        E(DEV, trace.OPS_LINE, "gpq_matmul.4", 20, 50),  # overlaps fusion.1
        E(DEV, trace.OPS_LINE, "copy.2", 90, 10),
        E(DEV, trace.MODULES_LINE, "jit_decode(1)", 200, 100),
        E(DEV, trace.OPS_LINE, "gpq_matmul.4", 210, 40),
        E(DEV, trace.MODULES_LINE, "jit_prefill(2)", 400, 50),
        E(DEV, trace.OPS_LINE, "gpq_matmul.9", 400, 50),
        E("/host:CPU", "python", "ignored", 0, 1000),
    ]


def test_union():
    assert trace.union_ns([(0, 30), (20, 70), (90, 100)]) == 80
    assert trace.union_ns([]) == 0


def test_busy_programs_and_kernels():
    v = trace.TraceView([e for e in small() if e.plane == DEV], 1e-6)
    assert v.busy_s == pytest.approx((80 + 40 + 50) / 1e9)
    assert v.program_ms("jit_decode") == pytest.approx(100 / 1e6)
    assert v.program_ms("jit_prefill") == pytest.approx(50 / 1e6)
    assert v.program_ms("jit_none") is None
    inside = v.ops_within("jit_decode")
    assert [e.name for e in inside] == ["fusion.1", "gpq_matmul.4",
                                        "copy.2", "gpq_matmul.4"]
    gpq = trace.kernel_events(inside, readers.GPQ_KERNELS)
    assert sum(e.dur_ns for e in gpq) == 90
    b = v.breakdown()
    assert b["device_ops"][0] == ["gpq_matmul.4", 90 / 1e9]
    assert b["idle_gaps"][0][1] == pytest.approx(150 / 1e9)  # 250 -> 400


def test_readers_on_small_trace():
    v = trace.TraceView(small(), 1000 / 1e9)
    ctx = Context(cell="c", config={}, traffic={}, setup_s=1.0,
                  peaks={"bf16_flops": 1e12, "int8_ops": 1e12,
                         "hbm_bytes_per_s": 1e12},
                  work={"flops_per_call": 100.0, "weight_bits": 8,
                        "programs": {"decode": "jit_decode",
                                     "prefill": "jit_prefill"},
                        "gpq_shapes": {"decode": [(1, 10, 10)]}},
                  calls=2, trace=v)
    assert readers.idle_share(ctx) == pytest.approx(100 * (1 - 170 / 1000))
    assert readers.mfu(ctx) == pytest.approx(100 * 200 / 1e-6 / 1e12)
    assert readers.gpq_ms(ctx, "decode") == pytest.approx(45 / 1e6)
    # ops 2*1*10*10*8 = 1600 -> 1.6 ns at 1e12; bytes 10+100+40 -> 0.15 ns
    assert readers.gpq_roofline(ctx, "decode") == pytest.approx(
        100 * 2 * 1.6 / 90)
    assert readers.gpq_roofline(ctx, "prefill") is None  # no shapes
    assert readers.rate(ctx) is None  # traced runs report no rate


def test_loops_are_not_counted_twice():
    v = trace.TraceView([E(DEV, trace.OPS_LINE, "while.2", 0, 100),
                         E(DEV, trace.OPS_LINE, "fusion.3", 10, 20),
                         E(DEV, trace.OPS_LINE, "gpq_matmul.1", 40, 50)], 1e-6)
    assert [e.name for e in v.leaf_ops()] == ["fusion.3", "gpq_matmul.1"]
    assert v.busy_s == pytest.approx(100 / 1e9)
    assert [n for n, _ in v.breakdown()["device_ops"]] == ["gpq_matmul.1",
                                                          "fusion.3"]


def test_short_names():
    assert trace.short_name("%gpq_matmul.47 = f32[128,4864]{1,0} custom-call("
                            "s32[128,896] %x)") == "gpq_matmul.47"
    assert trace.short_name("jit_decode(4107)") == "jit_decode(4107)"


def test_recorded_chip_trace():
    """Two decode steps of qwen2-0.5b.decode as a TPU v5 lite traced
    them (ops shortened by ``short_name``)."""
    path = pathlib.Path(__file__).with_name("trace_decode.json")
    events = trace.read_events(str(path))
    v = trace.TraceView(events, 1.0)
    assert v.planes == [DEV] or v.planes
    assert v.modules("jit_decode"), "the recorded trace holds decode steps"
    gpq = trace.kernel_events(v.ops_within("jit_decode"), readers.GPQ_KERNELS)
    assert gpq, "GPQ kernels are found by name inside the decode steps"
    assert 0 < v.busy_s < 1.0
