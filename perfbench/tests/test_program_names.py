"""The program's own names joined to a traced window: device scopes from
the optimized HLO, and the serving loop's host spans on the device
clock, on hand-made traces and on a recorded chip trace."""

import collections
import json
import pathlib

import pytest

from perfbench import harness, scopes, trace
from perfbench.harness import Context
from repro.serve import spans as spans_lib

E = trace.Event
DEV = "/device:TPU:0"
BODY = "jit(decode)/while/body/closed_call/"

HLO = """\
HloModule jit_decode, is_scheduled=true

%fused_computation.4 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(decode)/add"}
  ROOT %mul.2 = f32[8]{0} multiply(%add.1, %add.1), metadata={op_name="{BODY}cim.epilogue/mul" stack_frame_id=3}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="args[0]"}
  %constant.3 = f32[] constant(2)
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.4, metadata={op_name="{BODY}cim.epilogue/mul" stack_frame_id=3}
  ROOT %gpq_matmul.3 = f32[8]{0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="{BODY}cim.macro/pallas/pallas_call"}
}
""".replace("{BODY}", BODY)


def test_hlo_op_names():
    names = scopes.hlo_op_names(HLO)
    assert names["fusion.4"] == BODY + "cim.epilogue/mul"
    assert names["gpq_matmul.3"] == BODY + "cim.macro/pallas/pallas_call"
    assert names["add.1"] == "jit(decode)/add"
    assert "constant.3" not in names  # no metadata
    assert "main.9" not in names


def test_dumped_op_names(tmp_path):
    (tmp_path / "module_0003.jit_decode.tpu_after_optimizations.txt"
     ).write_text(HLO)
    (tmp_path / "module_0003.jit_decode.before_optimizations.txt"
     ).write_text(HLO.replace("cim.macro", "cim.other"))
    (tmp_path / "module_0004.jit_decode_x.after_optimizations.txt"
     ).write_text(HLO.replace("cim.macro", "cim.other"))
    names = scopes.dumped_op_names(str(tmp_path), "jit_decode")
    assert names["gpq_matmul.3"].endswith("cim.macro/pallas/pallas_call")
    # a second compile of the module that names an instruction otherwise
    (tmp_path / "module_0007.jit_decode.after_optimizations.txt"
     ).write_text(HLO.replace("cim.epilogue", "lm.head"))
    names = scopes.dumped_op_names(str(tmp_path), "jit_decode")
    assert "fusion.4" not in names and "gpq_matmul.3" in names


def scoped():
    """One decode execution: a layer loop whose body holds a quantizer
    fusion, a GPQ kernel and an epilogue fusion; an attention op; an
    unscoped op; and a call of another program."""
    return [
        E(DEV, trace.MODULES_LINE, "jit_decode(1)", 10, 200),
        E(DEV, trace.OPS_LINE, "while.1", 10, 150),
        E(DEV, trace.OPS_LINE, "fusion.2", 10, 20),
        E(DEV, trace.OPS_LINE, "gpq_matmul.3", 30, 100),
        E(DEV, trace.OPS_LINE, "fusion.4", 130, 30),
        E(DEV, trace.OPS_LINE, "fusion.5", 160, 25),
        E(DEV, trace.OPS_LINE, "convert.6", 185, 25),
        E(DEV, trace.MODULES_LINE, "jit_other(2)", 1100, 50),
        E(DEV, trace.OPS_LINE, "fusion.2", 1100, 50),
    ]


NAMES = {"while.1": "jit(decode)/while",
         "fusion.2": BODY + "cim.quantize/reduce_max",
         "gpq_matmul.3": BODY + "cim.macro/pallas/pallas_call",
         # the fusion's root is the epilogue's multiply; it also holds
         # the residual add, which is unscoped
         "fusion.4": BODY + "cim.epilogue/mul",
         "fusion.5": "jit(decode)/attn.core/dot_general",
         "convert.6": "jit(decode)/add"}


def test_scope_attribution():
    v = trace.TraceView(scoped(), 2e-6)
    by = scopes.scope_ms(v, "jit_decode", NAMES)
    # leaf ops only: the while loop spanning its body counts nowhere; the
    # other program's op counts toward none of this program's scopes
    assert by == pytest.approx({"cim.quantize": 20e-6, "cim.macro": 100e-6,
                                "cim.epilogue": 30e-6, "attn.core": 25e-6,
                                "": 25e-6})
    assert scopes.scope_ms(v, "jit_prefill", NAMES) == {}
    # an operation that no op name covers is unscoped
    assert scopes.scope_ms(v, "jit_decode", {}) == pytest.approx(
        {"": 200e-6})


def test_scope_is_a_whole_level_of_the_path():
    assert scopes.scope_of("jit(f)/cim.macro2/lm.head/lm.norm") == "lm.head"
    assert scopes.scope_of("jit(f)/mul") == ""
    assert scopes.scope_of("") == ""


def host(name, start, dur):
    return E("host", "spans", name, start, dur)


def call_spans(shift=0):
    """The host spans of one call around ``scoped()``'s execution, on a
    host clock ``shift`` ns ahead of the device's."""
    return [host("serve.generate", shift, 1000),
            host("serve.decode", shift + 5, 95),
            host("serve.fetch", shift + 900, 100)]


def test_device_offset_by_causality():
    v = trace.TraceView(scoped(), 2e-6)
    # the decode execution starts 5 ns after its span on a common clock
    assert scopes.device_offset_ns(v, call_spans(7_000_000),
                                   {"decode": "jit_decode"}) == -6_999_995
    # spans and executions that do not pair one to one
    assert scopes.device_offset_ns(v, call_spans()[:1],
                                   {"decode": "jit_decode"}) is None
    assert scopes.device_offset_ns(v, call_spans(), {}) is None
    got = scopes.aligned(v, call_spans(7_000_000), 1,
                         {"decode": "jit_decode"})
    assert [(s.name, s.start_ns) for s in got] == [
        ("serve.generate", 5), ("serve.decode", 10), ("serve.fetch", 905)]


def test_traced_calls_are_the_last():
    spans = (call_spans() + [host("serve.generate", 2000, 1000),
                             host("serve.decode", 2010, 10)])
    assert [s.start_ns for s in scopes.traced_calls(spans, 1)] == [2000,
                                                                    2010]
    assert len(scopes.traced_calls(spans, 2)) == 5
    assert scopes.traced_calls(spans, 3) is None


def test_idle_inside_and_outside_the_call_span():
    ev = [E(DEV, trace.OPS_LINE, "a", 0, 50),
          E(DEV, trace.OPS_LINE, "b", 120, 30),
          E(DEV, trace.OPS_LINE, "c", 250, 10)]
    v = trace.TraceView(ev, 1e-6)
    call = [host("serve.generate", 100, 100)]
    # 100..200 holds b (120..150): 70 ns idle; the gap after b, to c,
    # opens inside the call, the one before b outside it
    assert scopes.idle_within_ns(v, call) == 70
    assert dict(scopes.idle_gaps(v, call)) == pytest.approx({
        "after a / before b / host: outside the program": 70e-9,
        "after b / before c / host: serve.generate": 100e-9})
    nested = sorted(call + [host("serve.fetch", 140, 60)],
                    key=lambda s: s.start_ns)
    assert scopes.span_at(nested, 150).name == "serve.fetch"
    assert scopes.span_at(nested, 120).name == "serve.generate"
    assert scopes.span_at(nested, 50) is None


def _ctx(view, calls=1):
    return Context(cell="c", config={}, traffic={}, setup_s=1.0, peaks={},
                   work={"programs": {"decode": "jit_decode",
                                      "prefill": "jit_prefill"}},
                   calls=calls, trace=view)


def _keep(monkeypatch, spans):
    kept = collections.deque(
        spans_lib.Span(s.name, s.start_ns, s.end_ns, {}) for s in spans)
    monkeypatch.setattr(spans_lib, "RECENT", kept)


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read


@pytest.mark.parametrize("name", ["host_idle_ms.decode",
                                  "host_idle_ms.prefill"])
def test_host_idle_reader(monkeypatch, name):
    v = trace.TraceView(scoped(), 2e-6)
    read = _reader(name)
    _keep(monkeypatch, call_spans(123_456))
    # busy 10..210 of the 1000 ns call; the other program's op at 1100
    # lies after the call: the caller's
    assert read(_ctx(v)) == pytest.approx(800 / 1e6)
    # no trace, no device plane, no kept spans, or a program that keeps
    # none: nothing to read
    assert read(_ctx(None)) is None
    host_only = trace.TraceView([], 1e-6)
    assert read(_ctx(host_only)) is None
    _keep(monkeypatch, [])
    assert read(_ctx(v)) is None
    monkeypatch.setattr(scopes, "program_spans", lambda: None)
    assert read(_ctx(v)) is None


def test_generate_keeps_its_spans():
    """``ServeEngine.generate`` keeps one span per call, prefill, decode
    step and fetch, nested in the call's (CPU, tiny model)."""
    import jax

    from perfbench.drivers import lm_generate
    from perfbench.tests import tiny

    tr = tiny.lm_traffic("decode")
    cell = lm_generate.setup(tiny.lm_config(), tr, 3, jax.devices()[:1])
    cell.call(0)
    got = scopes.traced_calls(scopes.program_spans(), 1)
    steps = tr["new_tokens"] - 1
    assert [s.name for s in got] == (["serve.generate", "serve.prefill"]
                                     + ["serve.decode"] * steps
                                     + ["serve.fetch"])
    call = got[0]
    assert all(call.start_ns <= s.start_ns and s.end_ns <= call.end_ns
               for s in got[1:])
    last = spans_lib.RECENT[-1]
    assert last.name == "serve.generate" and last.attrs == {
        "batch": tr["batch"], "prompt_len": tr["prompt_len"],
        "n_tokens": tr["new_tokens"]}


@pytest.mark.parametrize("kind", ["decode", "prefill", "resnet"])
def test_traced_cpu_run_leaves_host_idle_out(kind):
    """A --trace 1 run on the CPU reads its spans but finds no device
    plane to join them with, so no host-idle metric is in the line."""
    from perfbench.tests import tiny

    name, config, traffic = {
        "decode": ("qwen2-0.5b.decode", tiny.lm_config,
                   lambda: tiny.lm_traffic("decode")),
        "prefill": ("qwen2-0.5b.prefill", tiny.lm_config,
                    lambda: tiny.lm_traffic("prefill")),
        "resnet": ("resnet20.paper", tiny.resnet_config,
                   tiny.resnet_traffic)}[kind]
    result, _ = tiny.run(name, config(), traffic(), trace=1)
    assert result["correct"], result["checks"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not [m for m in result["metrics"] if m.startswith("host_idle")]


# The 13 per-layer metrics of the first chip benchmark on its recorded
# decode trace, as that benchmark read them: what this PR adds beside
# the reduction leaves every one as it was.
BEFORE = {"idle_share.decode": 54.47314750000001,
          "idle_share.resnet": 54.47314750000001,
          "idle_share.prefill": 54.47314750000001,
          "decode_step_ms": 36.358859, "resnet_forward_ms": None,
          "prefill_step_ms": None, "mfu.decode": 6.345177664974619,
          "mfu.resnet": 6.345177664974619, "mfu.prefill": 6.345177664974619,
          "gpq_ms.decode": 33.347191, "gpq_ms.prefill": None,
          "gpq_roofline.decode": 0.05502556378280814,
          "gpq_roofline.prefill": None}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_existing_metrics_read_as_before(name):
    path = pathlib.Path(__file__).with_name("trace_decode.json")
    v = trace.TraceView(trace.read_events(str(path)), 0.08)
    ctx = Context(
        cell="c", config={}, traffic={}, setup_s=1.0,
        peaks={"bf16_flops": 197e12, "int8_ops": 393e12,
               "hbm_bytes_per_s": 819e9},
        work={"flops_per_call": 1e12, "weight_bits": 8,
              "programs": {"decode": "jit_decode", "prefill": "jit_prefill",
                           "forward": "jit_resnet_forward"},
              "gpq_shapes": {"decode": [(32, 896, 4864)] * 3,
                             "prefill": [(4096, 896, 896)]}},
        calls=1, trace=v)
    got = _reader(name)(ctx)
    if BEFORE[name] is None:
        assert got is None
    else:
        assert got == pytest.approx(BEFORE[name])


FIXTURE = pathlib.Path(__file__).with_name("trace_scoped.json")


def recorded():
    """A call of qwen2-0.5b.decode as a TPU v5 lite traced it
    (``scope_report.py --fixture``): every program execution, the
    operations of the first prefill and the first decode step, their op
    names from the optimized HLO, and the call's host spans as the
    program keeps them, on its own clock."""
    d = json.loads(FIXTURE.read_text())
    view = trace.TraceView([E(*row) for row in d["events"]], 1.0)
    spans = [host(n, s, e - s) for n, s, e in d["spans"]]
    return view, d["op_names"], spans


PROGRAMS = {"prefill": "jit_prefill", "decode": "jit_decode"}


def test_recorded_spans_share_the_device_clock():
    view, _, spans = recorded()
    got = scopes.aligned(view, spans, 1, PROGRAMS)
    assert got is not None
    for phase, module in PROGRAMS.items():
        ss = [s for s in got if s.name == f"serve.{phase}"]
        mods = sorted(view.modules(module), key=lambda m: m.start_ns)
        assert len(ss) == len(mods) > 0
        # each execution starts after the span that dispatched it
        assert all(s.start_ns <= m.start_ns for s, m in zip(ss, mods))
    (call,) = [s for s in got if s.name == scopes.CALL_SPAN]
    (fetch,) = [s for s in got if s.name == "serve.fetch"]
    mods = view.modules()
    # the call opens before its first execution; the fetch waits for
    # the last one
    assert call.start_ns < min(m.start_ns for m in mods)
    assert max(m.end_ns for m in mods) < fetch.end_ns <= call.end_ns


@pytest.mark.parametrize("module", ["jit_decode", "jit_prefill"])
def test_recorded_scopes_cover_the_programs(module):
    view, names, _ = recorded()
    first = min(view.modules(module), key=lambda m: m.start_ns)
    one = trace.TraceView([e for e in view.events if e.line == trace.OPS_LINE
                           or e == first], 1.0)
    by = scopes.scope_ms(one, module, names[module])
    total = sum(by.values())
    assert by["cim.macro"] > 0.9 * total  # the GPQ kernels
    assert by.get("", 0.0) < 0.1 * total
    assert {"cim.quantize", "cim.epilogue", "attn.core", "lm.norm",
            "lm.head"} <= set(by)


def test_recorded_host_idle(monkeypatch):
    view, _, spans = recorded()
    _keep(monkeypatch, spans)
    got = _reader("host_idle_ms.decode")(_ctx(view))
    (call,) = [s for s in spans if s.name == scopes.CALL_SPAN]
    assert 0 < got < call.dur_ns / 1e6
