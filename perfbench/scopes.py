"""The program's own names for its work, joined to a traced window.

``trace.TraceView`` holds each device operation under its HLO
instruction name (``fusion.79``, ``gpq_matmul.47``) and no host event.
The program names its work in two ways, and this module joins both to
that view:

* Device scopes (``jax.named_scope``) reach each instruction's
  ``op_name`` in the optimized HLO of the programs that ran; the view
  keeps no metadata, so the names come from that HLO's text (an XLA
  dump, ``scope_report.py``). A fusion carries its root operation's
  ``op_name``, so it counts toward its root's scope. Only leaf
  operations count: a ``while`` holds its body's.
* Host spans (``repro.serve.spans.RECENT``): the wall-clock times of
  the serving loop's steps, put on the device clock by causality
  (``device_offset_ns``). ``host_idle_ms`` reads them in every traced
  run of the LM cells.

Where the program keeps no spans, as a source before them, the reader
returns None and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from perfbench import trace as trace_lib

# The program's device scopes: the macro engine's three stages, the
# attention core and cache writes, the LM's norms and head, ResNet's
# im2col, BatchNorm and pooling.
SCOPES = ("cim.quantize", "cim.macro", "cim.epilogue", "attn.core",
          "attn.cache", "lm.norm", "lm.head", "resnet.im2col", "resnet.bn",
          "resnet.pool")

# The span of one call of the serving entry point; the span around the
# dispatch of each phase's program is ``serve.<phase>``.
CALL_SPAN = "serve.generate"

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"')


def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name -> ``op_name``, from an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def dumped_op_names(dump_dir: str, module: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of the module named ``module`` in
    an XLA dump (``--xla_dump_to`` with ``--xla_dump_hlo_as_text``): its
    optimized HLO, ``module_NNNN.<module>.*after_optimizations.txt``.
    An instruction that two dumped compiles name differently is left
    out."""
    out: dict[str, str] = {}
    clash: set[str] = set()
    for path in sorted(glob.glob(os.path.join(
            dump_dir, f"module_*.{module}.*after_optimizations.txt"))):
        with open(path) as f:
            for name, op in hlo_op_names(f.read()).items():
                if out.setdefault(name, op) != op:
                    clash.add(name)
    for name in clash:
        del out[name]
    return out


def scope_of(op_name: str, scopes=SCOPES) -> str:
    """The outermost level of an ``op_name`` path that is one of
    ``scopes``, or ""."""
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return ""


def leaf_ops_within(view, module: str) -> list:
    """Leaf operations inside an execution of a module whose name
    starts with ``module`` (same plane, by time; a device runs one
    module at a time)."""
    starts: dict[str, list[int]] = {}
    ends: dict[str, list[int]] = {}
    for m in sorted(view.modules(module), key=lambda m: m.start_ns):
        starts.setdefault(m.plane, []).append(m.start_ns)
        ends.setdefault(m.plane, []).append(m.end_ns)
    out = []
    for e in view.leaf_ops():
        i = bisect.bisect_right(starts.get(e.plane, ()), e.start_ns) - 1
        if i >= 0 and e.end_ns <= ends[e.plane][i]:
            out.append(e)
    return out


def scope_ms(view, module: str, names: dict[str, str],
             scopes=SCOPES) -> dict[str, float]:
    """Device time per execution of the module(s) named ``module`` in
    leaf operations, by scope ("" for none of ``scopes``); ``names``
    maps instruction names to ``op_name``s."""
    runs = len(view.modules(module))
    out: dict[str, float] = {}
    if not runs:
        return out
    for e in leaf_ops_within(view, module):
        k = scope_of(names.get(e.name, ""), scopes)
        out[k] = out.get(k, 0.0) + e.dur_ns / runs / 1e6
    return out


def program_spans() -> list | None:
    """The program's recent host spans, oldest first, as events; None
    for a program that keeps none."""
    try:
        from repro.serve import spans
    except ImportError:
        return None
    return sorted((trace_lib.Event("host", "spans", s.name, s.start_ns,
                                   s.end_ns - s.start_ns)
                   for s in spans.RECENT),
                  key=lambda s: (s.start_ns, -s.dur_ns))


def traced_calls(spans: list, n_calls: int) -> list | None:
    """The spans of the last ``n_calls`` calls, the traced window's (the
    harness reads its metrics right after the window); None where fewer
    were kept."""
    calls = [s for s in spans if s.name == CALL_SPAN][-n_calls:]
    if not calls or len(calls) < n_calls:
        return None
    lo, hi = calls[0].start_ns, calls[-1].end_ns
    return [s for s in spans if lo <= s.start_ns and s.end_ns <= hi]


def device_offset_ns(view, spans: list, programs: dict) -> int | None:
    """What to add to a span's host time to put it on the device clock.

    The trace's device times do not count from the wall clock's origin
    (on a v5e they lay 1.79e18 ns behind it), so the spans are moved by
    causality: the k-th execution of a phase's program on a chip cannot
    start before the k-th ``serve.<phase>`` span, which dispatched it,
    starts. The offset is the least such lead, which puts the tightest
    execution at its dispatch: late by that execution's true latency,
    tens of microseconds. None where the spans and executions of a
    phase do not pair one to one, or nothing pairs."""
    if not view.planes:
        return None
    plane = view.planes[0]
    leads = []
    for phase, module in programs.items():
        ss = [s for s in spans if s.name == f"serve.{phase}"]
        ms = sorted((m for m in view.modules(module) if m.plane == plane),
                    key=lambda m: m.start_ns)
        if len(ss) != len(ms):
            return None
        leads += [m.start_ns - s.start_ns for s, m in zip(ss, ms)]
    return min(leads) if leads else None


def aligned(view, spans: list, n_calls: int, programs: dict) -> list | None:
    """The host spans of the last ``n_calls`` calls on the device clock,
    or None where they do not pair with the view's executions."""
    spans = traced_calls(spans, n_calls)
    shift = None if spans is None else device_offset_ns(view, spans,
                                                        programs)
    if shift is None:
        return None
    return [trace_lib.Event(s.plane, s.line, s.name, s.start_ns + shift,
                            s.dur_ns) for s in spans]


def aligned_spans(ctx) -> list | None:
    """The traced calls' host spans on the device clock, or None."""
    spans = program_spans() if ctx.trace is not None else None
    if not spans:
        return None
    return aligned(ctx.trace, spans, ctx.calls, ctx.work["programs"])


def idle_within_ns(view, spans: list) -> float:
    """Device-idle time inside ``spans``, averaged over the chips."""
    idle = 0
    for p in view.planes:
        ops = [(e.start_ns, e.end_ns) for e in view.ops(p)]
        for s in spans:
            inside = [(max(a, s.start_ns), min(b, s.end_ns))
                      for a, b in ops if a < s.end_ns and b > s.start_ns]
            idle += s.dur_ns - trace_lib.union_ns(inside)
    return idle / len(view.planes)


def host_idle_ms(ctx):
    """Device-idle time per call inside the program's call spans: idle
    that the program's own host code leaves, as against the caller's;
    None without such spans or device operations."""
    spans = aligned_spans(ctx)
    if spans is None:
        return None
    calls = [s for s in spans if s.name == CALL_SPAN]
    return idle_within_ns(ctx.trace, calls) / len(calls) / 1e6


def span_at(spans: list, t_ns: int):
    """The innermost of ``spans`` (sorted by start) open at ``t_ns``, or
    None."""
    hit = None
    for s in spans:
        if s.start_ns > t_ns:
            break
        if t_ns < s.end_ns:
            hit = s
    return hit


def idle_gaps(view, spans: list, top: int = 10) -> list:
    """The longest device-idle gaps, each named as ``breakdown()`` names
    it and by the program's span open on the host where it starts
    (``... / host: serve.fetch``, or ``host: outside the program``)."""
    gaps = []
    for p in view.planes:
        end, prev = None, None
        for e in sorted(view.ops(p), key=lambda e: e.start_ns):
            if end is not None and e.start_ns > end:
                gaps.append((f"after {prev} / before {e.name}", end,
                             e.start_ns - end))
            if end is None or e.end_ns > end:
                end, prev = e.end_ns, e.name
    gaps.sort(key=lambda g: -g[2])
    out = []
    for name, at, ns in gaps[:top]:
        s = span_at(spans, at)
        out.append([f"{name} / host: "
                    f"{s.name if s else 'outside the program'}", ns / 1e9])
    return out
