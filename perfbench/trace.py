"""Reduction of a JAX profiler trace to device busy time, program and
kernel times.

A trace is read once into flat :class:`Event` records of the device
planes (``/device:...``); everything after that is pure functions over
those records, so the tests check the reduction on a small recorded
trace without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os

# Lines of a TPU device plane: one event per XLA operation, and one per
# execution of a compiled program (module).
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load_device_events(xplane_path: str) -> list[Event]:
    """Every event of every device plane of the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, short_name(ev.name),
                                 int(ev.start_ns), int(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """An operation's name without its HLO text: TPU traces name an op
    event ``%gpq_matmul.47 = f32[128,4864]{...} custom-call(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def save_events(events: list[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def read_events(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class TraceView:
    """Device events of one traced window, with the host-clock length
    of that window."""

    def __init__(self, events: list[Event], window_s: float):
        self.events = events
        self.window_s = window_s
        self.planes = sorted({e.plane for e in events
                              if e.line == OPS_LINE})

    def ops(self, plane: str | None = None) -> list[Event]:
        return [e for e in self.events if e.line == OPS_LINE
                and (plane is None or e.plane == plane)]

    def modules(self, prefix: str = "") -> list[Event]:
        return [e for e in self.events if e.line == MODULES_LINE
                and e.name.startswith(prefix)]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.planes:
            return 0.0
        per = [union_ns((e.start_ns, e.end_ns) for e in self.ops(p))
               for p in self.planes]
        return sum(per) / len(per) / 1e9

    def leaf_ops(self) -> list[Event]:
        """Operations that hold no other operation: a loop such as the
        layer scan's ``while`` spans its body's operations on the same
        line, and counting both would count the time twice."""
        out = []
        for p in self.planes:
            ops = sorted(self.ops(p), key=lambda e: (e.start_ns, -e.dur_ns))
            for i, e in enumerate(ops):
                nxt = ops[i + 1] if i + 1 < len(ops) else None
                if nxt is None or not (nxt.start_ns < e.end_ns
                                       and nxt.end_ns <= e.end_ns):
                    out.append(e)
        return out

    def ops_within(self, module_prefix: str) -> list[Event]:
        """Operations that ran inside an execution of a module whose
        name starts with ``module_prefix`` (same plane, by time)."""
        spans: dict[str, list[tuple[int, int]]] = {}
        for m in self.modules(module_prefix):
            spans.setdefault(m.plane, []).append((m.start_ns, m.end_ns))
        out = []
        for e in self.ops():
            for s, t in spans.get(e.plane, ()):
                if s <= e.start_ns and e.end_ns <= t:
                    out.append(e)
                    break
        return out

    def program_ms(self, prefix: str) -> float | None:
        """Mean device time of one execution of the module(s) named
        ``prefix``; None when the window ran none."""
        mods = self.modules(prefix)
        if not mods:
            return None
        return sum(m.dur_ns for m in mods) / len(mods) / 1e6

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most time, and the longest idle
        gaps, each named by the operations around it."""
        by_name: dict[str, int] = {}
        for e in self.leaf_ops():
            by_name[e.name] = by_name.get(e.name, 0) + e.dur_ns
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for p in self.planes:
            ops = sorted(self.ops(p), key=lambda e: e.start_ns)
            end, prev = None, None
            for e in ops:
                if end is not None and e.start_ns > end:
                    gaps.append((f"after {prev} / before {e.name}",
                                 e.start_ns - end))
                if end is None or e.end_ns > end:
                    end, prev = e.end_ns, e.name
        gaps.sort(key=lambda g: -g[1])
        return {
            "device_ops": [[n, ns / 1e9] for n, ns in device_ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps[:top]],
        }


def kernel_events(ops: list[Event], names) -> list[Event]:
    """Operations whose name contains one of ``names``."""
    return [e for e in ops if any(n in e.name for n in names)]
