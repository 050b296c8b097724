"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Planes named ``/device:TPU:<n>`` are the chips.  On each, the line ``XLA
Ops`` holds one event per operation run, named by its HLO instruction
(``%fusion.12 = bf16[...] fusion(...)``); a ``while`` or ``conditional``
event encloses the events of its body, so events nest.  ``XLA Modules``
holds one event per run of a jitted program.  Host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines, on
the same clock (as recorded on a v5e).

From these it keeps, for the host span that bounds the window:

- the device's busy time: the union of its operations' intervals inside the
  window, averaged over the chips;
- device self time (an event's time less that of the events it encloses)
  by instruction (``fusion.12``), by kind (``fusion``) and by class
  (``CLASSES``, matched on the name), and device time by jitted program;
- the idle gaps of the first chip inside the window, cut at the edges of the
  harness's host spans (``chipbench.*``), each piece named by the innermost
  span open over it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
#: operation classes, by the start of the HLO name
CLASSES = {"collective-permute": ("collective-permute",),
           "all-reduce": ("all-reduce",),
           "all-gather": ("all-gather",),
           "reduce-scatter": ("reduce-scatter",)}
_SUFFIX = re.compile(r"(\.\d+)+$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[8] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``collective-permute-start.3`` ->
    ``collective-permute-start``."""
    return _SUFFIX.sub("", op_name(name))


def op_class(name: str) -> str:
    name = op_name(name)
    for cls, prefixes in CLASSES.items():
        if name.startswith(prefixes):
            return cls
    return "other"


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float                            # averaged over the chips
    op_s: dict[str, float]                   # self time by instruction, averaged
    kind_s: dict[str, float]                 # self time by op kind, averaged
    class_s: dict[str, float]                # self time by op class, averaged
    module_s: dict[str, float]               # by jitted program, averaged
    module_runs: dict[str, float]            # runs per chip
    gaps: list[tuple[str, float]]            # idle gaps of chip 0, longest first

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir: Path) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins/profile/*/*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def _self_times(events, w0: float, w1: float):
    """(name, start, end, self time) of each event inside [w0, w1], clipped
    to it; an event's self time leaves out the events it encloses."""
    out = []
    stack: list[list] = []           # [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and stack[-1][0] <= s:
            stack.pop()
        cs, ce = max(s, w0), min(e, w1)
        inside = max(ce - cs, 0.0)
        if stack:
            out[stack[-1][1]][3] -= inside
        out.append([name, cs, ce, inside])
        stack.append([e, len(out) - 1])
    return [o for o in out if o[2] > o[1]]


def reduce(path, *, window_span: str) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return reduce_profile(data, window_span=window_span)


def reduce_profile(data, *, window_span: str) -> Summary:
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        spans.append((name, s, s + d))
    windows = [(s, e) for n, s, e in spans if n == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, w1 = windows[0]
    if not devices:
        raise ValueError("no TPU device plane in the trace")

    busy, op_s, kind_s, class_s = 0.0, defaultdict(float), defaultdict(float), defaultdict(float)
    module_s, module_runs = defaultdict(float), defaultdict(float)
    gaps: list[tuple[str, float]] = []
    inner = sorted((s, e, n) for n, s, e in spans if n != window_span)
    for i, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        ops = _self_times(_events(lines[OPS_LINE]) if OPS_LINE in lines else (), w0, w1)
        intervals = [(s, e) for _, s, e, _ in ops]
        for name, _, _, own in ops:
            op_s[op_name(name)] += own * 1e-9
            kind_s[op_kind(name)] += own * 1e-9
            class_s[op_class(name)] += own * 1e-9
        for name, s, d in _events(lines[MODULES_LINE]) if MODULES_LINE in lines else ():
            if w0 <= s + d / 2 <= w1:
                module_s[name] += d * 1e-9
                module_runs[name] += 1
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            cuts = sorted({t for s, e, _ in inner for t in (s, e)})
            for a, b in zip(edges[::2], edges[1::2]):
                # a gap that spans several host spans is split at their edges
                points = [a] + [t for t in cuts[bisect.bisect_right(cuts, a):
                                                bisect.bisect_left(cuts, b)]] + [b]
                for x, y in zip(points, points[1:]):
                    if y > x:
                        gaps.append((_span_at(inner, (x + y) / 2), (y - x) * 1e-9))
    n = len(devices)
    avg = lambda d: {k: v / n for k, v in d.items()}
    gaps.sort(key=lambda g: -g[1])
    return Summary(chips=n, window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                   op_s=avg(op_s), kind_s=avg(kind_s), class_s=avg(class_s),
                   module_s=avg(module_s),
                   module_runs=avg(module_runs), gaps=gaps)


def _span_at(spans: list[tuple[float, float, str]], t: float) -> str:
    """The innermost (latest-starting) span open at ``t``."""
    i = bisect.bisect_right(spans, (t, float("inf"), ""))
    for s, e, name in reversed(spans[max(0, i - 64):i]):
        if s <= t <= e:
            return name
    return "no harness span"
