"""The program's own host spans in a traced run's profiler trace.

The program wraps its host work in ``jax.profiler.TraceAnnotation`` spans
named ``r2ccl.<layer>.<what>``, with numeric arguments such as a batch's
number or a count of reads (``src/repro/core/spans.py``).  They sit on the
host plane beside the harness's ``chipbench.*`` spans, on the clock of the
device's operations.  ``trace.py`` reduces the trace for the harness's own
metrics; this module reads the program's spans beside it, inside the same
window span:

- self time by span name: each span's time inside the window less the time
  of the program spans it encloses (spans of one thread nest);
- the count of each span that overlaps the window;
- the sums of each span's numeric arguments;
- on request, the idle gaps of the first chip, cut at the edges of every
  harness and program span, each piece named by the innermost span open
  over it.

A program that has no such spans gives empty tables, and its readers
report nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from collections import defaultdict
from pathlib import Path

from chipbench import trace

PREFIX = "r2ccl."
WINDOW = "chipbench.window"


@dataclasses.dataclass
class Spans:
    window_s: float
    self_s: dict[str, float]                 # by span name
    count: dict[str, int]
    args: dict[str, dict[str, float]]        # span name -> argument -> sum
    gaps: list[tuple[str, float]] | None     # longest first, if asked for

    def per(self, name: str, unit: str) -> float | None:
        """Milliseconds of ``name``'s self time per span ``unit``."""
        n = self.count.get(unit)
        if name not in self.self_s or not n:
            return None
        return 1e3 * self.self_s[name] / n


def _host_spans(data, prefixes: tuple[str, ...]):
    """(name, start, end, numeric arguments) of every host span whose name
    starts with one of ``prefixes``, by host line."""
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                      {k: v for k, v in e.stats if isinstance(v, (int, float))})
                     for e in line.events if e.name.startswith(prefixes)]
            if found:
                lines.append(found)
    return lines


def reduce_profile(data, *, window_span: str = WINDOW, gaps: bool = False) -> Spans:
    lines = _host_spans(data, (PREFIX, trace.SPAN_PREFIX))
    windows = [(s, e) for line in lines for n, s, e, _ in line if n == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, w1 = windows[0]
    self_s, count = defaultdict(float), defaultdict(int)
    args: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for line in lines:
        ours = [(n, s, e - s) for n, s, e, _ in line if n.startswith(PREFIX)]
        for name, _, _, own in trace._self_times(ours, w0, w1):
            self_s[name] += own * 1e-9
            count[name] += 1
        for name, s, e, a in line:
            if name.startswith(PREFIX) and s < w1 and e > w0:
                for k, v in a.items():
                    args[name][k] += v
    named = None
    if gaps:
        inner = [(s, e, n) for line in lines for n, s, e, _ in line if n != window_span]
        named = _named_gaps(data, w0, w1, inner)
    return Spans(window_s=(w1 - w0) * 1e-9, self_s=dict(self_s), count=dict(count),
                 args={k: dict(v) for k, v in args.items()}, gaps=named)


def _named_gaps(data, w0: float, w1: float, spans: list[tuple[float, float, str]]):
    """The first chip's idle gaps inside [w0, w1], cut at every span's edges,
    each piece named by the innermost span open over it."""
    plane = next((p for p in data.planes if trace.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        raise ValueError("no TPU device plane in the trace")
    ops = next((line for line in plane.lines if line.name == trace.OPS_LINE), None)
    busy = trace._union([(s, s + d) for _, s, d in (trace._events(ops) if ops else ())
                         if s < w1 and s + d > w0])
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    starts = [s for s, _, _ in spans]
    parent, stack = [], []                   # enclosing span of each, by index
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)

    def name_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and spans[i][1] < t:
            i = parent[i]
        return spans[i][2] if i >= 0 else "no span"

    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    edges = [w0] + [min(max(x, w0), w1) for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        points = [a] + cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)] + [b]
        for x, y in zip(points, points[1:]):
            if y > x:
                out.append((name_at((x + y) / 2), (y - x) * 1e-9))
    out.sort(key=lambda g: -g[1])
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def trace_dir(cell) -> Path:
    """Where ``run.py`` has a run of ``cell`` write its trace."""
    return cell.bench_dir / ".out" / cell.name / "trace"


def for_run(run) -> Spans | None:
    """The program spans of the traced run ``run``; None without a trace."""
    try:
        path = trace.find_xplane(trace_dir(run.cell))
    except FileNotFoundError:
        return None
    return _load(path, Path(path).stat().st_mtime_ns)
