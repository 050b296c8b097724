"""Cut a small piece out of a recorded trace, program spans included.

  python3 chipbench/tools/record_spans_fixture.py <xplane.pb> <out.pbtxt>
      [--ms 5] [--skip-ms 0]

As ``record_fixture.py``, and besides the harness's spans it keeps the
program's (``r2ccl.*``) with their numeric arguments: ``--ms``
milliseconds of the window span from ``--skip-ms`` after its start, the
host spans and the device planes' operations and programs that overlap
that piece, with their names and times as recorded, and the window span
cut to it.  ``ProfileData.from_text_proto`` reads the result.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from chipbench import spans, trace  # noqa: E402

Event = tuple[str, float, float, dict]          # name, start, duration, args


def _esc(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def write(planes: list[tuple[str, list[tuple[str, list[Event]]]]]) -> str:
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names = sorted({e[0] for _, evs in lines for e in evs})
        index = {n: i for i, n in enumerate(names, 1)}
        keys = sorted({k for _, evs in lines for e in evs for k in e[3]})
        kindex = {k: i for i, k in enumerate(keys, 1)}
        out.append(f'planes {{ id: {pid} name: "{_esc(pname)}"')
        for lid, (lname, evs) in enumerate(lines, 1):
            out.append(f'  lines {{ id: {lid} name: "{_esc(lname)}" timestamp_ns: 0')
            for n, s, d, args in evs:
                stats = "".join(
                    f" stats {{ metadata_id: {kindex[k]} "
                    f"{'double' if isinstance(v, float) else 'int64'}_value: {v} }}"
                    for k, v in sorted(args.items()))
                out.append(f"    events {{ metadata_id: {index[n]} offset_ps: "
                           f"{round(s * 1000)} duration_ps: {round(d * 1000)}{stats} }}")
            out.append("  }")
        for n, i in index.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{_esc(n)}" }} }}')
        for k, i in kindex.items():
            out.append(f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{_esc(k)}" }} }}')
        out.append("}")
    return "\n".join(out) + "\n"


def main() -> None:
    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=5.0)
    ap.add_argument("--skip-ms", type=float, default=0.0)
    args = ap.parse_args()
    data = ProfileData.from_file(args.xplane)
    host = [(n, s, e - s, a) for line in spans._host_spans(
        data, (spans.PREFIX, trace.SPAN_PREFIX)) for n, s, e, a in line]
    window = next(e for e in host if e[0] == spans.WINDOW)
    t0 = window[1] + args.skip_ms * 1e6
    t1 = t0 + args.ms * 1e6
    keep = lambda evs: [e for e in evs if e[1] < t1 and e[1] + e[2] > t0]
    picked = sorted(keep(e for e in host if e[0] != spans.WINDOW), key=lambda e: e[1])
    planes = [("/host:CPU", [("python", [(spans.WINDOW, t0, t1 - t0, {})] + picked)])]
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = [(line.name, keep([(n, s, d, {}) for n, s, d in trace._events(line)]))
                     for line in plane.lines
                     if line.name in (trace.OPS_LINE, trace.MODULES_LINE)]
            planes.append((plane.name, lines))
    base = min(e[1] for _, lines in planes for _, evs in lines for e in evs)
    planes = [(p, [(ln, [(n, s - base, d, a) for n, s, d, a in evs]) for ln, evs in lines])
              for p, lines in planes]
    Path(args.out).write_text(write(planes))
    print(f"{args.out}: {sum(len(e) for _, ls in planes for _, e in ls)} events, "
          f"{(t1 - t0) * 1e-6:.3f} ms")


if __name__ == "__main__":
    main()
