"""Cut a small piece out of a recorded trace, as a text proto for the tests.

  python3 chipbench/tools/record_fixture.py <xplane.pb> <out.pbtxt> [--ms 5]
      [--skip-ms 0]

Keeps ``--ms`` milliseconds of the window span, from ``--skip-ms`` after
its start: the harness's host spans and the device planes' operations and
programs that overlap them, with their names and times as recorded, and
the window span cut to that piece.  ``ProfileData.from_text_proto`` reads
the result.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from chipbench import trace  # noqa: E402


def _esc(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def write(planes: list[tuple[str, list[tuple[str, list[tuple[str, float, float]]]]]]) -> str:
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names = sorted({n for _, evs in lines for n, _, _ in evs})
        index = {n: i for i, n in enumerate(names, 1)}
        out.append(f'planes {{ id: {pid} name: "{_esc(pname)}"')
        for lid, (lname, evs) in enumerate(lines, 1):
            out.append(f'  lines {{ id: {lid} name: "{_esc(lname)}" timestamp_ns: 0')
            for n, s, d in evs:
                out.append(f"    events {{ metadata_id: {index[n]} "
                           f"offset_ps: {round(s * 1000)} duration_ps: {round(d * 1000)} }}")
            out.append("  }")
        for n, i in index.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{_esc(n)}" }} }}')
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
    host = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in trace._events(line) if e[0].startswith(trace.SPAN_PREFIX)]
    window = next(e for e in host if e[0] == "chipbench.window")
    t0 = window[1] + args.skip_ms * 1e6
    t1 = t0 + args.ms * 1e6
    keep = lambda evs: [(n, s, d) for n, s, d in evs if s < t1 and s + d > t0]
    picked = keep(e for e in host if e[0] != "chipbench.window")
    planes = [("/host:CPU", [("python", [("chipbench.window", t0, t1 - t0)] + picked)])]
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = [(line.name, keep(list(trace._events(line))))
                     for line in plane.lines
                     if line.name in (trace.OPS_LINE, trace.MODULES_LINE)]
            planes.append((plane.name, lines))
    base = min(s for _, lines in planes for _, evs in lines for _, s, _ in evs)
    planes = [(p, [(ln, [(n, s - base, d) for n, s, d in evs]) for ln, evs in lines])
              for p, lines in planes]
    Path(args.out).write_text(write(planes))
    print(f"{args.out}: {sum(len(e) for _, ls in planes for _, e in ls)} events, "
          f"{(t1 - t0) * 1e-6:.3f} ms")


if __name__ == "__main__":
    main()
