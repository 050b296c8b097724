"""Split a cell's last traced run by the program's scopes and spans.

  python3 chipbench/tools/scope_report.py <cell> [--top 10]

Run on the chip, after ``run.py --workload <cell> --trace 1`` in the same
checkout: it reads that run's trace under ``chipbench/.out/<cell>/trace``
and prints one JSON object.

- ``scopes`` (train and AllReduce cells): device self time by each
  instruction's innermost scope, ``unscoped`` included, the time under each
  scope at any depth, the longest unscoped instructions, the time of the
  loops the compiler made with no ``op_name`` (``scopes.compiler_loops``),
  and the time of instructions that the compiled program does not name (0
  when the program compiled again is the one that ran);
- ``spans``: the program's host spans, self time, count and argument sums;
- ``idle_gaps``: the longest idle gaps of the first chip, named by the
  innermost harness or program span open over them;
- ``runs``: runs of each jitted program in the window, to turn totals into
  per-step or per-call times.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.profiler import ProfileData
    from chipbench import bench, scopes, spans, trace

    cell = bench.load_cell(args.cell)
    path = trace.find_xplane(spans.trace_dir(cell))
    data = ProfileData.from_file(path)
    summary = trace.reduce_profile(data, window_span=spans.WINDOW)
    found = spans.reduce_profile(data, gaps=True)
    out = {"cell": cell.name, "trace": str(path), "window_s": summary.window_s,
           "busy_s": summary.busy_s, "runs": summary.module_runs,
           "device_s": summary.module_s}
    if cell.traffic["driver"] in scopes.COMPILERS:
        hlo = scopes.program_text(cell, jax.devices()[:cell.chips])
        chains, made = scopes.scope_chains(hlo), scopes.compiler_loops(hlo)
        op_s = summary.op_s
        unscoped = sorted(((op, t) for op, t in op_s.items() if not chains.get(op)),
                          key=lambda kv: -kv[1])
        out["scopes"] = {
            "innermost_s": scopes.innermost(op_s, chains),
            "under_s": {s: scopes.time_under(op_s, chains, s) for s in scopes.SCOPES},
            "unscoped_share": sum(t for _, t in unscoped) / summary.busy_s,
            "top_unscoped": unscoped[:args.top],
            "compiler_loops_s": sum(t for op, t in op_s.items() if op in made),
            "not_in_program_s": sum(t for op, t in op_s.items() if op not in chains),
        }
    out["spans"] = {"self_s": found.self_s, "count": found.count, "args": found.args}
    out["idle_gaps"] = found.gaps[:args.top]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
