"""Share of the AllReduce's roofline: the least time its algorithmic bytes
need (wire bytes 2(n-1)/n S at the ICI peak, or HBM bytes 2 S at the HBM
peak, whichever is longer) over the device time per call (busy time of the
traced window over its calls).  The work is counted the same whatever
schedule implements it."""

from chipbench import flops


def read(run):
    c = run.window.counts
    if not c.get("calls") or not run.trace.busy_s:
        return None
    size, n = c["bytes_per_rank"], c["ranks"]
    least = max(flops.allreduce_wire_bytes(size, n) / run.peaks["ici_bytes_per_s"],
                flops.allreduce_hbm_bytes(size) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (run.trace.busy_s / c["calls"])
