"""Prefill FLOPs of the traced window's batches (``flops.prefill_flops``)
over the device time of the prefill program (``jit_prefill``), over the
chip's bf16 peak."""

from chipbench import flops

PROGRAM = "jit_prefill"


def read(run):
    t = sum(v for k, v in run.trace.module_s.items() if k.startswith(PROGRAM))
    c = run.window.counts
    if not t or not c.get("batch_sizes"):
        return None
    work = sum(flops.prefill_flops(run.cell.config, b, c["prompt_len"])
               for b in c["batch_sizes"])
    return 100.0 * work / t / run.peaks["bf16_flops"]
