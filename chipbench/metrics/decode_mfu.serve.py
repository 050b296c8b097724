"""Decode FLOPs of the traced window's batches (each token attending the
prompt and the tokens before it) over the device time of the decode program
(``jit_decode``), over the chip's bf16 peak."""

from chipbench import flops

PROGRAM = "jit_decode"


def read(run):
    t = sum(v for k, v in run.trace.module_s.items() if k.startswith(PROGRAM))
    c = run.window.counts
    if not t or not c.get("batch_sizes"):
        return None
    p, new = c["prompt_len"], c["max_new_tokens"]
    work = sum(flops.decode_flops(run.cell.config, b, p + j)
               for b in c["batch_sizes"] for j in range(1, new))
    return 100.0 * work / t / run.peaks["bf16_flops"]
