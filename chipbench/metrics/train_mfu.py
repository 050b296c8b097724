"""Model FLOP utilization of the training step: model FLOPs per token
(``flops.train_flops_per_token``, recomputation not counted) times the
tokens per second of the traced window, over the chips' bf16 peak."""

from chipbench import flops


def read(run):
    w = run.window
    if not w.counts.get("steps"):
        return None
    per_token = flops.train_flops_per_token(run.cell.config, w.counts["seq_len"])
    rate = w.counts["tokens"] / w.seconds
    return 100.0 * per_token * rate / (run.chips * run.peaks["bf16_flops"])
