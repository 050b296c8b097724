"""Wall time per decode step, from the engine's clock readings, minus the
device time of the decode program per step: the host's share of a token."""

PROGRAM = "jit_decode"


def read(run):
    c = run.window.counts
    t = sum(v for k, v in run.trace.module_s.items() if k.startswith(PROGRAM))
    if not t or not c.get("decode_steps"):
        return None
    return 1e3 * (c["decode_wall_s"] - t) / c["decode_steps"]
