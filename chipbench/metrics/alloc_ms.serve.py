"""Self time per batch of the engine's ``r2ccl.serve.alloc`` host span:
left-padding the prompts, allocating the batch's caches and moving its
tokens to the device (``chipbench/spans.py``)."""

from chipbench import spans

SPAN = "r2ccl.serve.alloc"


def read(run):
    found = spans.for_run(run)
    return found.per(SPAN, SPAN) if found else None
