"""Self time per decode step of the engine's ``r2ccl.serve.readback`` host
span: one device-to-host read per unfinished request of the batch
(``chipbench/spans.py``)."""

from chipbench import spans


def read(run):
    found = spans.for_run(run)
    return found.per("r2ccl.serve.readback", "r2ccl.serve.decode") if found else None
