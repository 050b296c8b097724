"""Self time per decode step of the engine's ``r2ccl.serve.dispatch`` host
span: the decode call until it returns, before the step is waited for
(``chipbench/spans.py``)."""

from chipbench import spans


def read(run):
    found = spans.for_run(run)
    return found.per("r2ccl.serve.dispatch", "r2ccl.serve.decode") if found else None
