"""Device busy time per AllReduce call outside the ``collective-permute``
operations: the per-round take / add / where / update passes, averaged over
the chips."""


def read(run):
    calls = run.window.counts.get("calls")
    if not calls or not run.trace.busy_s:
        return None
    rest = run.trace.busy_s - run.trace.class_s.get("collective-permute", 0.0)
    return 1e3 * rest / calls
