"""Device time of ``collective-permute`` operations per AllReduce call,
averaged over the chips."""


def read(run):
    t = run.trace.class_s.get("collective-permute")
    calls = run.window.counts.get("calls")
    if not t or not calls:
        return None
    return 1e3 * t / calls
