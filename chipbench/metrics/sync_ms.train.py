"""Device self time per training step of the instructions under the program's
``sync`` scope: the gradients' wire casts, their collective, the casts
back and the metrics' ``pmean`` (``chipbench/scopes.py``), averaged over
the chips."""

from chipbench import scopes


def read(run):
    return scopes.ms_per(run, "sync", "steps")
