"""Device self time per training step of the instructions under the program's
``attention`` scope: the layers' attention (GQA or MLA) branch, forward,
backward and recomputed (``chipbench/scopes.py``), averaged over the
chips."""

from chipbench import scopes


def read(run):
    return scopes.ms_per(run, "attention", "steps")
