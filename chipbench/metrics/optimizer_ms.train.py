"""Device self time per training step of the instructions under the program's
``optimizer`` scope: the AdamW update (``chipbench/scopes.py``), averaged
over the chips."""

from chipbench import scopes


def read(run):
    return scopes.ms_per(run, "optimizer", "steps")
