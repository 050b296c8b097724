"""Device self time per training step of the instructions under the program's
``ffn`` scope: the layers' MLP (or MoE), forward, backward and recomputed
(``chipbench/scopes.py``), averaged over the chips."""

from chipbench import scopes


def read(run):
    return scopes.ms_per(run, "ffn", "steps")
