"""Device self time per AllReduce call of the instructions under the
``partial_ar`` segment scope, its permutes, merges and packing included:
what R2CCL's partial AllReduce over the healthy ranks (fraction y of the
payload) costs beside the global ring (``chipbench/scopes.py``), averaged
over the chips."""

from chipbench import scopes


def read(run):
    return scopes.ms_per(run, "partial_ar", "calls")
