"""Device self time per AllReduce call of the instructions under the
program's ``pack`` scope: padding and reshaping the payload into chunks,
slicing it into segments and concatenating the answers
(``chipbench/scopes.py``), averaged over the chips."""

from chipbench import scopes


def read(run):
    return scopes.ms_per(run, "pack", "calls")
