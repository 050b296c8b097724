#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It runs only on a TPU: with no TPU, or
fewer chips than the cell asks for, it exits non-zero and prints no result.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
a ``breakdown``) and, last, ``checks``: each number compared with the plain
reference beside its limit.  The same comparisons are the last lines of
standard error.
"""

import time

STARTED = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_REPO), str(_REPO / "src")]

from chipbench.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(started=STARTED))
