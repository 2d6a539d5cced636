"""Run one benchmark cell once, from the checkout root:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, chip start, graphs, compile or cache load, a warm-up of
the cell's grid shapes) is timed from the start of this script.
``--trace 0`` then measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` traces the first chunks of one grid under the
profiler and reports its per-layer metrics.  Either way a sample of the window's rows is checked
against the plain reference.  Exits non-zero, with no result line, without
an accelerator or with another number of chips than the cell asks for.
"""

import time

START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(start=START, root=ROOT))
