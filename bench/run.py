"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 -m bench.run ...            (the same, from the repository root)

Set-up makes the cell's data on the device from the seed and warms the
cell's programs (through the persistent compile cache in the checkout);
the window then measures for ``--seconds``; the check compares what the
window produced with the plain reference (``bench/reference.py``).  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  It needs a TPU: with none, with fewer chips than the cell asks
for, or with a chip missing from ``bench/peaks.json``, it exits non-zero
and prints no result.
"""

import os
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.harness import main

    sys.exit(main(t_start=T_START))
