"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout on a machine with a TPU.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import sys
import time

T_START = time.monotonic()

if __name__ == "__main__":
    from pathlib import Path
    HERE = Path(__file__).resolve().parent
    sys.path.insert(0, str(HERE))
    from chipbench.harness import main
    sys.exit(main(sys.argv[1:], root=HERE.parents[1], t_start=T_START))
