"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration x traffic mix x chips) comes from BENCHMARK.json
at the repository root.  The process holds the cell's chips, sets up,
measures for ``--seconds``, checks the timed path's output against the
plain reference and prints, as the last line of stdout, one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` too with ``--trace 1``) and, last, ``checks``: each number
compared with its limit.  With no TPU, or fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import repro  # noqa: F401  (the system under test; absent: no run)
    from harness import core
    return core.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
