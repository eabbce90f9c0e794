"""Read a cell's compared numbers over many seeds, with its control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one run of the cell as ``run.py`` makes it (on the chip,
at the cell's own size), followed by the same comparison with the
control put in the program's place: the plain reference computed with
one of the configuration's guarantees broken (each window's last tx
lost).  One JSON line per seed: the program's numbers (the lower
readings of each limit) and the control's (the upper readings).  The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    from harness import core
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = T_START if seed == int(args.seeds.split(",")[0]) \
            else time.perf_counter()
        r = core.run_cell(args.workload, seed, args.seconds, False, t0,
                          with_control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"],
                          "control": r["control_checks"],
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
