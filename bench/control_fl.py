"""Run an FL cell with one guarantee broken, and print what its check reads.

    python bench/control_fl.py --workload fl-wide-t4n128 --fault bf16_state \\
        --seeds 1,2 --seconds 20

For each seed, one run of the cell as ``run.py`` makes it (on the chip,
at the cell's own size) with the configuration's ``fault`` set (see
``drivers/fl.py``: ``bf16_state``, ``no_dp``, ``unsettled_tx``,
``drop_update``, ``uniform_merge``, ``malicious_as_good``).  One JSON line
per seed: whether the run was correct (it must not be), ``failed`` and
the checks.  The benchmark's own runs never set a fault.
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
    ap.add_argument("--workload", default="fl-wide-t4n128")
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    from harness import core
    t0 = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = core.run_cell(args.workload, seed, args.seconds, False, t0,
                          overrides={"config": {"fault": args.fault}})
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": r["correct"], "failed": r["failed"],
                          "checks": r["checks"]}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
