"""Runs a cell with a planted fault, on whatever device the worker finds,
and prints each run's result line: the check has to read it as not
correct.

    python3 benchmark/tests/control.py --workload <cell> --fault skip_half \\
        --seconds 20 --seeds 1,2,3

The faults are those of ``fault_worker.py``; ``skip_half`` is the
control: it breaks the guarantee that one worker answers the first
winner of its range (or the exact least hash of a range without one).
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from fault_worker import FAULT_ENV  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--fault", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args()
    os.environ[FAULT_ENV] = args.fault
    rcs = []
    for seed in args.seeds.split(","):
        rcs.append(run.main(
            ["--workload", args.workload, "--seed", seed,
             "--seconds", str(args.seconds), "--trace", "0"],
            launcher=os.path.join(HERE, "fault_worker.py")))
        sys.stdout.flush()
    return max(rcs)


if __name__ == "__main__":
    sys.exit(main())
