"""The readings the limits of ``correct`` are set from, on the chip, in one
process: the numbers compared for a run of the program on each of
``--seeds``, and for a run with the control (the bfloat16 reference in the
program's place, ``perf/control.py``) on each of ``--control-seeds``. Each
run is a whole run of the cell at ``--seconds``; one JSON line per run.

    python -m perf.tools.readings --workload solve-2400x3200 \
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 3
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

from perf import control
from perf import run as harness


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args(argv)

    harness.configure_jax()
    cell = harness.load_cell(args.workload)[1]
    devices = harness._ensure_chip(int(cell["chips"]))
    for arm, seeds in (("program", _seeds(args.seeds)),
                       ("control", _seeds(args.control_seeds))):
        for seed in seeds:
            with (control.in_place() if arm == "control"
                  else contextlib.nullcontext()):
                result = harness.run_cell(args.workload, seed, args.seconds,
                                          False, devices=devices)
            print(json.dumps({"arm": arm, "seed": seed,
                              "correct": result["correct"],
                              "attempted": result["attempted"],
                              "failed": result["failed"],
                              "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
