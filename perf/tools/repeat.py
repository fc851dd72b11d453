"""Several runs of one cell in one process on the chip: the result line of
each, as ``perf/run.py`` prints it, with its arm and seed. ``--seeds`` run
with the profiler off, ``--trace-seeds`` with it on, ``--control-seeds``
under a control (``--control``, the module whose ``in_place`` puts it in
the program's place). The process's caches carry over from run to run
(compiled programs, the program's device hierarchy, the reference's host
levels), so only the first run's ``setup_s`` and memory peak are a fresh
process's: this measures the spread of the window's metrics and the
readings of ``correct`` at one set-up's cost.

    python -m perf.tools.repeat --workload mg-mesh2x2-12800x19200 \
        --seeds 1,2,3,4 --trace-seeds 5 --control-seeds 6 \
        --control perf.control_mg_mesh --out repeat.jsonl
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys

from perf import run as harness
from perf.tools.readings import _seeds


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--trace-seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--control", default="perf.control")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--control-seconds", type=float, default=3.0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    harness.configure_jax()
    cell = harness.load_cell(args.workload)[1]
    devices = harness._ensure_chip(int(cell["chips"]))
    control = importlib.import_module(args.control)
    arms = [("program", s, False) for s in _seeds(args.seeds)]
    arms += [("program", s, True) for s in _seeds(args.trace_seeds)]
    arms += [("control", s, False) for s in _seeds(args.control_seeds)]
    out = open(args.out, "a") if args.out else None
    try:
        for arm, seed, trace in arms:
            seconds = (args.control_seconds if arm == "control"
                       else args.seconds)
            with (control.in_place() if arm == "control"
                  else contextlib.nullcontext()):
                result = harness.run_cell(args.workload, seed, seconds,
                                          trace, devices=devices)
            line = json.dumps(dict(result, arm=arm, seed=seed, trace=trace))
            print(line, flush=True)
            if out is not None:
                out.write(line + "\n")
                out.flush()
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
