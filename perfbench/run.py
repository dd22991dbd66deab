#!/usr/bin/env python3
"""One cell of the benchmark, once:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of an eges-tpu checkout, on a machine that holds the chips
the cell asks for.  The last line of standard output is the result; a run
that finds no TPU prints none and exits non-zero.  ``--rehearse`` is the
CPU dress rehearsal (tiny sizes; never ``correct``, never exit code 0).
``--control <name>`` puts a control in the program's place
(``perfbench/control.py``); such a run must come out not correct.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", choices=["jax", "native"], default=None)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "eges_tpu")):
        print("perfbench/run.py runs from the root of an eges-tpu checkout;"
              " there is no program here to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    # ended from outside, a run still stops what it started
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    from perfbench import harness

    harness.build_native()
    cell = harness.Cell(args.workload, bool(args.rehearse))
    driver = importlib.import_module("perfbench.drivers."
                                     + cell.config["driver"])
    return driver.run(cell, args, T0)


if __name__ == "__main__":
    sys.exit(main())
