#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with as many chips as the cell asks for; without one it exits
non-zero and prints no result.
"""

import time

STARTED = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the checkout's program and this package, ahead of anything installed
sys.path[:1] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], root=ROOT, started=STARTED))
