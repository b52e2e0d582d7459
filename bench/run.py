#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. See ``harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
