#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place,
computed one precision below what the configuration states.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

The configurations state exact answers in 64-bit integers; the control
computes every answer of the cell's query mix in 32-bit integers over
the cell's own data (at its own scale, with the refreshes set-up
applies for a refreshing cell) and counts, per seed, the answers the
comparison rejects: the reading ``wrong_answers`` must show for a
program that made that step down. The benchmark's runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import compare
import harness
from reference import oracle, queries, refresh, tpch


def readings(cell: dict, seed: int) -> dict:
    sf = float(cell["config"]["scale_factor"])
    tables = tpch.generate(sf, seed)
    traffic = harness.load_module("traffic", cell["workload"]["traffic_kind"])
    n_ref = getattr(traffic, "SETUP_REFRESHES", 0)
    if n_ref:
        rs = refresh.Refreshes(tables, sf, seed)
        for k in range(n_ref):
            tables = refresh.apply(tables, rs[k])
    specs = queries.all_queries()
    wrong = []
    for name in queries.names(cell["workload"]["params"]["queries"]):
        want = oracle.answer(tables, specs[name])
        low = oracle.answer(tables, specs[name], dtype=np.int32)
        if compare.differences(low, want):
            wrong.append(name)
    return {"seed": seed, "wrong_answers": len(wrong), "which": wrong}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        out = readings(cell, seed)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
