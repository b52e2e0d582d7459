"""Power traffic: TPC-H's power test shape, one closed-loop client.

The client calls ``PimDatabase.execute`` once per query, the next as
soon as the last returned, over the mix ``params["queries"]`` (a
``reference.queries.names`` mix) in a permutation drawn from the seed
for every pass. A pass starts while the window's seconds last; the pass
running when they are up completes, and the window ends with it. So
every window holds whole passes, the same mix for every seed: the
queries' latencies differ a hundredfold, and a window cut inside a pass
would weigh them by where the seed's order put the cut. ``execute`` has
no result cache, so every query runs on the device.

Set-up runs the mix once, which compiles or loads every program the
window runs. The answers kept for the comparison are
drawn from the seed by ``compare.keep``.

Parameters: ``queries`` (the mix).
"""
from __future__ import annotations

import sys
import time
import traceback

import compare
import roofline
import system
from reference import oracle, queries


def warm(ctx) -> None:
    names = queries.names(ctx.params["queries"])
    ref = queries.all_queries()
    st = ctx.state
    st["names"] = names
    st["ref"] = {n: ref[n] for n in names}
    st["prog"] = {n: system.program_spec(ctx.P, ref[n]) for n in names}
    st["floor"] = {n: roofline.floor_bytes(ref[n], ctx.tables)
                   for n in names}
    for n in names:
        ctx.db.execute(st["prog"][n])


def window(ctx) -> None:
    st, rec, db = ctx.state, ctx.record, ctx.db
    order_rng, keep_rng = ctx.rng(3), ctx.rng(4)
    samples, seen = [], set()
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while time.perf_counter() < deadline:
        for i in order_rng.permutation(len(st["names"])):
            name = st["names"][i]
            rec["attempted"] += 1
            with ctx.span(f"execute {name}"):
                t = time.perf_counter()
                try:
                    res = db.execute(st["prog"][name])
                except Exception:                       # noqa: BLE001
                    rec["failed"] += 1
                    traceback.print_exc(file=sys.stderr)
                    continue
                dt = time.perf_counter() - t
            rec["served"].append({"name": name, "latency_s": dt,
                                  "pim_s": res.pim_s, "host_s": res.host_s,
                                  "cached": False,
                                  "floor_bytes": st["floor"][name]})
            if compare.keep(name not in seen, keep_rng, len(samples),
                            len(st["names"])):
                samples.append((name, system.plain(res)))
            seen.add(name)
            del res
    rec["window_s"] = time.perf_counter() - t0
    st["samples"] = samples


def release(ctx) -> None:
    """Nothing outlives the window but the database the harness frees."""


def check(ctx) -> dict:
    """Every kept answer against the reference over the same tables."""
    st = ctx.state
    want = {n: oracle.answer(ctx.tables, st["ref"][n])
            for n in {name for name, _ in st["samples"]}}
    wrong = 0
    for name, got in st["samples"]:
        diff = compare.differences(got, want[name])
        if diff:
            wrong += 1
            print(f"wrong answer {name}: {'; '.join(diff)[:400]}",
                  file=sys.stderr)
    print(f"answers checked: {len(st['samples'])}", file=sys.stderr)
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "failed_requests": {"value": ctx.record["failed"], "limit": 0}}
