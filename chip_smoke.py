#!/usr/bin/env python3
"""Chip smoke: PIMDB's main path once, end to end, on a TPU.

    python chip_smoke.py [--sf 1.0] [--seed 0] [--chips 1|4]

One process, which touches JAX once. With ``--chips 1`` (the default) it
runs six phases on one chip and checks every result against the NumPy
oracle of the same semantics:

* device  — the first JAX device must be a TPU; otherwise exit non-zero
  before any other work;
* load    — TPC-H at ``--sf`` (``tpch.generate``) packed into bit-planes
  that must sit on the TPU (``PimDatabase``);
* query   — every TPC-H spec through ``PimDatabase.execute`` (FUSED),
  aggregates and masks against ``run_baseline``, the host-stage result
  rows against ``exec.run_host_stage`` over ``exec.baseline_context``;
* serve   — a 16-request mixed trace through ``QueryService.submit`` at
  concurrency 8, equal to the query phase's results;
* dml     — one Insert+Delete batch on lineitem through
  ``QueryService.apply``, then Q6 and Q1 against a ``dml.MutableTable``
  and ``run_baseline`` on the live tables;
* pallas  — ``PimDatabase(backend="pallas")`` runs Q1, Q6 and Q14 with
  compiled (not interpreted) kernels, equal to the oracle and to the jnp
  lowering.

``--chips 4`` runs only the sharded path instead: relations word-sharded
over a ``("pod", "data")`` mesh of four chips, Q1, Q6, Q3 and Q14 through
``execute`` and ``QueryService.submit`` against the oracle.

Every phase prints one line with its wall time. These are smoke timings
(compilation included), not benchmark numbers. Any failure ends the run
with a non-zero exit. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SERVE_TRACE = ("Q1", "Q6", "Q14", "Q3", "Q12", "Q19", "Q1", "Q6",
               "Q14", "Q3", "Q12", "Q19", "Q6", "Q1", "Q14", "Q3")
MESH_QUERIES = ("Q1", "Q6", "Q3", "Q14")
SERVE_CONCURRENCY = 8
DML_INSERTS = 300
DML_DELETES = 200


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def phase(name: str, t0: float, dev, **kv) -> None:
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    extra = " ".join(f"{k}={v}" for k, v in kv.items())
    say(f"phase={name} ok wall_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={peak} {extra} (smoke timing, not a benchmark)")


# --------------------------------------------------------------------------
# Result comparison
# --------------------------------------------------------------------------
def oracle_rows(tables, spec):
    """Host-stage result rows of the NumPy column-scan stand-in."""
    from repro.db import exec as E
    from repro.db.database import QueryResult

    table = E.run_host_stage(spec.host, E.baseline_context(tables, spec))
    return QueryResult.from_table(spec, table, 0.0, 0.0, {}).rows


def check_against_oracle(db, spec, res, label: str,
                         masks: bool = True) -> None:
    """FUSED result == oracle: rows for end-to-end specs, aggregates and
    (with ``masks``) per-relation masks for mask/aggregate specs. After
    DML the PIM masks index physical slots and the oracle's live rows,
    so only aggregates and rows compare."""
    import numpy as np

    if spec.host is not None:
        want = oracle_rows(db.tables, spec)
        check(res.rows == want, f"{label} {spec.name}: rows differ from "
              f"the oracle ({len(res.rows)} vs {len(want)} rows)")
        return
    base = db.run_baseline(spec)
    check(res.aggregates == base.aggregates,
          f"{label} {spec.name}: aggregates {res.aggregates} != oracle "
          f"{base.aggregates}")
    for rel in spec.filters if masks else ():
        check(np.array_equal(res.relations[rel].mask,
                             base.relations[rel].mask),
              f"{label} {spec.name}: {rel} mask differs from the oracle")


def check_same(a, b, label: str) -> None:
    import numpy as np

    check(a.aggregates == b.aggregates and a.rows == b.rows,
          f"{label} {a.name}: result differs")
    for rel, run in a.relations.items():
        check(np.array_equal(run.mask, b.relations[rel].mask),
              f"{label} {a.name}: {rel} mask differs")


def check_resident(db, devices) -> int:
    """Every plane and valid plane of every relation sits on exactly
    ``devices``; returns the resident bytes of all relations."""
    total = 0
    for name, rel in db.relations.items():
        for arr in [*rel.planes.values(), rel.valid]:
            check(arr.devices() == set(devices),
                  f"{name}: planes on {arr.devices()}, want {devices}")
            # Sharded: each device holds its own slice of the word axis.
            starts = {s.index[-1].start for s in arr.addressable_shards}
            check(len(starts) == len(devices),
                  f"{name}: planes are not split over {len(devices)} "
                  f"devices (shard starts {sorted(starts)})")
        say(f"  {name}: records={rel.n_records} "
            f"words={rel.layout.n_words} "
            f"bytes_resident={rel.bytes_resident()}")
        total += rel.bytes_resident()
    return total


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------
def load_phase(args, dev, mesh=None):
    from repro.db import tpch
    from repro.db.database import PimDatabase

    t0 = time.perf_counter()
    tables = tpch.generate(args.sf, args.seed)
    t_gen = time.perf_counter() - t0
    t1 = time.perf_counter()
    db = PimDatabase(tables, mesh=mesh)
    for rel in db.relations.values():
        rel.valid.block_until_ready()
        for p in rel.planes.values():
            p.block_until_ready()
    t_pack = time.perf_counter() - t1
    devices = list(mesh.devices.flat) if mesh is not None else [dev]
    total = check_resident(db, devices)
    phase("load", t0, dev, sf=args.sf,
          lineitem_rows=len(tables["lineitem"]["l_orderkey"]),
          generate_s=f"{t_gen:.3f}", pack_and_put_s=f"{t_pack:.3f}",
          bytes_resident=total)
    return tables, db


def query_phase(db, specs, dev, label="query"):
    t0 = time.perf_counter()
    results = {}
    for spec in specs:
        t = time.perf_counter()
        res = db.execute(spec)
        dt = time.perf_counter() - t
        check_against_oracle(db, spec, res, label)
        results[spec.name] = res
        say(f"  {spec.name}: execute_s={dt:.3f} pim_s={res.pim_s:.6f} "
            f"host_s={res.host_s:.6f} rows={len(res.rows)} "
            f"materialized={res.total_materialized} oracle=equal")
    phase(label, t0, dev, queries=len(specs))
    return results


async def _replay(svc, specs):
    sem = asyncio.Semaphore(SERVE_CONCURRENCY)

    async def one(spec):
        async with sem:
            return await svc.submit(spec)

    return await asyncio.gather(*(one(s) for s in specs))


def serve_phase(db, by_name, expected, dev, trace=SERVE_TRACE):
    from repro.serve import QueryService

    async def run():
        async with QueryService(db, max_window=SERVE_CONCURRENCY) as svc:
            got = await _replay(svc, [by_name[q] for q in trace])
            return got, svc.stats()

    t0 = time.perf_counter()
    got, stats = asyncio.run(run())
    for q, res in zip(trace, got):
        check_same(res, expected[q], "serve")
    check(stats["errors"] == 0, f"serve: {stats['errors']} errors")
    check(stats["degraded_windows"] == 0,
          f"serve: {stats['degraded_windows']} degraded windows")
    lat = stats["latency_ms"]
    phase("serve", t0, dev, requests=len(trace),
          dispatches=stats["dispatches"], coalesced=stats["coalesced"],
          cache_hits=stats["cache"].get("hits"),
          p50_ms=f"{lat['p50']:.3f}", p99_ms=f"{lat['p99']:.3f}")


def dml_phase(db, by_name, seed, dev):
    import numpy as np

    from repro import dml
    from repro.serve import QueryService

    live = db.tables["lineitem"]
    n = len(live["l_orderkey"])
    rng = np.random.default_rng(seed)
    src = rng.choice(n, DML_INSERTS, replace=False)
    batch = [dml.Insert("lineitem", {a: live[a][src] for a in live}),
             dml.Delete("lineitem", row_ids=rng.choice(
                 n, DML_DELETES, replace=False).tolist())]
    oracle = dml.MutableTable(live)
    for m in batch:
        oracle.apply(m)
    q6, q1 = by_name["Q6"], by_name["Q1"]

    async def run():
        async with QueryService(db) as svc:
            stats = await svc.apply(batch)
            return stats, await svc.submit(q6), await svc.submit(q1), \
                svc.stats()

    t0 = time.perf_counter()
    stats, r6, r1, sstats = asyncio.run(run())
    now = db.tables["lineitem"]
    for a, col in oracle.columns().items():
        check(np.array_equal(now[a], col),
              f"dml: live lineitem.{a} differs from the MutableTable")
    check(not r6.cached and not r1.cached, "dml: a stale cached result")
    for spec, res in ((q6, r6), (q1, r1)):
        check_against_oracle(db, spec, res, "dml", masks=False)
    want6 = oracle.aggregate(q6.filters["lineitem"], q6.aggregates)
    got6 = tuple(r6.aggregates["all"][a.name] for a in q6.aggregates)
    check(got6 == want6, f"dml: Q6 {got6} != MutableTable {want6}")
    check(sstats["errors"] == 0, f"dml: {sstats['errors']} errors")
    phase("dml", t0, dev, inserted=DML_INSERTS, deleted=DML_DELETES,
          live_rows=oracle.n_rows,
          cells_written=stats["lineitem"]["cells_written"])


def pallas_phase(db, by_name, dev):
    from repro.core import program as prog
    from repro.db.compiler import Compiler
    from repro.db.database import PimDatabase

    t0 = time.perf_counter()
    dbp = PimDatabase(db.tables, backend="pallas")
    q6 = by_name["Q6"]
    rel = dbp.relations["lineitem"]
    c = Compiler(rel)
    m = c.compile_filter(q6.filters["lineitem"], with_transform=False)
    cp = prog.compile_program(rel, c.program, mask_outputs=(m,),
                              backend="pallas")
    check(cp.interpret is False, "pallas: kernels would be interpreted")
    for q in ("Q1", "Q6", "Q14"):
        spec = by_name[q]
        t = time.perf_counter()
        res = dbp.execute(spec)
        dt = time.perf_counter() - t
        check_against_oracle(dbp, spec, res, "pallas")
        # db's lineitem went through DML: its slots are not dbp's rows,
        # so the two lowerings compare on aggregates and rows.
        jnp_res = db.execute(spec)
        check(res.aggregates == jnp_res.aggregates
              and res.rows == jnp_res.rows, f"pallas {q}: differs from jnp")
        say(f"  {q}: execute_s={dt:.3f} oracle=equal jnp=equal")
    phase("pallas", t0, dev, queries=3, interpret=cp.interpret)


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1.0)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        say(f"device: no TPU: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); this smoke runs on a TPU only")
        return 2
    if len(devices) < args.chips:
        say(f"device: --chips {args.chips} needs {args.chips} TPU chips, "
            f"JAX sees {len(devices)}")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.core import compile_cache
    except ImportError as e:
        say(f"device: the repro package is not next to this script ({e})")
        return 2
    entries_before = compile_cache.n_entries()
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={compile_cache.cache_dir()} "
        f"cache_entries={entries_before}")
    phase("device", t0, dev)

    from repro.db import queries as Q

    specs = Q.all_queries()
    by_name = {s.name: s for s in specs}
    if args.chips == 1:
        _, db = load_phase(args, dev)
        expected = query_phase(db, specs, dev)
        serve_phase(db, by_name, expected, dev)
        dml_phase(db, by_name, args.seed, dev)
        pallas_phase(db, by_name, dev)
    else:
        mesh = jax.make_mesh((1, args.chips), ("pod", "data"),
                             devices=devices[:args.chips])
        _, db = load_phase(args, dev, mesh=mesh)
        expected = query_phase(db, [by_name[q] for q in MESH_QUERIES], dev,
                               label="mesh_query")
        serve_phase(db, by_name, expected, dev, trace=MESH_QUERIES * 2)

    say(f"compile cache: entries before={entries_before} "
        f"after={compile_cache.n_entries()} dir={compile_cache.cache_dir()}")
    say(f"total wall_s={time.perf_counter() - t0:.3f} "
        "(smoke timing, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
