"""TPC-H analytics end-to-end: the paper's evaluation, miniaturised.

Generates TPC-H at a small scale factor, executes the paper's query set
through the unified ``PimDatabase.execute`` API on the bulk-bitwise
engine AND the column-scan oracle (``Engine.ORACLE``), verifies
equality, and prints the paper-scale (SF=1000) modeled speedup/energy/
endurance — the numbers Figs. 8/11/15 report. Queries with a host stage
then run END TO END (PIM filter + in-dispatch materialization + host
join/agg/order), and the full decoded result rows of one joined query
(Q3 by default) are printed — the part of the pipeline the paper leaves
to the host. A CONCURRENT batch (Q1+Q6+Q14 by default) goes through
``db.execute([...])``: canonicalized, linked, and dispatched as one
fused program per relation, with the dispatch/plane-read amortization
printed from ``db.last_batch_stats``. The same workload is then
replayed as a concurrent STREAM through the async serving frontend
(``repro.serve.QueryService``), reporting qps/p50/p99 against a
sequential loop. Finally an HTAP STREAMING round trickle-inserts rows
into ``lineitem`` (``repro.dml``: real ISA write programs into reserved
append capacity) between Q6 re-runs, verifies bit-parity against the
NumPy mutable-table oracle, and prints the endurance delta the write
pressure produces in the cost report.

    PYTHONPATH=src python examples/tpch_analytics.py [--sf 0.01]
"""
import argparse

import numpy as np

from repro import dml
from repro.core import bitslice
from repro.db import Engine, database, queries, tpch
from repro.serve.replay import serve_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.003)
    ap.add_argument("--queries", nargs="*", default=None)
    ap.add_argument("--e2e", default="Q3",
                    help="query whose full joined result rows to print")
    ap.add_argument("--batch", nargs="*", default=["Q1", "Q6", "Q14"],
                    help="queries to run concurrently as ONE fused batch")
    args = ap.parse_args()

    print(f"generating TPC-H sf={args.sf} ...")
    db = database.PimDatabase(tpch.generate(sf=args.sf, seed=42))
    specs = queries.all_queries()
    if args.queries:
        specs = [q for q in specs if q.name in args.queries]

    print(f"{'query':9s} {'kind':7s} {'cycles':>9s} {'speedup':>8s} "
          f"{'readred':>8s} {'energy':>7s} {'endur(10y)':>10s} verified")
    for spec in specs:
        # filter_only(): the paper's mask/aggregate scope of every query,
        # host stage (if any) dropped — the cost report's subject.
        pim = db.execute(spec.filter_only())
        base = db.execute(spec.filter_only(), engine=Engine.ORACLE)
        ok = all((pim.relations[r].mask == base.relations[r].mask).all()
                 for r in spec.filters) and pim.aggregates == base.aggregates
        rep = database.cost_report(pim, sf_scale=1000 / args.sf)
        e2e = " +host" if spec.host is not None else ""
        print(f"{spec.name:9s} {spec.kind + e2e:13s} {rep.cycles['total']:>9d} "
              f"{rep.speedup:>8.1f} {rep.read_reduction:>8.1f} "
              f"{rep.energy_saving:>7.2f} "
              f"{rep.endurance_ops_per_cell_10y:>10.2e} "
              f"{'✓' if ok else 'MISMATCH'}")

    # Full end-to-end result rows of one joined query: the PIM stage hands
    # the host only the selected records (materialized in-dispatch), the
    # host completes join/group/order, and the rows decode back to
    # currency/dates/strings.
    spec = queries.get_query(args.e2e)
    if spec.host is None:
        print(f"\n{spec.name} has no host stage; pick one of "
              f"{[q.name for q in queries.all_queries() if q.host]}")
        return
    res = db.execute(spec)
    mat = ", ".join(f"{r}:{n}" for r, n in res.materialized_rows.items())
    print(f"\n== {spec.name} end to end: PIM stage {res.pim_s * 1e3:.1f} ms "
          f"(materialized rows {mat}), host stage {res.host_s * 1e3:.1f} ms ==")
    print(" | ".join(f"{c:>16s}" for c in res.columns))
    for row in res.decoded_rows():
        print(" | ".join(f"{str(v):>16s}" for v in row))

    # Concurrent batch: the same queries submitted together fuse into one
    # linked dispatch per relation — shared source planes stream once,
    # structurally-equal predicate subtrees compile once (CSE), and each
    # query demuxes its own results from the shared ProgramResult.
    batch_specs = [queries.get_query(n) for n in args.batch]
    results = db.execute(batch_specs)
    stats = db.last_batch_stats
    print(f"\n== concurrent batch {'+'.join(args.batch)}: "
          f"{stats['n_queries']} queries -> {stats['n_dispatches']} fused "
          f"dispatches (PIM {stats['pim_s'] * 1e3:.1f} ms, "
          f"demux {stats['demux_s'] * 1e3:.1f} ms) ==")
    for rel, rs in sorted(stats["relations"].items()):
        print(f"  {rel:10s} {rs['n_programs']} programs: "
              f"{rs['instrs_unlinked']} instrs -> {rs['instrs_linked']} "
              f"linked ({rs['instrs_deduped']} deduped by CSE), "
              f"{rs['plane_reads']} plane reads "
              f"({rs['source_plane_reads']} source, streamed once for all "
              f"{rs['n_programs']} queries)")
    for spec, res in zip(batch_specs, results):
        if spec.host is not None:
            print(f"  {spec.name}: {len(res.rows)} result rows (host stage "
                  f"on demuxed materialization)")
        else:
            oracle = db.execute(spec, engine=Engine.ORACLE)
            ok = res.aggregates == oracle.aggregates
            print(f"  {spec.name}: {sum(len(g) for g in res.aggregates.values())}"
                  f" aggregates {'✓' if ok else 'MISMATCH'}")

    # Streamed serving: the batch queries arrive CONCURRENTLY (x2 repeats,
    # so the result cache and in-flight coalescing both engage) through
    # the async frontend — admission windows re-create the fused batch
    # above on the fly.
    trace = [queries.get_query(n) for n in args.batch * 2]
    serve_trace(db, trace)                      # warm executables
    served, sstats, wall = serve_trace(db, trace)
    lat = sstats["latency_ms"]
    print(f"\n== served {len(trace)} concurrent submissions in "
          f"{wall * 1e3:.1f} ms ({len(trace) / wall:.0f} qps, "
          f"p50 {lat['p50']:.1f} ms, p99 {lat['p99']:.1f} ms) ==")
    print(f"  {sstats['dispatches']} dispatches, "
          f"{sstats['coalesced']} coalesced, "
          f"{sstats['cache']['hits']} cache hits, "
          f"windows: {sstats['batcher']['windows']}")

    # HTAP streaming: trickle-insert batches into lineitem between Q6
    # re-runs. Each insert is a real write program (PlaneWrite per
    # attribute + the valid bit) into reserved append-segment capacity,
    # so the layout signature — and every compiled executable — survives;
    # versions bump so cached results can never go stale. The endurance
    # figure moves because the wear-leveling allocator's busiest-row
    # write count now rides into the cost report (dml_row_ops).
    spec6 = queries.get_query("Q6")
    q6 = spec6.filter_only()
    rep0 = db.report(db.execute(q6), sf_scale=1000 / args.sf)
    src = {a: np.asarray(c) for a, c in db.tables["lineitem"].items()}
    n0 = src["l_quantity"].size
    oracle = dml.MutableTable(db.tables["lineitem"])
    rng = np.random.default_rng(0)
    rounds, k, cells = 5, 32, 0
    prev = []
    for _ in range(rounds):
        idx = rng.integers(0, n0, k)
        rows = {a: c[idx] for a, c in src.items()}
        # Rolling staging buffer: each round expires the previous batch —
        # the churn pattern that makes slot choice (wear policy) matter.
        muts = [dml.Insert("lineitem", rows)]
        if prev:
            muts.append(dml.Delete("lineitem", row_ids=prev))
        st = db.apply(muts)["lineitem"]
        new_ids = oracle.insert(rows)
        if prev:
            oracle.delete(row_ids=prev)
        prev = new_ids                     # ids align: same assignment rule
        cells += st["cells_written"]
        r6 = db.execute(q6)
    exp = oracle.aggregate(spec6.filters["lineitem"], spec6.aggregates)
    got = tuple(r6.aggregates["all"][a.name] for a in spec6.aggregates)
    rep1 = db.report(r6, sf_scale=1000 / args.sf)
    d = db.dml_state("lineitem")
    unleveled = dml.replay(d.segments.events,
                           bitslice.pad_words(n0) * bitslice.WORD_BITS,
                           n0, "first_fit").busiest_row_ops()
    print(f"\n== HTAP stream: {rounds} rounds x {k} staged rows into "
          f"lineitem (previous batch expired each round), Q6 after each "
          f"(v{st['version']}) ==")
    print(f"  Q6 vs mutable oracle: "
          f"{'✓ bit-identical' if exp == got else 'MISMATCH'}")
    print(f"  {cells} cells written; busiest row {d.segments.busiest_row_ops():.0f} "
          f"ops leveled (rotate) vs {unleveled:.0f} first-fit replay")
    print(f"  reserved append capacity: {rep1.bytes_reserved / 1024:.0f} KiB "
          f"of {rep1.bytes_resident / 1024:.0f} KiB resident")
    print(f"  endurance (10y, paper scale): "
          f"{rep0.endurance_ops_per_cell_10y:.2e} -> "
          f"{rep1.endurance_ops_per_cell_10y:.2e} ops/cell "
          f"(dml_row_ops {rep1.dml_row_ops:.0f})")


if __name__ == "__main__":
    main()
