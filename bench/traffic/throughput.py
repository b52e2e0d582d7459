"""Throughput traffic: TPC-H's throughput test through ``QueryService``.

``params["streams"]`` query streams, each a closed loop that submits
through ``QueryService.submit``, run beside one refresh stream of
``QueryService.apply`` over the refresh sequence of
``reference.refresh`` (RF1, RF2, RF1, ...). The streams take their
queries from one shared sequence of passes, each pass the mix
``params["queries"]`` in a permutation drawn from the seed, and the
refresh stream runs one RF1/RF2 pair per pass, as TPC-H's refresh
stream runs one pair per query stream: pair ``k`` starts once ``k``
passes' worth of queries have completed. A pass starts while the
window's seconds last; the pass running when they are up is finished,
its pair with it, and the window ends when the last query and refresh
have completed. So every window holds whole passes and their pairs, the
same work for every seed. The service is built with
``params["service"]`` as its keyword arguments.

Set-up submits every query of the mix, applies the first RF1 and RF2,
and submits every query again, so that each program the window
dispatches, and the refresh path, is compiled and loaded before the
window opens.

Which refreshes an answer may reflect: at least every refresh whose
``apply`` returned before the query was submitted (the configuration's
guarantee) and at most every refresh whose ``apply`` had started. The
comparison replays the refreshes on the reference's tables and accepts
an answer that equals the reference at one of those states, mask by
mask row by row: the program's log of each refresh (the logical row ids
and the storage slots it wrote) gives where each live row sat after
every refresh. After the window, the ``orders`` and ``lineitem`` rows
are read back from the chip's bit-planes at the slots of the live rows
and must equal the reference's with every refresh applied, and the
chip's valid plane must hold exactly those slots.

Parameters: ``streams``, ``queries``, ``service`` (as above).
"""
from __future__ import annotations

import asyncio
import sys
import time
import traceback
from collections import defaultdict

import numpy as np

import compare
import system
from reference import oracle, queries, refresh

REFRESHED = ("orders", "lineitem")
#: Refreshes set-up applies (one RF1 and one RF2) before the window.
SETUP_REFRESHES = 2


def warm(ctx) -> None:
    names = queries.names(ctx.params["queries"])
    ref = queries.all_queries()
    st = ctx.state
    st.update(names=names, ref={n: ref[n] for n in names},
              prog={n: system.program_spec(ctx.P, ref[n]) for n in names},
              refreshes=refresh.Refreshes(
                  ctx.tables, float(ctx.config["scale_factor"]), ctx.seed),
              initial=ctx.tables, started=0, returned=0,
              logged=[{rel: 0 for rel in REFRESHED}])
    st["loop"] = asyncio.new_event_loop()
    st["svc"] = ctx.P.serve.QueryService(ctx.db, **ctx.params["service"])

    async def go():
        svc = st["svc"]
        for n in names:
            await svc.submit(st["prog"][n])
        for _ in range(SETUP_REFRESHES):
            await _apply(ctx, record=False)
        for n in names:
            await svc.submit(st["prog"][n])

    st["loop"].run_until_complete(go())


async def _apply(ctx, record: bool) -> None:
    st = ctx.state
    r = st["refreshes"][st["started"]]
    batch = system.mutations(ctx.P, r)
    st["started"] += 1               # the apply is queued in this step
    t = time.perf_counter()
    with ctx.span(f"apply RF{1 if r[0] == 'insert' else 2}"):
        stats = await st["svc"].apply(batch)
    dt = time.perf_counter() - t
    st["returned"] += 1
    st["logged"].append({rel: len(ctx.db.dml_state(rel).programs)
                         for rel in REFRESHED})
    if record:
        ctx.record["refreshes"].append({
            "kind": r[0], "apply_s": dt,
            "rows": sum(s["n_rows"] for s in stats.values()),
            "cells_written": sum(s["cells_written"] for s in stats.values())})


class _Passes:
    """The shared sequence of passes the query streams draw from, and the
    count of completed queries that paces the refresh stream."""

    def __init__(self, ctx, deadline: float):
        self.names = ctx.state["names"]
        self.rng = ctx.rng(3)
        self.deadline = deadline
        self.order: list = []
        self.dealt = 0               # passes started
        self.closed = False          # no pass starts any more
        self.completed = 0           # queries answered or failed
        self.changed = asyncio.Condition()

    async def draw(self):
        """The next query of the current pass, starting a new pass while
        the window lasts; ``None`` once the window is closed."""
        if not self.order:
            if time.perf_counter() >= self.deadline:
                self.closed = True
                await self.notify()
                return None
            self.order = list(self.rng.permutation(len(self.names)))
            self.dealt += 1
            await self.notify()
        return self.names[self.order.pop(0)]

    async def notify(self) -> None:
        async with self.changed:
            self.changed.notify_all()

    async def done(self) -> None:
        self.completed += 1
        await self.notify()


async def _stream(ctx, k: int, passes: _Passes, samples: list) -> None:
    st, rec = ctx.state, ctx.record
    keep_rng = ctx.rng(4, k)
    while True:
        # Yield first: an answer that needs no dispatch returns without
        # suspending, and the other streams share this event loop.
        await asyncio.sleep(0)
        name = await passes.draw()
        if name is None:
            return
        lo, hi = st["returned"], st["started"]
        rec["attempted"] += 1
        t = time.perf_counter()
        try:
            with ctx.span(f"submit {name}"):
                res = await st["svc"].submit(st["prog"][name])
        except Exception:                               # noqa: BLE001
            rec["failed"] += 1
            traceback.print_exc(file=sys.stderr)
            await passes.done()
            continue
        dt = time.perf_counter() - t
        await passes.done()
        rec["served"].append({
            "name": name, "latency_s": dt, "cached": res.cached,
            "pim_s": 0.0 if res.cached else res.pim_s,
            "host_s": 0.0 if res.cached else res.host_s})
        first = name not in st["seen"]
        st["seen"].add(name)
        if compare.keep(first, keep_rng, len(samples), len(st["names"])):
            samples.append((name, system.plain(res), lo, hi))


async def _refresher(ctx, passes: _Passes) -> None:
    """Pair ``k`` (RF1, then RF2) once ``k`` passes' worth of queries
    have completed, for every pass the window started."""
    n = len(passes.names)
    k = 0
    while True:
        async with passes.changed:
            await passes.changed.wait_for(
                lambda: passes.completed >= k * n
                and (passes.dealt > k or passes.closed))
        if passes.dealt <= k:
            return
        for _ in range(2):
            ctx.record["attempted"] += 1
            try:
                await _apply(ctx, record=True)
            except Exception:                           # noqa: BLE001
                ctx.record["failed"] += 1
                traceback.print_exc(file=sys.stderr)
                return
        k += 1


def window(ctx) -> None:
    st, rec = ctx.state, ctx.record
    samples: list = []
    st["seen"] = set()

    async def go():
        passes = _Passes(ctx, time.perf_counter() + ctx.seconds)
        await asyncio.gather(
            *(_stream(ctx, k, passes, samples)
              for k in range(int(ctx.params["streams"]))),
            _refresher(ctx, passes))

    t0 = time.perf_counter()
    st["loop"].run_until_complete(go())
    rec["window_s"] = time.perf_counter() - t0
    st["samples"] = samples


def release(ctx) -> None:
    """Read the refreshed relations back from the chip and keep the
    program's refresh log, then stop the service."""
    st = ctx.state
    st["chip"] = {rel: system.chip_rows(ctx.db, rel) for rel in REFRESHED}
    st["log"] = {rel: system.slot_log(ctx.db, rel) for rel in REFRESHED}
    st["loop"].run_until_complete(st["svc"].__aexit__(None, None, None))
    st["loop"].close()
    st["svc"] = st["loop"] = None


def _slot_maps(ctx):
    """For each number of refreshes applied, in order: {relation: the
    storage slot of each live row, in row order}, replayed from the
    program's refresh log."""
    st = ctx.state
    ids = {rel: np.arange(len(next(iter(st["initial"][rel].values()))))
           for rel in REFRESHED}
    slots = dict(ids)
    done = {rel: 0 for rel in REFRESHED}
    for mark in st["logged"]:
        for rel in REFRESHED:
            for op, op_ids, op_slots in st["log"][rel][done[rel]:mark[rel]]:
                if op == "insert":
                    ids[rel] = np.concatenate([ids[rel], op_ids])
                    slots[rel] = np.concatenate([slots[rel], op_slots])
                else:
                    keep = ~np.isin(ids[rel], op_ids)
                    ids[rel], slots[rel] = ids[rel][keep], slots[rel][keep]
            done[rel] = mark[rel]
        yield dict(slots)


def _chip_rows_wrong(chip, slots: np.ndarray, want) -> int:
    """Live rows whose values on the chip differ from ``want``, plus
    every slot whose valid bit disagrees with the live rows."""
    values, valid = chip
    if slots.size and slots.max() >= valid.size:
        return max(slots.size, len(next(iter(want.values()))))
    live = np.zeros(valid.size, bool)
    live[slots] = True
    got = {a: v[slots] for a, v in values.items()}
    return compare.table_differences(got, want) + int((valid != live).sum())


def check(ctx) -> dict:
    st = ctx.state
    samples = st["samples"]
    by_version = defaultdict(list)
    for i, (_, _, lo, hi) in enumerate(samples):
        for v in range(lo, hi + 1):
            by_version[v].append(i)
    ok, wants = set(), {}
    tables = st["initial"]
    for v, slots in enumerate(_slot_maps(ctx)):
        if v:
            tables = refresh.apply(tables, st["refreshes"][v - 1])
        for i in by_version.get(v, ()):
            name, got = samples[i][0], samples[i][1]
            if i in ok:
                continue
            if (name, v) not in wants:
                wants[(name, v)] = oracle.answer(tables, st["ref"][name])
            diff = compare.differences(got, wants[(name, v)], slots)
            if not diff:
                ok.add(i)
            elif v == samples[i][3]:
                print(f"wrong answer {name} (refreshes {samples[i][2]}.."
                      f"{samples[i][3]}): {'; '.join(diff)[:400]}",
                      file=sys.stderr)
    live_wrong = sum(_chip_rows_wrong(st["chip"][rel], slots[rel],
                                      tables[rel]) for rel in REFRESHED)
    print(f"answers checked: {len(samples)}; refreshes applied: "
          f"{st['started']}", file=sys.stderr)
    return {"wrong_answers": {"value": len(samples) - len(ok), "limit": 0},
            "failed_requests": {"value": ctx.record["failed"], "limit": 0},
            "live_rows_wrong": {"value": live_wrong, "limit": 0}}
