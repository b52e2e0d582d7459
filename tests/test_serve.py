"""Async serving frontend: result cache (hit/miss/version-invalidation),
admission batcher (size vs timeout flush), concurrent-submit parity vs
sequential ``execute`` on jnp+pallas, backpressure, every query through
``submit`` after an RF1/RF2 refresh pair against the oracle, trace
replay, and an 8-device mesh subprocess smoke test."""
import asyncio

import numpy as np
import pytest

from _mesh_subprocess import run_forced_multidevice

from repro import dml
from repro.db import queries, tpch
from repro.db.database import Engine, PimDatabase
from repro.serve import (AdmissionBatcher, QueryService, ResultCache,
                         spec_cache_key)
from repro.serve.replay import parse_trace, serve_trace
from repro.serve.service import _pct

# Same generator parameters as test_fusion.py / test_api.py so the
# compiled-executable cache is shared across modules.
SF, SEED = 0.002, 123
_CACHE: dict = {}


def _get_db(backend: str = "jnp") -> PimDatabase:
    if "tables" not in _CACHE:
        _CACHE["tables"] = tpch.generate(sf=SF, seed=SEED)
    if backend not in _CACHE:
        _CACHE[backend] = PimDatabase(_CACHE["tables"], backend=backend)
    return _CACHE[backend]


@pytest.fixture(scope="module")
def db():
    return _get_db("jnp")


@pytest.fixture(scope="module")
def db_pallas():
    return _get_db("pallas")


# --------------------------------------------------------------------------
# Cache key + ResultCache
# --------------------------------------------------------------------------
def test_spec_cache_key_structural(db):
    from repro.db.compiler import And, Between, Cmp, Col, Lit
    import dataclasses

    q6 = queries.get_query("Q6")
    assert spec_cache_key(db, q6, Engine.FUSED) \
        == spec_cache_key(db, q6, Engine.FUSED)
    assert spec_cache_key(db, q6, Engine.FUSED) \
        != spec_cache_key(db, q6, Engine.EAGER)
    # Equal-meaning, differently-spelled predicates share a key.
    col = Col("l_quantity")
    a = dataclasses.replace(q6, filters={"lineitem": Between(col, 10, 20)})
    b = dataclasses.replace(q6, filters={"lineitem": And(
        Cmp("ge", col, Lit(10)), Cmp("le", col, Lit(20)))})
    assert spec_cache_key(db, a, Engine.FUSED) \
        == spec_cache_key(db, b, Engine.FUSED)


def test_cache_key_tracks_relation_version(db):
    # Real mutations (repro.dml), not a simulated version bump: the
    # publish step of ``PimDatabase.apply`` is what the cache key must
    # track.
    q6 = queries.get_query("Q6")
    before = spec_cache_key(db, q6, Engine.FUSED)
    take = {a: np.asarray(c[:2]) for a, c in db.tables["lineitem"].items()}
    db.apply([dml.Insert("lineitem", take)])
    after = spec_cache_key(db, q6, Engine.FUSED)
    assert before != after
    # Mutating an unrelated relation leaves other queries' keys alone.
    q14 = queries.get_query("Q14")
    k1 = spec_cache_key(db, q14, Engine.FUSED)
    db.apply([dml.Delete("customer", row_ids=[0])])
    assert spec_cache_key(db, q14, Engine.FUSED) == k1


def test_result_cache_lru():
    c = ResultCache(capacity=2)
    c.put(("a",), "ra")
    c.put(("b",), "rb")
    assert c.get(("a",)) == "ra"          # refreshes 'a'
    c.put(("c",), "rc")                   # evicts 'b' (LRU)
    assert c.get(("b",)) is None
    assert c.get(("a",)) == "ra" and c.get(("c",)) == "rc"
    s = c.stats()
    assert s["evictions"] == 1 and s["size"] == 2
    assert s["hits"] == 3 and s["misses"] == 1


# --------------------------------------------------------------------------
# Admission batcher: flush on size vs timeout
# --------------------------------------------------------------------------
def test_batcher_flush_on_size():
    windows = []

    async def run():
        b = AdmissionBatcher(windows.append, max_window=3, max_wait_s=60.0)
        for i in range(7):
            b.add(i)
        # Two size-flushes fired inline; one item still pending on the
        # (long) timer.
        assert b.pending == 1
        b.flush_now()
        return b.stats()

    stats = asyncio.run(run())
    assert windows == [[0, 1, 2], [3, 4, 5], [6]]
    assert stats["flush_size"] == 2
    assert stats["flush_timeout"] == 0
    assert stats["flush_forced"] == 1
    assert stats["max_window_seen"] == 3


def test_batcher_flush_on_timeout():
    windows = []

    async def run():
        b = AdmissionBatcher(windows.append, max_window=100,
                             max_wait_s=0.02)
        b.add("x")
        b.add("y")
        assert b.pending == 2 and not windows
        await asyncio.sleep(0.1)
        return b.stats()

    stats = asyncio.run(run())
    assert windows == [["x", "y"]]
    assert stats["flush_timeout"] == 1 and stats["flush_size"] == 0


def test_batcher_rejects_bad_window():
    with pytest.raises(ValueError):
        AdmissionBatcher(lambda w: None, max_window=0)


def test_pct_helper():
    assert _pct([1.0], 0.99) == 1.0
    assert _pct([1.0, 2.0, 3.0, 4.0], 0.0) == 1.0
    assert _pct([1.0, 2.0, 3.0, 4.0], 0.99) == 4.0


# --------------------------------------------------------------------------
# Service: cache hit/miss/invalidation through submit()
# --------------------------------------------------------------------------
def test_service_cache_hit_and_version_invalidation(db):
    q6 = queries.get_query("Q6")
    want = db.execute(q6)

    async def run():
        async with QueryService(db, max_window=4, max_wait_s=0.001) as svc:
            r1 = await svc.submit(q6)
            r2 = await svc.submit(q6)
            misses_before_dml = svc.cache.misses
            # Real DML through the service: deleting live rows bumps the
            # published relation version, so the stale cached result can
            # never be served again.
            ids = db.dml_state("lineitem").live_ids()[:2]
            await svc.apply([dml.Delete("lineitem", row_ids=ids)])
            r3 = await svc.submit(q6)
            return (r1, r2, r3, misses_before_dml, svc.cache.stats(),
                    svc.stats())

    r1, r2, r3, misses_before, cstats, sstats = asyncio.run(run())
    assert not r1.cached and r2.cached
    # The mutation changed the key: r3 re-dispatched (a miss) and ran
    # against the post-delete contents — bit-identical to a fresh direct
    # execute on the mutated database.
    assert not r3.cached
    assert cstats["misses"] == misses_before + 1
    assert r1.aggregates == r2.aggregates == want.aggregates
    assert r3.aggregates == db.execute(q6).aggregates
    assert sstats["mutations"] == 1


def test_service_coalesces_identical_inflight(db):
    q1 = queries.get_query("Q1")
    want = db.execute(q1)

    async def run():
        async with QueryService(db, max_window=8, max_wait_s=0.005) as svc:
            res = await asyncio.gather(*[svc.submit(q1) for _ in range(5)])
            return res, svc.stats()

    res, stats = asyncio.run(run())
    assert all(r.aggregates == want.aggregates for r in res)
    assert stats["coalesced"] == 4
    assert stats["batcher"]["items"] == 1     # ONE dispatched request


# --------------------------------------------------------------------------
# Concurrent-submit parity vs sequential execute, both backends
# --------------------------------------------------------------------------
def _parity_trace(db, svc_kwargs=None):
    names = ["Q1", "Q6", "Q14", "Q3", "Q6", "Q1"]
    specs = [queries.get_query(n) for n in names]
    seq = [db.execute(s) for s in specs]

    async def run():
        async with QueryService(db, max_window=4, max_wait_s=0.005,
                                **(svc_kwargs or {})) as svc:
            res = await asyncio.gather(*[svc.submit(s) for s in specs])
            return res, svc.stats()

    res, stats = asyncio.run(run())
    for name, r, s in zip(names, res, seq):
        assert r.rows == s.rows, name
        assert r.aggregates == s.aggregates, name
    assert stats["completed"] == len(specs)
    assert stats["errors"] == 0
    return stats


def test_service_concurrent_parity_jnp(db):
    stats = _parity_trace(db)
    # Windowed linking must beat one dispatch per (query, relation).
    assert stats["dispatches"] < 8


def test_service_concurrent_parity_pallas(db_pallas):
    _parity_trace(db_pallas)


def test_service_eager_engine_parity(db):
    q6 = queries.get_query("Q6")
    want = db.execute(q6, engine=Engine.EAGER)

    async def run():
        async with QueryService(db, engine=Engine.EAGER,
                                max_wait_s=0.001) as svc:
            return await svc.submit(q6)

    got = asyncio.run(run())
    assert got.aggregates == want.aggregates
    assert got.engine is Engine.EAGER


# --------------------------------------------------------------------------
# Backpressure
# --------------------------------------------------------------------------
def test_service_backpressure_semaphore(db):
    q6 = queries.get_query("Q6")
    q1 = queries.get_query("Q1")

    async def run():
        svc = QueryService(db, max_window=1, max_wait_s=0.001,
                           max_pending=2, cache_capacity=0)
        async with svc:
            res = await asyncio.gather(
                *[svc.submit(q6 if i % 2 else q1) for i in range(6)])
            # All admissions resolved and every permit was returned.
            assert svc._sem._value == 2
            return res, svc.stats()

    res, stats = asyncio.run(run())
    assert len(res) == 6 and stats["errors"] == 0
    # cache_capacity=0 disables the result cache; repeats still resolve
    # (coalescing or fresh dispatch), so the semaphore really cycled.
    assert stats["cache"]["hits"] == 0


# --------------------------------------------------------------------------
# Failure paths: rejection fan-out, permit restoration, cache hygiene
# --------------------------------------------------------------------------
def test_dispatch_failure_propagates_to_all_coalesced_waiters(db):
    # A dispatch-worker exception must reach EVERY awaiter parked on the
    # window — the submitter that admitted the query AND the coalesced
    # submissions sharing its key — and must restore the backpressure
    # permit, or the service wedges after its first bad window.
    q6 = queries.get_query("Q6")
    boom = ValueError("injected dispatch failure")

    def bad_dispatch(specs):
        raise boom

    async def run():
        svc = QueryService(db, max_window=8, max_wait_s=0.05, max_pending=2)
        real = db.dispatch_batch
        db.dispatch_batch = bad_dispatch
        try:
            async with svc:
                # Both submits land before the (slow) timer flush: the
                # second coalesces onto the first's in-flight future.
                res = await asyncio.gather(svc.submit(q6), svc.submit(q6),
                                           return_exceptions=True)
                assert [r is boom for r in res] == [True, True]
                assert svc.stats()["coalesced"] == 1
                # The failed admission returned its permit.
                assert svc._sem._value == 2
                # A failed result is never cached, and nothing is stuck
                # in flight: a resubmit with the fault cleared dispatches
                # fresh and matches direct execution.
                db.dispatch_batch = real
                key = spec_cache_key(db, q6, Engine.FUSED)
                assert svc.cache.get(key) is None
                assert not svc._inflight
                ok = await svc.submit(q6)
                assert not ok.cached
                assert ok.aggregates == db.execute(q6).aggregates
                return svc.stats()
        finally:
            db.dispatch_batch = real

    stats = asyncio.run(run())
    # One rejection (the coalesced waiter shares the future), nothing
    # left in flight.
    assert stats["errors"] == 1
    assert stats["inflight"] == 0


def test_closed_service_rejects_promptly(db):
    # Submitting after close() must fail fast (the window handoff to the
    # shut-down pool raises and every request is rejected) — never hang
    # the awaiter on a future nothing will resolve.
    q6 = queries.get_query("Q6")

    async def run():
        svc = QueryService(db, max_window=4, max_wait_s=0.001)
        svc.close()
        with pytest.raises(RuntimeError):
            await asyncio.wait_for(svc.submit(q6), timeout=30)
        assert svc._sem._value == svc.max_pending
        return svc.stats()

    stats = asyncio.run(run())
    assert stats["errors"] == 1 and stats["inflight"] == 0


# --------------------------------------------------------------------------
# The served path after a refresh: every query through submit() equals
# the oracle over the refreshed tables
# --------------------------------------------------------------------------
N_REFRESH = 30      # orders inserted by RF1 and deleted by RF2 (1%)


def _refresh_pair(tables):
    """An RF1/RF2 pair over ``tables``: RF1 inserts copies of some orders
    under fresh keys, with copies of their lineitems; RF2 deletes other
    orders and all their lineitems, by key."""
    from repro.db.compiler import Col, InSet

    rng = np.random.default_rng(SEED)
    orders, lineitem = tables["orders"], tables["lineitem"]
    keys = np.asarray(orders["o_orderkey"])
    picked = rng.permutation(keys.size)
    src, gone = picked[:N_REFRESH], picked[N_REFRESH:2 * N_REFRESH]
    new_key = dict(zip(keys[src].tolist(),
                       range(int(keys.max()) + 1,
                             int(keys.max()) + 1 + N_REFRESH)))
    new_orders = {a: np.asarray(c)[src] for a, c in orders.items()}
    new_orders["o_orderkey"] = np.asarray(
        [new_key[k] for k in keys[src].tolist()])
    lk = np.asarray(lineitem["l_orderkey"])
    lines = np.flatnonzero(np.isin(lk, keys[src]))
    new_lines = {a: np.asarray(c)[lines] for a, c in lineitem.items()}
    new_lines["l_orderkey"] = np.asarray(
        [new_key[k] for k in lk[lines].tolist()])
    dead = tuple(int(k) for k in keys[gone])
    rf1 = [dml.Insert("orders", new_orders),
           dml.Insert("lineitem", new_lines)]
    rf2 = [dml.Delete("orders", pred=InSet(Col("o_orderkey"), dead)),
           dml.Delete("lineitem", pred=InSet(Col("l_orderkey"), dead))]
    return rf1, rf2


@pytest.fixture(scope="module")
def refreshed():
    """One service applies the pair, then answers every query; the oracle
    is a fresh database over the tables a ``MutableTable`` yields after
    the same mutations."""
    tables = tpch.generate(sf=SF, seed=SEED)
    served = PimDatabase(tables)
    rf1, rf2 = _refresh_pair(tables)
    mirror = {rel: dml.MutableTable(tables[rel])
              for rel in ("orders", "lineitem")}
    for m in rf1 + rf2:
        mirror[m.relation].apply(m)

    async def run():
        answers = {}
        async with QueryService(served, max_window=1,
                                cache_capacity=0) as svc:
            await svc.apply(rf1)
            await svc.apply(rf2)
            for spec in queries.all_queries():
                try:
                    answers[spec.name] = await svc.submit(spec)
                except Exception as e:       # raised by its own case
                    answers[spec.name] = e
        return answers

    answers = asyncio.run(run())
    oracle = PimDatabase({**tables, **{rel: t.columns()
                                       for rel, t in mirror.items()}})
    return served, answers, oracle


@pytest.mark.parametrize("qname", [q.name for q in queries.all_queries()])
def test_submit_after_refresh_matches_oracle(refreshed, qname):
    served, answers, oracle = refreshed
    got = answers[qname]
    if isinstance(got, Exception):
        raise got
    spec = queries.get_query(qname)
    want = oracle.execute(spec, engine=Engine.ORACLE)
    assert got.rows == want.rows
    assert got.aggregates == want.aggregates
    if spec.host is not None:
        return
    for rel in spec.filters:
        mask = np.asarray(got.relations[rel].mask)
        if rel in ("orders", "lineitem"):
            # Served masks are in slot order; read them in logical order.
            d = served.dml_state(rel)
            mask = mask[[d.slot_of[i] for i in d.live_ids()]]
        np.testing.assert_array_equal(mask, want.relations[rel].mask,
                                      err_msg=rel)


# --------------------------------------------------------------------------
# Trace replay (python -m repro.serve.replay)
# --------------------------------------------------------------------------
def test_parse_trace_repeats_and_blank_tokens():
    specs = parse_trace(" Q1, Q6x3,,Q3 ,")
    assert [s.name for s in specs] == ["Q1", "Q6", "Q6", "Q6", "Q3"]


def test_parse_trace_unknown_query_raises():
    with pytest.raises(KeyError):
        parse_trace("Q1,Q99")


def test_serve_trace_matches_sequential_execute(db):
    specs = parse_trace("Q1,Q6x2,Q14,Q3")
    results, stats, wall = serve_trace(db, specs, concurrency=4,
                                       max_window=4)
    assert len(results) == len(specs) and wall > 0
    assert stats["completed"] == len(specs) and stats["errors"] == 0
    for spec, got in zip(specs, results):
        want = db.execute(spec)
        assert got.rows == want.rows, spec.name
        assert got.aggregates == want.aggregates, spec.name


# --------------------------------------------------------------------------
# 8-device mesh subprocess smoke test
# --------------------------------------------------------------------------
def test_serve_mesh_8dev_smoke():
    run_forced_multidevice("""
        import asyncio, jax
        from repro.db import queries, tpch
        from repro.db.database import PimDatabase
        from repro.serve import QueryService

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        tables = tpch.generate(sf=0.002, seed=123)
        db1 = PimDatabase(tables)
        dbm = PimDatabase(tables, mesh=mesh)

        specs = [queries.get_query(n)
                 for n in ("Q1", "Q6", "Q14", "Q6", "Q1")]
        want = [db1.execute(s) for s in specs]

        async def main():
            async with QueryService(dbm, max_window=3,
                                    max_wait_s=0.005) as svc:
                res = await asyncio.gather(*[svc.submit(s) for s in specs])
                return res, svc.stats()

        res, stats = asyncio.run(main())
        for s, got, exp in zip(specs, res, want):
            if s.host is not None:
                assert got.rows == exp.rows, s.name
            else:
                assert got.aggregates == exp.aggregates, s.name
        assert stats["errors"] == 0
        assert stats["coalesced"] == 2
        print("mesh serve smoke OK:", stats["dispatches"], "dispatches")
    """)
