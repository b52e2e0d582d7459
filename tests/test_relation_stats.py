"""The cost report's per-relation statistics (``RelationRun.selectivity``
and ``filter_attr_sels``): computed on first read, equal on every path
to what ``execute`` used to compute on every query, never paid for by
serving a query, and taken over the rows the query read."""
import dataclasses

import numpy as np
import pytest

from repro import dml
from repro.db import database, queries as Q, tpch
from repro.db.compiler import And
from repro.db.database import Engine, PimDatabase, RelationRun

SF = 0.002
# Every spec of the catalog at array-stage scope: host-stage specs run
# their filters through the same path as mask specs.
SPECS = [s if s.host is None else s.filter_only() for s in Q.all_queries()]
IDS = [s.name for s in SPECS]


@pytest.fixture(scope="module")
def db():
    return PimDatabase(tpch.generate(sf=SF, seed=7))


def _eager(cols, pred, mask):
    """The oracle: the statistics as ``execute`` computed them on every
    query, each conjunct's pass fraction over the host columns (1.0
    where NumPy cannot evaluate it) and the mask's."""
    sels = []
    for c in (list(pred.ps) if isinstance(pred, And) else [pred]):
        try:
            sels.append(float(Q.eval_pred(cols, c).mean()))
        except Exception:
            sels.append(1.0)
    return sels, float(mask.mean()) if mask.size else 0.0


def _eagerly_filled(res, tables):
    """``res`` with every RelationRun's statistics given at construction
    by the oracle over ``tables``; reads nothing deferred of ``res``."""
    rels = {}
    for r, rr in res.relations.items():
        sels, sel = _eager(tables[r], res.spec.filters[r], rr.mask)
        rels[r] = dataclasses.replace(rr, selectivity=sel,
                                      filter_attr_sels=sels,
                                      filter_source=None)
    return dataclasses.replace(res, relations=rels)


def _assert_reports_equal(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_equal(getattr(a, f.name), getattr(b, f.name),
                                err_msg=f.name)


def _check_against_oracle(db, res):
    eager = _eagerly_filled(res, db.tables)
    assert res.relations and res.relations.keys() == res.spec.filters.keys()
    for r, rr in res.relations.items():
        assert rr.filter_source is not None       # nothing computed yet
        want = eager.relations[r]
        assert rr.filter_attr_sels == want.filter_attr_sels
        assert all(type(s) is float for s in rr.filter_attr_sels)
        assert type(rr.selectivity) is float
        assert rr.selectivity == want.selectivity
        assert rr.filter_source is None           # columns released
    for sf_scale in (1.0, 1000 / SF):
        _assert_reports_equal(database.cost_report(res, sf_scale),
                              database.cost_report(eager, sf_scale))
        _assert_reports_equal(db.report(res, sf_scale),
                              db.report(eager, sf_scale))


@pytest.mark.parametrize("spec", SPECS, ids=IDS)
def test_fused_single_query_stats_equal_eager(db, spec):
    _check_against_oracle(db, db.execute(spec))


@pytest.mark.parametrize("spec", SPECS, ids=IDS)
def test_eager_engine_stats_equal_eager(db, spec):
    _check_against_oracle(db, db.execute(spec, engine=Engine.EAGER))


def test_batch_stats_equal_eager(db):
    specs = [Q.get_query(n) for n in ("Q1", "Q6", "Q22_sub")] + [
        s for s in SPECS if s.kind == "filter"][:3]
    pendings, _ = db.dispatch_batch(specs)
    for p in pendings:
        _check_against_oracle(db, db.finish_query(p))


def test_serving_never_computes_the_stats(db, monkeypatch):
    q6, q1 = Q.get_query("Q6"), Q.get_query("Q1")
    real, calls = Q.eval_pred, []

    def counted(cols, p):
        calls.append(p)
        return real(cols, p)

    monkeypatch.setattr(Q, "eval_pred", counted)
    r6 = db.execute(q6)
    pendings, _ = db.dispatch_batch([q6, q1])
    b6, b1 = (db.finish_query(p) for p in pendings)
    assert calls == []

    n6 = len(q6.filters["lineitem"].ps)
    db.report(r6)
    assert calls == list(q6.filters["lineitem"].ps)
    db.report(r6)                                  # cached on the run
    assert len(calls) == n6

    db.report(b6)
    assert len(calls) == 2 * n6
    del calls[:]
    db.report(b1)
    assert calls == [q1.filters["lineitem"]]       # Q1's one conjunct
    db.report(b1)
    db.report(b6)
    assert len(calls) == 1


def test_stats_are_over_the_rows_the_query_read():
    # Own database: the DML batch must not reach the shared fixture.
    db = PimDatabase(tpch.generate(sf=SF, seed=11))
    q6 = Q.get_query("Q6")
    pred = q6.filters["lineitem"]
    before = db.tables["lineitem"]
    res = db.execute(q6)
    run = res.relations["lineitem"]
    want = _eager(before, pred, run.mask)

    # Insert copies of the rows Q6 selects, delete rows it rejects.
    hit = Q.eval_pred(before, pred)
    take = {a: np.asarray(c[hit]) for a, c in before.items()}
    miss = np.flatnonzero(~hit)[:200].tolist()
    db.apply([dml.Insert("lineitem", take),
              dml.Delete("lineitem", row_ids=miss)])
    after = db.tables["lineitem"]
    assert after is not before
    assert _eager(after, pred, Q.eval_pred(after, pred)) != want

    rep = db.report(res)
    assert (run.filter_attr_sels, run.selectivity) == want
    _assert_reports_equal(
        rep, db.report(_eagerly_filled(res, {"lineitem": before})))


def test_given_stats_are_kept_and_missing_ones_refused(db):
    mask = np.array([True, False, False, False])
    given = RelationRun(n_records=4, mask=mask, trace=[],
                        filter_attr_bits=[8], agg_attr_bits=[],
                        selectivity=0.75, filter_attr_sels=[0.5])
    assert given.selectivity == 0.75 and given.filter_attr_sels == [0.5]
    bare = RelationRun(n_records=4, mask=mask, trace=[],
                       filter_attr_bits=[8], agg_attr_bits=[])
    with pytest.raises(ValueError, match="filter_source"):
        bare.selectivity
    base = db.run_baseline(Q.get_query("Q6"))
    (rr,) = base.relations.values()
    assert rr.filter_attr_sels == [] and rr.filter_source is None
    assert rr.selectivity == float(rr.mask.mean())
