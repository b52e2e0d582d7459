"""Program spans (``repro.obs``): what a profiler session records from
``execute``, the split-phase batch path, the service and DML, and that
spans change no answer and record nothing without a session."""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import dml, obs
from repro.db import queries, tpch
from repro.db.database import PimDatabase
from repro.serve import QueryService


@pytest.fixture(scope="module")
def db():
    return PimDatabase(tpch.generate(sf=0.002, seed=123))


def _start(path):
    # The benchmark's profiler options: device ops and annotations only.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)


def _spans(path):
    """Every ``pimdb.*`` event of the trace under ``path``: dicts with
    name, start, end, attrs and the name of its parent on its thread."""
    (xplane,) = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                          recursive=True)
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((ev.start_ns, -ev.duration_ns, ev)
                          for ev in line.events
                          if ev.name.startswith(obs.PREFIX)),
                         key=lambda t: t[:2])
            stack = []
            for start, neg, ev in evs:
                while stack and stack[-1]["end"] <= start:
                    stack.pop()
                sp = {"name": ev.name[len(obs.PREFIX):], "start": start,
                      "end": start - neg, "attrs": dict(ev.stats),
                      "parent": stack[-1]["name"] if stack else None}
                out.append(sp)
                stack.append(sp)
    return out


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _answer(res):
    return (res.aggregates, res.rows,
            {r: np.asarray(run.mask) for r, run in res.relations.items()})


def _same(a, b):
    assert a[0] == b[0] and a[1] == b[1]
    assert a[2].keys() == b[2].keys()
    for r in a[2]:
        np.testing.assert_array_equal(a[2][r], b[2][r])


def test_spans_of_execute_batch_and_apply(db, tmp_path):
    q6, q3 = queries.get_query("Q6"), queries.get_query("Q3")
    db.execute(q6)                       # warm: the traced Q6 hits the LRU
    db.execute(q3)
    n_lineitem = db.relations["lineitem"].n_records
    _start(tmp_path)
    try:
        r6 = db.execute(q6)
        db.execute(q3)
        pendings, _ = db.dispatch_batch([q6, q3])
        [db.finish_query(p) for p in pendings]
        take = {a: np.asarray(c[:8])
                for a, c in db.tables["lineitem"].items()}
        db.apply([dml.Insert("lineitem", take)])
        with obs.span("test.report"):    # marks the report's stretch
            db.report(r6)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    names = {s["name"] for s in spans}
    assert {"execute", "compile", "prepare", "dispatch", "readback",
            "mat_readback", "unpack", "relation_stats", "link", "demux",
            "host_stage", "dml.mutate", "dml.publish"} <= names

    ex = _named(spans, "execute")
    assert [s["attrs"]["q"] for s in ex] == ["Q6", "Q3"]
    # Q6's path: its children account for it, each carrying its query.
    q6_kids = {s["name"] for s in spans if s["parent"] == "execute"
               and ex[0]["start"] <= s["start"] < ex[0]["end"]}
    assert {"compile", "prepare", "dispatch", "readback",
            "unpack"} <= q6_kids
    unpack = _named(spans, "unpack")
    assert unpack[0]["parent"] == "execute"
    assert unpack[0]["attrs"]["q"] == "Q6"
    assert unpack[0]["attrs"]["rel"] == "lineitem"
    assert unpack[0]["attrs"]["records"] == n_lineitem
    assert all(s["attrs"]["bytes"] > 0 for s in _named(spans, "readback"))
    assert all(s["attrs"]["hit"] == 1 for s in _named(spans, "prepare")
               if s["attrs"]["q"] == "Q6")
    assert all(s["attrs"]["instrs"] > 0 for s in _named(spans, "compile"))
    # The cost report's statistics are computed when the report reads
    # them, never on the served path: not under any execute or demux.
    stats = _named(spans, "relation_stats")
    served = _named(spans, "execute") + _named(spans, "demux")
    assert not any(e["start"] <= s["start"] < e["end"]
                   for s in stats for e in served)
    assert [s["parent"] for s in stats] == ["test.report"]
    assert stats[0]["attrs"]["rel"] == "lineitem"
    assert all(s["attrs"]["conjuncts"] >= 1 for s in stats)
    mat = _named(spans, "mat_readback")
    assert mat and all(s["attrs"]["rows"] >= 0 for s in mat)

    # The batch: per-spec compiles, one link per relation under the
    # batch's names, the demux with the unpack inside, the host stage.
    link = _named(spans, "link")
    assert link and all(s["attrs"]["q"] == "Q6+Q3" for s in link)
    lineitem = [s for s in link if s["attrs"]["rel"] == "lineitem"][0]
    assert lineitem["attrs"]["programs"] == 2
    assert lineitem["attrs"]["deduped"] >= 0
    (demux,) = _named(spans, "demux")
    assert demux["attrs"]["queries"] == 2
    assert unpack[-1]["parent"] == "demux"
    hosts = _named(spans, "host_stage")
    assert [s["attrs"]["q"] for s in hosts] == ["Q3", "Q3"]
    assert hosts[-1]["parent"] is None           # finish_query, no execute
    assert all(s["attrs"]["rows_in"] > 0 and s["attrs"]["rows_out"] >= 0
               for s in hosts)

    (mutate,) = _named(spans, "dml.mutate")
    assert mutate["attrs"]["rel"] == "lineitem"
    assert mutate["attrs"]["rows"] == 8
    assert mutate["attrs"]["cells_written"] > 0
    (publish,) = _named(spans, "dml.publish")
    assert publish["attrs"]["rel"] == "lineitem"
    assert publish["attrs"]["rows"] == len(
        db.dml_state("lineitem").live_ids())


def test_service_spans_on_its_workers(tmp_path):
    db = PimDatabase(tpch.generate(sf=0.002, seed=123))
    q6, q3 = queries.get_query("Q6"), queries.get_query("Q3")
    take = {a: np.asarray(c[:4]) for a, c in db.tables["orders"].items()}

    async def run():
        async with QueryService(db, max_window=2, max_wait_s=0.05,
                                cache_capacity=0) as svc:
            await asyncio.gather(svc.submit(q6), svc.submit(q3))
            await svc.apply([dml.Insert("orders", take)])

    _start(tmp_path)
    try:
        asyncio.run(run())
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    (window,) = _named(spans, "serve.window")
    assert window["attrs"]["n"] == 2
    assert window["attrs"]["q"] == "Q6+Q3"
    assert window["attrs"]["queued_s"] >= window["attrs"]["max_queued_s"] > 0
    assert _named(spans, "link")[0]["parent"] == "serve.window"
    (host,) = _named(spans, "serve.host")
    assert host["attrs"]["q"] == "Q3" and host["attrs"]["queued_s"] >= 0
    assert _named(spans, "host_stage")[0]["parent"] == "serve.host"
    (apply,) = _named(spans, "serve.apply")
    assert apply["attrs"]["queued_s"] >= 0
    assert _named(spans, "dml.mutate")[0]["parent"] == "serve.apply"


def test_span_without_a_session_records_nothing(tmp_path):
    assert not TraceAnnotation.is_enabled()
    with obs.query("Q0"), obs.span("unseen", rel="x") as sp:
        sp.set_metadata(rows=1)
    _start(tmp_path)
    try:
        with obs.span("seen", n=1):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [s["name"] for s in _spans(tmp_path)] == ["seen"]


def test_query_names_nest_and_restore():
    with obs.query("Q1"):
        with obs.query("Q1+Q6"):
            assert obs.current_query.get() == "Q1+Q6"
        assert obs.current_query.get() == "Q1"
    assert obs.current_query.get() is None


def test_answers_bit_equal_with_and_without_a_session(db, tmp_path):
    specs = [queries.get_query(n) for n in ("Q1", "Q6", "Q3")]
    plain = [_answer(db.execute(s)) for s in specs]
    _start(tmp_path)
    try:
        traced = [_answer(db.execute(s)) for s in specs]
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain, traced):
        _same(a, b)
