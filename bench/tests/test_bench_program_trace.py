"""The reduction of the program's own spans in a profiler trace, and the
shares read from them (``program_trace.py``)."""
import gzip
import json
import os

import pytest

import harness
import program_trace as pt
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def v5e_trace(tmp_path):
    with open(os.path.join(DATA, "v5e_q6_q1.json")) as f:
        meta = json.load(f)
    path = tmp_path / "v5e.xplane.pb"
    with gzip.open(os.path.join(DATA, "v5e_q6_q1.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path), meta


def test_recorded_v5e_trace_keeps_device_numbers_and_has_no_program_spans(
        v5e_trace):
    """The trace was recorded before the program had spans: it reduces to
    the harness's numbers exactly, and to no program spans."""
    path, meta = v5e_trace
    plain = tr.reduce_file(path, meta["spans"], meta["window_start_host"])
    out = pt.reduce_file(path, meta["spans"], meta["window_start_host"])
    for k in ("busy_s", "window_s", "idle_share", "device_ops", "idle_gaps",
              "n_devices", "lines"):
        assert out[k] == plain[k], k
    assert out["program"] == {}
    assert pt.read_program(path) == {}


# Program spans on two thread lines: an execute with three children on
# one, a worker's window on another; times in seconds.
PROGRAM = {
    "/host:CPU#0": [
        ("pimdb.execute", 1.0, 5.0, {"q": "Q6"}),
        ("pimdb.prepare", 1.0, 1.5, {"q": "Q6", "rel": "lineitem", "hit": 1}),
        ("pimdb.readback", 2.0, 3.0, {"q": "Q6", "bytes": 100}),
        ("pimdb.unpack", 3.5, 4.5, {"q": "Q6", "records": 7}),
        ("pimdb.execute", 8.0, 12.0, {"q": "Q1"}),     # cut by the window
        ("pimdb.unpack", 11.0, 11.5, {"q": "Q1", "records": 3}),
        ("pimdb.execute", 20.0, 21.0, {"q": "Q1"}),    # outside it
    ],
    "/host:CPU#1": [
        ("pimdb.serve.window", 0.5, 6.0, {"n": 2, "queued_s": 0.25}),
        ("pimdb.prepare", 2.5, 3.0, {"rel": "orders", "hit": 0}),
    ],
}


def test_program_spans_self_time_attribute_sums_and_clipping():
    out = pt.program_spans(PROGRAM, (0.0, 10.0))
    ex = out["pimdb.execute"]
    assert ex["n"] == 2
    assert ex["total_s"] == pytest.approx(4.0 + 2.0)
    # The first execute less its three children; the second's part in
    # the window, its unpack outside.
    assert ex["self_s"] == pytest.approx((4.0 - 0.5 - 1.0 - 1.0) + 2.0)
    assert ex["attrs"] == {}                  # strings are not summed
    assert out["pimdb.unpack"]["n"] == 1
    assert out["pimdb.unpack"]["attrs"] == {"records": 7}
    pre = out["pimdb.prepare"]
    assert pre["n"] == 2 and pre["attrs"] == {"hit": 1}
    assert pre["self_s"] == pytest.approx(1.0)
    assert out["pimdb.readback"]["attrs"] == {"bytes": 100}
    win = out["pimdb.serve.window"]
    assert win["total_s"] == pytest.approx(5.5)
    assert win["self_s"] == pytest.approx(5.0)   # its own line's child only
    assert win["attrs"] == {"n": 2, "queued_s": 0.25}


# Window 0-10 ms; the device busy 2-3 ms (in readback). On the host:
# execute 1-5 ms with prepare 1-1.5, readback 2-3, unpack 3.5-4.5.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 2000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 4000000000
             stats { metadata_id: 1 str_value: "Q6" } }
    events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 500000000
             stats { metadata_id: 2 int64_value: 1 } }
    events { metadata_id: 4 offset_ps: 2000000000 duration_ps: 1000000000
             stats { metadata_id: 3 int64_value: 4096 } }
    events { metadata_id: 5 offset_ps: 3500000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
  event_metadata { key: 2 value { id: 2 name: "pimdb.execute" } }
  event_metadata { key: 3 value { id: 3 name: "pimdb.prepare" } }
  event_metadata { key: 4 value { id: 4 name: "pimdb.readback" } }
  event_metadata { key: 5 value { id: 5 name: "pimdb.unpack" } }
  stat_metadata { key: 1 value { id: 1 name: "q" } }
  stat_metadata { key: 2 value { id: 2 name: "hit" } }
  stat_metadata { key: 3 value { id: 3 name: "bytes" } }
}
"""
#: The client's span, 0.5 ms before and after execute, on its own clock,
#: and the window's start on that clock.
CLIENT = [("execute Q6", 100.0005, 100.0055)]
WINDOW_START_HOST = 100.0


@pytest.fixture
def small_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


def test_idle_gaps_under_a_client_span_go_to_the_innermost_program_span(
        small_trace):
    """Idle time is labelled with the program's spans, which nest inside
    the client's: each idle second goes to the innermost. The device's
    numbers and the idle total are the harness's own."""
    (line,) = pt.read_program(small_trace).values()
    assert [n for n, _, _, _ in line] == [
        "pimdb.execute", "pimdb.prepare", "pimdb.readback", "pimdb.unpack"]
    assert line[0][3] == {"q": "Q6"} and line[2][3] == {"bytes": 4096}
    out = pt.reduce_file(small_trace, CLIENT, WINDOW_START_HOST)
    plain = tr.reduce_file(small_trace, CLIENT, WINDOW_START_HOST)
    for k in ("busy_s", "window_s", "idle_share", "device_ops"):
        assert out[k] == plain[k], k
    assert out["busy_s"] == pytest.approx(1e-3)
    assert out["idle_share"] == pytest.approx(0.9)
    assert dict(plain["idle_gaps"]) == pytest.approx({
        "execute Q6": 4e-3, "outside_spans": 5e-3})
    assert dict(out["idle_gaps"]) == pytest.approx({
        "execute Q6": 1e-3, "pimdb.prepare": 0.5e-3,
        "pimdb.execute": 1.5e-3, "pimdb.unpack": 1e-3,
        "outside_spans": 5e-3})
    prog = out["program"]
    assert prog["pimdb.execute"]["self_s"] == pytest.approx(1.5e-3)
    assert prog["pimdb.readback"]["attrs"] == {"bytes": 4096}
    assert prog["pimdb.prepare"]["attrs"] == {"hit": 1}


def _program(**spans):
    return {f"pimdb.{name}": {"n": 1, "total_s": t, "self_s": t / 10,
                              "attrs": attrs}
            for name, (t, attrs) in spans.items()}


SERVED = [{"latency_s": 0.5, "pim_s": 0.1, "host_s": 0.2,
           "cached": False}] * 4                          # 2 s of latency


def test_shares_of_the_program_spans():
    program = _program(**{
        "execute": (1.9, {}), "unpack": (0.3, {"records": 9}),
        "relation_stats": (0.5, {"conjuncts": 4}),
        "compile": (0.04, {"instrs": 50}), "prepare": (0.06, {"hit": 4}),
        "serve.window": (1.0, {"n": 4, "queued_s": 0.8}),
        "serve.apply": (4.0, {"queued_s": 0.1}),
        "dml.publish": (1.0, {"rows": 10})})
    rec = {"served": SERVED, "trace": {
        "busy_s": 0.5, "window_s": 2.5, "program": program,
        "idle_gaps": [["pimdb.unpack", 1.2], ["execute Q6", 0.4],
                      ["pimdb.execute", 0.3], ["outside_spans", 0.1]]}}
    got = {cell: {k: v["value"] for k, v in pt.shares(rec, cell).items()}
           for cell in ("sf1-power-array", "sf1-throughput-rf")}
    assert got == {
        "sf1-power-array": pytest.approx({
            "array.unpack_share.power": 15.0,
            "array.stats_share.power": 25.0,
            "compiler.prepare_share.power": 5.0}),
        "sf1-throughput-rf": pytest.approx({
            "serve.queue_share.throughput": 40.0,
            "dml.publish_share.throughput": 25.0})}
    assert all(v["unit"] == "%" for v in
               pt.shares(rec, "sf1-power-array").values())
    assert pt.cover(rec) == pytest.approx({
        "execute_of_latency": 95.0, "execute_self_of_latency": 9.5,
        "idle_under_program_at_least": 75.0})


@pytest.mark.parametrize("trace", [None, {"busy_s": 0.1, "idle_share": 0.9,
                                          "program": {}}])
def test_shares_without_spans_read_nothing(trace):
    """No trace, or a trace of a program without spans: nothing to read."""
    rec = {"served": SERVED[:1]}
    if trace is not None:
        rec["trace"] = trace
    assert pt.shares(rec, "sf1-power-array") == {}
    assert pt.shares(rec, "sf1-throughput-rf") == {}
    assert all(read(rec) is None for _, read in pt.SHARES.values())


def test_run_reduces_with_the_program_spans_and_restores_the_harness(
        monkeypatch, small_trace):
    """``run`` goes through ``harness.run``; inside it the trace reduction
    reads the program's spans and the metrics gain the shares, and both
    are the harness's own again afterwards."""
    reduce_file, metrics = tr.reduce_file, harness._metrics

    def fake_run(cell, seed, seconds, trace, t_start):
        rec = {"served": SERVED, "trace": tr.reduce_file(
            small_trace, CLIENT, WINDOW_START_HOST)}
        return {"correct": True, "metrics": harness._metrics([], rec)}

    monkeypatch.setattr(harness, "run", fake_run)
    out = pt.run({"name": "sf1-power-array"}, 1, 1.0, 0.0)
    assert tr.reduce_file is reduce_file and harness._metrics is metrics
    assert out["program"]["pimdb.unpack"]["total_s"] == pytest.approx(1e-3)
    assert out["metrics"]["array.unpack_share.power"]["value"] == \
        pytest.approx(100.0 * 1e-3 / 2.0)
    assert set(out["metrics"]) == {"array.unpack_share.power"}
    assert out["cover"]["execute_of_latency"] == pytest.approx(0.2)
    assert out["cover"]["idle_under_program_at_least"] == pytest.approx(
        100.0 * 3e-3 / 9e-3)
