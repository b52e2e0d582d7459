"""The reduction from a profiler trace to busy time, idle share and
labelled idle gaps."""
import gzip
import json
import os

import pytest

import trace_reduce as tr


def test_union_merges_overlaps_and_drops_empties():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2), (5, 6), (5.5, 5.7)]) \
        == [(0, 2), (3, 4), (5, 6)]


def test_gaps_are_the_uncovered_window():
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([(0, 5)], 0, 5) == []


def test_reduce_busy_idle_and_labels():
    dev = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.1", 1.5, 2.5),
                             ("reduce.2", 4.0, 5.0), ("outside", 9.0, 12.0)]}
    spans = [("execute Q6", 0.0, 3.0), ("execute Q1", 3.0, 6.0),
             ("publish", 6.0, 9.5)]
    out = tr.reduce(dev, (0.0, 10.0), spans)
    assert out["busy_s"] == pytest.approx(1.5 + 1.0 + 1.0)
    assert out["window_s"] == 10.0
    assert out["idle_share"] == pytest.approx(1 - 3.5 / 10)
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 2.0, "reduce.2": 1.0, "outside": 1.0})
    # Gaps: [0,1] and [2.5,3] under Q6, [3,4] and [5,6] under Q1,
    # [6,9] under publish.
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"execute Q6": 1.5, "execute Q1": 2.0, "publish": 3.0})


def test_reduce_averages_over_devices_and_labels_unspanned_gaps():
    dev = {"/device:TPU:0": [("a", 0.0, 1.0)],
           "/device:TPU:1": [("a", 0.0, 3.0)]}
    out = tr.reduce(dev, (0.0, 4.0))
    assert out["busy_s"] == pytest.approx(2.0)
    assert out["n_devices"] == 2
    assert dict(out["idle_gaps"]) == pytest.approx({"outside_spans": 2.0})


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        tr.reduce({}, (0.0, 1.0))


# A small trace in the profiler's XSpace form, with the plane and line
# names of a TPU trace: two device ops, the window annotation and one
# client span on the host.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 6000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "reduce.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__run" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
}
"""


def test_trace_file(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    t = tr.read_xplane(str(path))
    assert list(t["devices"]) == ["/device:TPU:0"]
    assert t["window"] == pytest.approx((1e-3, 11e-3))
    # A client span from 3 to 9 ms after the window opened, on the
    # host's own clock, where the window opened at 100.0 s.
    out = tr.reduce_file(str(path), [("execute Q6", 100.003, 100.009)],
                         window_start_host=100.0)
    assert out["busy_s"] == pytest.approx(3e-3)
    assert out["window_s"] == pytest.approx(10e-3)
    assert out["idle_share"] == pytest.approx(0.7)
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 2e-3, "reduce.2": 1e-3})
    # Idle: 0-1 and 3-6 ms (the span covers 3-6), 7-10 ms (span to 9).
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"execute Q6": 5e-3, "outside_spans": 2e-3})
    assert tr.find_xplane(str(tmp_path)) == str(path)


def test_trace_without_an_op_line_reads_programs(tmp_path):
    from jax.profiler import ProfileData

    start = XSPACE.index('  lines {\n    id: 1 name: "XLA Ops"')
    end = XSPACE.index('  lines {\n    id: 2 name: "XLA Modules"')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        XSPACE[:start] + XSPACE[end:]))
    out = tr.reduce_file(str(path))
    assert out["busy_s"] == pytest.approx(6e-3)
    assert dict(out["device_ops"]) == pytest.approx({"jit__run": 6e-3})


# A trace recorded on one TPU v5e: Q6 then Q1 through ``execute`` at SF
# 0.01 under the window annotation, with the client's spans on the
# host's clock (``data/v5e_q6_q1.json``).
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def v5e_trace(tmp_path):
    with open(os.path.join(DATA, "v5e_q6_q1.json")) as f:
        meta = json.load(f)
    path = tmp_path / "v5e.xplane.pb"
    with gzip.open(os.path.join(DATA, "v5e_q6_q1.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path), meta


def test_recorded_v5e_trace(v5e_trace):
    from jax.profiler import ProfileData

    path, meta = v5e_trace
    t = tr.read_xplane(path)
    assert list(t["devices"]) == ["/device:TPU:0"]
    assert "XLA Ops" in t["lines"]["/device:TPU:0"]
    out = tr.reduce_file(path, meta["spans"], meta["window_start_host"])
    want = meta["reduced"]
    for k in ("busy_s", "window_s", "idle_share"):
        assert out[k] == pytest.approx(want[k], rel=1e-9)
    assert dict(out["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))

    # Busy time, read again from the raw events: the union of the
    # device's operations inside the window, each overlap counted once.
    lo, hi = t["window"]
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            evs = [(max(ev.start_ns * 1e-9, lo),
                    min((ev.start_ns + ev.duration_ns) * 1e-9, hi))
                   for ln in plane.lines if ln.name == "XLA Ops"
                   for ev in ln.events]
    inside = sorted((s, e) for s, e in evs if e > s)
    assert inside
    busy, end = 0.0, lo
    for s, e in inside:
        busy += max(0.0, e - max(s, end))
        end = max(end, e)
    assert out["busy_s"] == pytest.approx(busy, rel=1e-9)

    # Every idle second is labelled, by the span over it: the host's
    # spans land on the trace's clock, so each span's idle time is its
    # part of the window less the device's busy time inside it.
    gaps = dict(out["idle_gaps"])
    assert set(gaps) <= {n for n, _, _ in meta["spans"]} | {"outside_spans"}
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - busy)
    shift = lo - meta["window_start_host"]
    for name, s, e in meta["spans"]:
        s, e = max(s + shift, lo), min(e + shift, hi)
        busy_in = sum(max(0.0, min(b, e) - max(a, s)) for a, b in
                      tr.union(inside))
        assert gaps[name] == pytest.approx(e - s - busy_in, rel=1e-6)
