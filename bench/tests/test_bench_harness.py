"""The harness's arithmetic and refusals, on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import harness
import roofline
import stats
from reference import queries, tpch

ROOT = harness.ROOT


def test_geomean_is_over_every_latency():
    assert stats.geomean([1.0, 100.0]) == pytest.approx(10.0)
    assert stats.geomean([2.0, 2.0, 2.0, 16.0]) == pytest.approx(
        (2 * 2 * 2 * 16) ** 0.25)
    with pytest.raises(ValueError):
        stats.geomean([1.0, 0.0])


def test_p95_is_over_every_request():
    lat = list(range(1, 101))           # 1..100
    assert stats.percentile(lat, 95) == pytest.approx(95.05)
    assert stats.percentile(lat, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile(lat[::-1], 95) == pytest.approx(95.05)


def test_spread_uses_statistics_quartiles():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def _record(**kw):
    rec = {"served": [], "refreshes": [], "counters": {}, "window_s": 2.0,
           "setup_s": 30.0, "peaks": {"hbm_bytes_per_s": 1e9}}
    rec.update(kw)
    return rec


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def test_rates_are_over_the_whole_window():
    served = [{"latency_s": 0.5, "pim_s": 0.1, "host_s": 0.2,
               "floor_bytes": 10 ** 8, "cached": False}] * 6
    refreshes = [{"rows": 300, "apply_s": 0.4, "cells_written": 3000},
                 {"rows": 100, "apply_s": 0.2, "cells_written": 200}]
    rec = _record(served=served, refreshes=refreshes,
                  trace={"busy_s": 0.8, "idle_share": 0.75})
    assert _read("queries_per_s", rec) == pytest.approx(3.0)
    assert _read("stream_queries_per_s", rec) == pytest.approx(3.0)
    assert _read("refresh_rows_per_s", rec) == pytest.approx(200.0)
    assert _read("query_geomean_ms", rec) == pytest.approx(500.0)
    assert _read("query_p95_ms", rec) == pytest.approx(500.0)
    assert _read("stream_query_p95_ms", rec) == pytest.approx(500.0)
    assert _read("array.pim_share.power", rec) == pytest.approx(20.0)
    assert _read("host.host_share.throughput", rec) == pytest.approx(40.0)
    assert _read("serve.wait_share.throughput", rec) == pytest.approx(40.0)
    assert _read("dml.apply_ms", rec) == pytest.approx(300.0)
    assert _read("dml.cells_written_per_row", rec) == pytest.approx(8.0)
    assert _read("device.idle_share", rec) == pytest.approx(75.0)
    assert _read("device.idle_share.throughput", rec) == pytest.approx(75.0)
    # 6e8 floor bytes at 1e9 B/s need 0.6 s; the device was busy 0.8 s.
    assert _read("relation_program_roofline", rec) == pytest.approx(75.0)
    assert _read("setup_s", rec) == 30.0


def test_readers_with_nothing_to_read_return_nothing():
    rec = _record()
    for name in ("queries_per_s", "query_geomean_ms", "query_p95_ms",
                 "stream_queries_per_s", "stream_query_p95_ms",
                 "device.idle_share.throughput",
                 "program.compiles_in_window.throughput",
                 "refresh_rows_per_s", "relation_program_roofline",
                 "device.idle_share", "dml.apply_ms",
                 "serve.wait_share.throughput",
                 "program.compiles_in_window"):
        assert _read(name, rec) is None, name


def test_every_metric_in_the_benchmark_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert harness.load_module(
            "traffic", cell["workload"]["traffic_kind"]).warm
        moved = {m["moves"] for m in cell["per_layer"]}
        assert moved <= {m["name"] for m in cell["end_to_end"]}


def test_floor_bytes_count_each_plane_once():
    t = {"lineitem": {"l_shipdate": np.array([0, 2555, 7]),
                      "l_discount": np.array([10, 0, 1]),
                      "l_quantity": np.array([1, 50, 3]),
                      "l_extendedprice": np.array([5, 6, 7])}}
    q6 = queries.all_queries()["Q6"]
    # shipdate 12 bits, discount 4, quantity 6, extendedprice 3, valid 1;
    # three rows fill one 32-bit word per plane.
    assert roofline.floor_bytes(q6, t) == (12 + 4 + 6 + 3 + 1) * 1 * 4
    big = {"lineitem": {a: np.resize(v, 65) for a, v in t["lineitem"].items()}}
    assert roofline.floor_bytes(q6, big) == 26 * 3 * 4


def test_floor_bytes_of_a_three_relation_query():
    t = tpch.generate(0.001, 5)
    q21 = queries.all_queries()["Q21"]
    want = 0
    for rel, attrs in (("supplier", ["s_nationkey"]),
                       ("orders", ["o_orderstatus"]),
                       ("lineitem", ["l_receiptdate", "l_commitdate"])):
        n = len(t[rel][attrs[0]])
        bits = 1 + sum(int(t[rel][a].max()).bit_length() for a in attrs)
        want += bits * -(-n // 32) * 4
    assert roofline.floor_bytes(q21, t) == want


def test_peaks_refuse_an_unknown_device_kind():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def test_device_check_refuses_the_cpu():
    import jax

    with pytest.raises(harness.NoDevice):
        harness.device_check(jax, 1)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "sf1-power-array", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
