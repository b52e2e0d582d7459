"""The benchmark harness: one run of one cell, driven by data.

``run.py`` calls :func:`main`. A run reads its cell from
``BENCHMARK.json`` and from the files the cell's names point to, and
finds its code by the same names:

* ``bench/workloads/<cell>.json``: the configuration, the traffic kind
  and the traffic's parameters;
* ``bench/configs/<config>.json``: the deployment (scale factor, chips,
  guarantees, what was cut from the source);
* ``bench/traffic/<kind>.py``: the generator of that kind of traffic,
  with ``warm(ctx)`` (set-up), ``window(ctx)`` (the measured traffic),
  ``release(ctx)`` (free what the program holds) and ``check(ctx)``
  (the comparison with the reference, ``{number: {value, limit}}``);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(record)``
  returning a number or ``None`` where the run has nothing to read;
* ``bench/peaks.json``: the device's peaks, by ``device_kind``.

A new configuration, traffic mix or metric is therefore a new file and
a new entry in ``BENCHMARK.json``; no file here changes.

The run: check the device (a TPU, as many chips as the cell asks; else
exit non-zero with no result), generate the data from ``--seed``, load
it into the program, warm the cell's programs (all of this is
``setup_s``), run the traffic for ``--seconds`` (under the profiler
with ``--trace 1``), read the device's peak memory, free the program's
state, compare the sampled answers with the reference, and print the
result line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

import system
import trace_reduce
from reference import tpch

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: JAX's event around every executable it builds or loads: each miss of
#: its in-memory caches, a trace and lowering followed by an XLA compile
#: or a load from the persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The machine lacks what the cell needs; the run prints no result."""


# --------------------------------------------------------------------------
# Reading the benchmark's data
# --------------------------------------------------------------------------
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, parameters and metrics."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    bench = os.path.join(root, "bench")
    workload = _json(os.path.join(bench, "workloads", f"{name}.json"))
    config = _json(os.path.join(bench, "configs", f"{cell['config']}.json"))
    if workload["config"] != cell["config"]:
        raise ValueError(f"{name}: workload file names configuration "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{cell['config']!r}")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {"name": name, "chips": int(cell["chips"]), "cell": cell,
            "workload": workload, "config": config,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {path}")
    mod_name = f"bench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, root: str = ROOT) -> dict:
    """The peaks of one ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]


# --------------------------------------------------------------------------
# Device
# --------------------------------------------------------------------------
def device_check(jax_module, chips: int) -> list:
    """The cell's devices, or ``NoDevice`` where JAX finds no TPU or
    fewer chips than the cell asks for. Never falls back to the CPU."""
    devices = jax_module.devices()
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise NoDevice(f"JAX finds no TPU (first device: {plat}); this "
                       "benchmark runs on a TPU only")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------
class Context:
    """What a traffic module works with: the cell, the seed, the program
    (``P``, from ``system.load_program``), the data and the record it
    fills. ``span(name)`` records what the client is doing, for the
    trace's idle-gap labels."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.params = cell["workload"]["params"]
        self.config = cell["config"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = trace
        self.P = None
        self.tables = None
        self.db = None
        self.state: dict = {}
        self.spans: List[tuple] = []
        self.record: dict = {"attempted": 0, "failed": 0, "served": [],
                             "refreshes": [], "counters": {}}

    def rng(self, *stream: int):
        """A generator drawn from the seed, one per named stream of
        choices, so that each choice is the same however the others
        fell."""
        import numpy as np

        return np.random.default_rng([self.seed & (2 ** 64 - 1), *stream])

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if self.trace:
                self.spans.append((name, t0, time.perf_counter()))


class CompileCounter:
    """Counts JAX's ``COMPILE_EVENT`` from the moment it is made."""

    def __init__(self, jax_module):
        self.n = 0
        jax_module.monitoring.register_event_duration_secs_listener(
            self._seen)

    def _seen(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def _metrics(entries: List[dict], record: dict) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    devices = device_check(jax, cell["chips"])
    kind = devices[0].device_kind
    peaks = peaks_for(kind)
    compiles = CompileCounter(jax)
    ctx = Context(cell, seed, seconds, trace)
    ctx.P = system.load_program()
    ctx.tables = tpch.generate(float(ctx.config["scale_factor"]), ctx.seed)
    ctx.db = ctx.P.database.PimDatabase(ctx.tables)
    system.block(ctx.db)
    traffic = load_module("traffic", cell["workload"]["traffic_kind"])
    traffic.warm(ctx)
    rec = ctx.record
    rec["setup_s"] = time.perf_counter() - t_start
    rec["peaks"] = peaks

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            # Device operations and the benchmark's own annotations only:
            # tracing every Python call would slow the host it measures.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_window = time.perf_counter()
        compiles0 = compiles.n
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            traffic.window(ctx)
        rec["counters"]["compiles_in_window"] = compiles.n - compiles0
        if trace:
            jax.profiler.stop_trace()
            rec["trace"] = trace_reduce.reduce_file(
                trace_reduce.find_xplane(trace_dir), ctx.spans, t_window)
            print(f"trace device lines: {rec['trace']['lines']}",
                  file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    peak = memory_peak(devices)
    traffic.release(ctx)
    ctx.db = None
    gc.collect()
    checks = traffic.check(ctx)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    entries = cell["per_layer"] if trace else cell["end_to_end"]
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": _metrics(entries, rec),
           "device": {"platform": devices[0].platform, "kind": kind,
                      "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        t = rec["trace"]
        out["device"]["busy_s"] = t["busy_s"]
        out["device"]["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The compile cache sits at one fixed path inside the checkout, so
    # only the first run of a cell there compiles; every program is kept,
    # however short its compile, so later runs compile nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    try:
        cell = load_cell(args.workload)
    except (FileNotFoundError, KeyError) as e:
        print(f"bench: no cell {args.workload}: {e!r}", file=sys.stderr)
        return 2
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except system.NoProgram as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
