#!/usr/bin/env python3
"""One traced run of a cell, read through the program's own spans.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

From the root of a checkout, on the chip, like ``run.py --trace 1``.

The program marks its stages with profiler spans named ``pimdb.<stage>``
(``src/repro/obs.py``), on the same clock as the device's operations.
The harness's reduction (``trace_reduce.py``) does not read them. This
script runs the cell the harness's way, with ``--trace 1``, through a
reduction that does:

* ``program``: per span name, how many overlap the window, their time
  inside it (``total_s``), that time less their direct children's on the
  same thread line (``self_s``), and the sum of each numeric attribute
  (``attrs``);
* the idle gaps are labelled with the program's spans as well as the
  client's, so the innermost span names each idle second;
* ``busy_s``, ``window_s``, ``idle_share`` and ``device_ops`` are the
  harness's own, from the same functions.

It prints the harness's result line with the cell's shares of ``SHARES``
added to its metrics, then ``program`` and ``cover``: how much of the
client's latency lies under ``pimdb.execute`` and in its self time, and
how much of the device's idle time the listed labels put under a
program span.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple
from unittest import mock

T_START = time.perf_counter()

import harness  # noqa: E402
import trace_reduce  # noqa: E402

#: Name prefix of the program's spans.
PROGRAM_PREFIX = "pimdb."
#: (name, start_s, end_s, {attribute: value}) of one program span.
ProgramSpan = Tuple[str, float, float, Dict[str, object]]


def read_program(path: str) -> Dict[str, List[ProgramSpan]]:
    """The program's spans in one ``.xplane.pb``, per host thread line,
    on the trace's clock."""
    from jax.profiler import ProfileData

    out: Dict[str, List[ProgramSpan]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.setdefault(f"{plane.name}#{i}", []).append(
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         dict(ev.stats)))
    return out


def program_spans(lines: Mapping[str, Sequence[ProgramSpan]],
                  window: Tuple[float, float]) -> Dict[str, dict]:
    """Count, time, self time and attribute sums of the program's spans
    inside the window, by name. Spans on one line nest; a span's parent
    is the innermost span still open at its start."""
    lo, hi = window
    out: Dict[str, dict] = {}
    for evs in lines.values():
        stack: List[Tuple[str, float]] = []      # (name, end) still open
        for name, s, e, attrs in sorted(evs, key=lambda v: (v[1], -v[2])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            part = max(0.0, min(e, hi) - max(s, lo))
            if s < hi and e >= lo:
                st = out.setdefault(name, {"n": 0, "total_s": 0.0,
                                           "self_s": 0.0, "attrs": {}})
                st["n"] += 1
                st["total_s"] += part
                st["self_s"] += part
                for k, v in attrs.items():
                    if isinstance(v, (int, float)):
                        st["attrs"][k] = st["attrs"].get(k, 0) + v
                if stack:
                    out[stack[-1][0]]["self_s"] -= part
            stack.append((name, e))
    return out


def reduce_file(path: str, spans: Sequence[trace_reduce.Interval] = (),
                window_start_host: Optional[float] = None) -> dict:
    """``trace_reduce.reduce_file`` with the program's spans: idle gaps
    labelled by them too, and their table under ``program``."""
    t = trace_reduce.read_xplane(path)
    if t["window"] is None:
        raise ValueError(
            f"no {trace_reduce.WINDOW_SPAN!r} annotation in {path}")
    program = read_program(path)
    shift = 0.0 if window_start_host is None else \
        t["window"][0] - window_start_host
    moved = [(n, s + shift, e + shift) for n, s, e in spans]
    moved += [(n, s, e) for evs in program.values() for n, s, e, _ in evs]
    out = trace_reduce.reduce(t["devices"], t["window"], moved)
    out["lines"] = t["lines"]
    out["program"] = program_spans(program, t["window"])
    return out


# --------------------------------------------------------------------------
# Shares read from the spans, each None where the run has nothing to read
# --------------------------------------------------------------------------
def _latency(rec: dict) -> float:
    return sum(s["latency_s"] for s in rec["served"])


def _of_latency(rec: dict, names: Sequence[str]) -> Optional[float]:
    """Σ time of the spans ``names`` / Σ client latency, in %."""
    spans = rec.get("trace", {}).get("program", {})
    lat = _latency(rec)
    if not lat or not all(PROGRAM_PREFIX + n in spans for n in names):
        return None
    return 100.0 * sum(spans[PROGRAM_PREFIX + n]["total_s"]
                       for n in names) / lat


def _queue_share(rec: dict) -> Optional[float]:
    """Σ ``queued_s`` of the dispatch worker's windows / Σ latency, in %."""
    spans = rec.get("trace", {}).get("program", {})
    lat = _latency(rec)
    if not lat or "pimdb.serve.window" not in spans:
        return None
    return 100.0 * spans["pimdb.serve.window"]["attrs"].get(
        "queued_s", 0.0) / lat


def _publish_share(rec: dict) -> Optional[float]:
    """Σ ``dml.publish`` / Σ ``serve.apply``, in %."""
    spans = rec.get("trace", {}).get("program", {})
    if "pimdb.dml.publish" not in spans or "pimdb.serve.apply" not in spans:
        return None
    apply_s = spans["pimdb.serve.apply"]["total_s"]
    return 100.0 * spans["pimdb.dml.publish"]["total_s"] / apply_s \
        if apply_s else None


#: Each share of a layer's work that the program's spans measure, with
#: the cell it belongs to: the unpacking of the array stage's outputs,
#: the cost report's statistics, compiling and preparing programs, the
#: wait for the dispatch worker, and publishing a refresh.
SHARES: Dict[str, Tuple[str, Callable[[dict], Optional[float]]]] = {
    "array.unpack_share.power":
        ("sf1-power-array", lambda rec: _of_latency(rec, ["unpack"])),
    "array.stats_share.power":
        ("sf1-power-array", lambda rec: _of_latency(rec, ["relation_stats"])),
    "compiler.prepare_share.power":
        ("sf1-power-array",
         lambda rec: _of_latency(rec, ["compile", "prepare"])),
    "serve.queue_share.throughput": ("sf1-throughput-rf", _queue_share),
    "dml.publish_share.throughput": ("sf1-throughput-rf", _publish_share),
}


def shares(rec: dict, cell: str) -> Dict[str, dict]:
    """The cell's shares that read a number, in the result line's form."""
    out = {}
    for name, (of, read) in SHARES.items():
        value = read(rec) if of == cell else None
        if value is not None:
            out[name] = {"value": value, "unit": "%"}
    return out


def cover(rec: dict) -> dict:
    """How much the program's spans account for, in %: ``execute`` and
    its self time of Σ client latency, and the device's idle time that
    the listed idle-gap labels (the largest ``trace_reduce.TOP``) put
    under a program span, a lower bound."""
    t = rec["trace"]
    out = {}
    ex = t["program"].get("pimdb.execute")
    lat = _latency(rec)
    if ex and lat:
        out["execute_of_latency"] = 100.0 * ex["total_s"] / lat
        out["execute_self_of_latency"] = 100.0 * ex["self_s"] / lat
    idle = t["window_s"] - t["busy_s"]
    if idle > 0:
        out["idle_under_program_at_least"] = 100.0 * sum(
            v for k, v in t["idle_gaps"]
            if k.startswith(PROGRAM_PREFIX)) / idle
    return out


def run(cell: dict, seed: int, seconds: float, t_start: float) -> dict:
    """``harness.run`` with ``--trace 1``, reduced with the program's
    spans; the result line's object with ``program`` and ``cover``."""
    kept: dict = {}
    metrics = harness._metrics

    def with_shares(entries, record):
        kept["record"] = record
        return {**metrics(entries, record),
                **shares(record, cell["name"])}

    with mock.patch.object(trace_reduce, "reduce_file", reduce_file), \
            mock.patch.object(harness, "_metrics", with_shares):
        out = harness.run(cell, seed, seconds, True, t_start)
    rec = kept["record"]
    out["program"] = rec["trace"]["program"]
    out["cover"] = cover(rec)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell traced, read through the "
                    "program's spans.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    try:
        out = run(harness.load_cell(args.workload), args.seed,
                  args.seconds, T_START)
    except harness.NoDevice as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
