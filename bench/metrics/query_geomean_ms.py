"""Geometric mean of every query latency in the window, in ms: TPC-H's
Power@Size statistic."""
from stats import geomean


def read(rec):
    lat = [1e3 * s["latency_s"] for s in rec["served"]]
    return geomean(lat) if lat else None
