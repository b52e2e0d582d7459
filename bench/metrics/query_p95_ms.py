"""95th percentile of every query latency in the window, in ms, timed by
the client from submission to the answer."""
from stats import percentile


def read(rec):
    lat = [1e3 * s["latency_s"] for s in rec["served"]]
    return percentile(lat, 95) if lat else None
