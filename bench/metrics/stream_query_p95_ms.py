"""95th percentile of every query latency of the query streams in the
window, in ms, timed by the client from submission to the answer; the
waits behind refreshes count."""
from stats import percentile


def read(rec):
    lat = [1e3 * s["latency_s"] for s in rec["served"]]
    return percentile(lat, 95) if lat else None
