"""Queries the query streams completed in the window over the window's
seconds: the rate of TPC-H's throughput test, queries and refreshes
sharing the system."""


def read(rec):
    return len(rec["served"]) / rec["window_s"] if rec["served"] else None
