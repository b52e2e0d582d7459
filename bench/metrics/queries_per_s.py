"""Queries completed in the window over the window's seconds."""


def read(rec):
    return len(rec["served"]) / rec["window_s"] if rec["served"] else None
