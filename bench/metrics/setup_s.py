"""Seconds from the start of the process to the start of the window:
JAX start, data generation, loading, warm-up and any compilation."""


def read(rec):
    return rec["setup_s"]
