"""Executables JAX built or loaded during the window (its compile
event, counted by the harness): each is a trace and lowering followed by
an XLA compile or a load from the persistent cache, inside the window."""


def read(rec):
    return rec["counters"].get("compiles_in_window")
