"""Share of the traced window in which no operation ran on the device,
in %."""


def read(rec):
    t = rec.get("trace")
    return 100.0 * t["idle_share"] if t else None
