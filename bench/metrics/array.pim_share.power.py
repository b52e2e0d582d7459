"""Share of the client's query latency spent in the array stage
(``QueryResult.pim_s``: dispatch, device and readback), in %."""


def read(rec):
    lat = sum(s["latency_s"] for s in rec["served"])
    return 100.0 * sum(s["pim_s"] for s in rec["served"]) / lat \
        if lat else None
