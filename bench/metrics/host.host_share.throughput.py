"""Share of the client's query latency spent in host stages
(``QueryResult.host_s``, joins, groups and ordering in ``db.exec``),
in %. Answers from the result cache add their latency and no host
time."""


def read(rec):
    lat = sum(s["latency_s"] for s in rec["served"])
    return 100.0 * sum(s["host_s"] for s in rec["served"]) / lat \
        if lat else None
