"""Share of the client's query latency spent neither in the array stage
(``QueryResult.pim_s``) nor in host stages (``QueryResult.host_s``), in
%: waiting for the service's one dispatch worker behind other queries
and refreshes, and the event loop's own time."""


def read(rec):
    lat = sum(s["latency_s"] for s in rec["served"])
    if not lat:
        return None
    work = sum(s["pim_s"] + s["host_s"] for s in rec["served"])
    return 100.0 * (lat - work) / lat
