"""Mean client time of one refresh ``apply`` (RF1 or RF2), in ms."""


def read(rec):
    r = rec["refreshes"]
    return 1e3 * sum(x["apply_s"] for x in r) / len(r) if r else None
