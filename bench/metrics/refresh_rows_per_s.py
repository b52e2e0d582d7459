"""Rows inserted plus rows deleted by the refreshes that completed in
the window, over the window's seconds."""


def read(rec):
    if not rec["refreshes"]:
        return None
    return sum(r["rows"] for r in rec["refreshes"]) / rec["window_s"]
