"""Device cells written per inserted or deleted row, from the DML
layer's apply statistics."""


def read(rec):
    rows = sum(x["rows"] for x in rec["refreshes"])
    return sum(x["cells_written"] for x in rec["refreshes"]) / rows \
        if rows else None
