"""The least device traffic a query needs, from the data alone.

A bulk-bitwise query reads each attribute it references as bit-planes,
once, plus the relation's valid plane. The floor counts, per relation
the query touches, one plane per bit of each referenced attribute and
one valid plane, each of ``ceil(rows / 32)`` 32-bit words. The widths
are the data's own after leading-zero suppression (the bit length of
the column's largest value), not whatever the program chose, so the
floor never counts more than a correct implementation must read.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from reference import oracle


def width(column: np.ndarray) -> int:
    return max(1, int(np.max(column)).bit_length()) if len(column) else 1


def referenced(q: dict) -> Dict[str, set]:
    """{relation: attributes} a query reads on the device."""
    out: Dict[str, set] = {}
    for rel, pred in q["filters"].items():
        out.setdefault(rel, set()).update(oracle.attrs_of(pred))
    if q["kind"] == "full":
        attrs = out.setdefault(q["agg_relation"], set())
        for _, expr, _ in q["aggregates"]:
            if expr is not None:
                attrs.update(oracle.attrs_of(expr))
        for _, g in q["groups"] or ():
            attrs.update(oracle.attrs_of(g))
    if q["host"] is not None:
        for rel, cols in oracle.scan_relations(q["host"][0]).items():
            out.setdefault(rel, set()).update(cols)
    return out


def floor_bytes(q: dict, tables) -> int:
    total = 0
    for rel, attrs in referenced(q).items():
        cols = tables[rel]
        words = -(-len(next(iter(cols.values()))) // 32)
        planes = 1 + sum(width(cols[a]) for a in attrs)
        total += planes * words * 4
    return total
