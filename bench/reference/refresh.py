"""TPC-H refresh functions RF1 and RF2, generated from the seed.

The throughput test's refresh stream alternates RF1 (insert SF x 1,500
new orders, each with 1 to 7 lineitems, under fresh keys above every
existing key) and RF2 (delete SF x 1,500 existing orders and all their
lineitems). Refresh ``k`` is a pure function of the seed and ``k``, so
the reference can replay exactly the sequence a run applied, however
the run's timing fell: even ``k`` is RF1 number ``k // 2``, odd ``k``
is RF2 number ``k // 2``. RF2 deletes orders of the initial load, in a
seeded order, never the same one twice.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from . import tpch as T

Tables = Dict[str, Dict[str, np.ndarray]]
MAX_LINES_PER_ORDER = 7


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2 ** 64 - 1), *stream])


class Refreshes:
    """The refresh sequence of one run, over the initial ``tables``."""

    def __init__(self, tables: Tables, sf: float, seed: int):
        self.seed = seed
        self.n_orders = max(1, int(1500 * sf))
        counts = T.row_counts(sf)
        self.n_part, self.n_supp = counts["part"], counts["supplier"]
        self.n_cust = counts["customer"]
        keys = np.asarray(tables["orders"]["o_orderkey"])
        self.first_new_key = int(keys.max()) + 1
        self.delete_order = _rng(seed, 2).permutation(keys)

    def __getitem__(self, k: int) -> Tuple[str, object]:
        """Refresh ``k``: ``("insert", {relation: columns})`` or
        ``("delete", orderkeys)``."""
        j = k // 2
        if k % 2:
            keys = self.delete_order[j * self.n_orders:(j + 1) * self.n_orders]
            if keys.size < self.n_orders:
                raise IndexError(f"RF2 number {j} runs past the initial "
                                 "orders")
            return "delete", np.sort(keys)
        rng = _rng(self.seed, 1, j)
        keys = self.first_new_key + j * self.n_orders + \
            np.arange(self.n_orders, dtype=np.int64)
        orders = T.orders(rng, keys, self.n_cust)
        lines = rng.integers(1, MAX_LINES_PER_ORDER + 1, self.n_orders)
        lineitem = T.lineitems(rng, np.repeat(keys, lines),
                               np.repeat(orders["o_orderdate"], lines),
                               self.n_part, self.n_supp)
        return "insert", {
            "orders": {a: np.asarray(v, np.int64) for a, v in orders.items()},
            "lineitem": {a: np.asarray(v, np.int64)
                         for a, v in lineitem.items()}}


def apply(tables: Tables, refresh: Tuple[str, object]) -> Tables:
    """``tables`` with one refresh applied (a new dict; the relations it
    does not touch are shared). Rows keep their order: survivors first,
    in their old order, then inserted rows in the order given."""
    kind, body = refresh
    out = dict(tables)
    if kind == "insert":
        for rel, rows in body.items():
            out[rel] = {a: np.concatenate([tables[rel][a], rows[a]])
                        for a in tables[rel]}
    elif kind == "delete":
        for rel, key in (("orders", "o_orderkey"), ("lineitem", "l_orderkey")):
            keep = ~np.isin(tables[rel][key], body)
            out[rel] = {a: v[keep] for a, v in tables[rel].items()}
    else:
        raise ValueError(f"unknown refresh {kind!r}")
    return out


def n_rows(refresh: Tuple[str, object], tables: Tables) -> int:
    """Rows a refresh inserts or deletes, counted over ``tables`` (the
    state it is applied to)."""
    kind, body = refresh
    if kind == "insert":
        return sum(len(next(iter(rows.values()))) for rows in body.values())
    return int(np.isin(tables["orders"]["o_orderkey"], body).sum()
               + np.isin(tables["lineitem"]["l_orderkey"], body).sum())
