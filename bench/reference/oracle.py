"""Plain NumPy reference of the query semantics in ``queries`` (pandas
for the host plans).

Given the tables (``tpch``, with any refreshes applied by ``refresh``)
and one query spec, ``answer`` returns what a correct system returns:

* ``aggregates``: {group label: {name: value}} for "full" queries, with
  exact integers: sum and count as ``int``, avg as the exact pair
  ``(sum, count)`` (``None`` for an empty group), min/max as ``int``
  (``None`` when empty);
* ``masks``: {relation: bool array over its rows} of every filter;
* ``rows``: the host stage's result rows (tuples of ints, in output
  column order) for queries that carry one, else ``None``.

Nothing here imports the program. ``dtype`` is the integer type the
arithmetic runs in: ``int64`` (exact at every scale the benchmark runs)
for the reference, ``int32`` for the control that stands for a program
computing one precision below what the configuration states.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

Tables = Dict[str, Dict[str, np.ndarray]]
_CHUNK = 1 << 20
_CMP = {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
        "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal}


def attrs_of(node) -> Tuple[str, ...]:
    """Column names an expression or predicate reads, first use first."""
    out: Dict[str, None] = {}

    def walk(x):
        if isinstance(x, str):
            out[x] = None
        elif isinstance(x, tuple) and x and isinstance(x[0], str):
            op, args = x[0], x[1:]
            if op == "in":
                walk(args[0])
            elif op == "between":
                walk(args[0])
            else:
                for a in args:
                    walk(a)

    walk(node)
    return tuple(out)


def eval_expr(cols, e, dtype=np.int64) -> np.ndarray:
    if isinstance(e, str):
        return np.asarray(cols[e]).astype(dtype)
    if isinstance(e, (int, np.integer)):
        return dtype(e)
    op = e[0]
    if op == "mul":
        return eval_expr(cols, e[1], dtype) * eval_expr(cols, e[2], dtype)
    if op == "add":
        return eval_expr(cols, e[1], dtype) + eval_expr(cols, e[2], dtype)
    if op == "rsub":
        return dtype(e[1]) - eval_expr(cols, e[2], dtype)
    raise ValueError(f"not an expression: {e!r}")


def eval_pred(cols, p, dtype=np.int64) -> np.ndarray:
    op = p[0]
    if op in _CMP:
        return _CMP[op](eval_expr(cols, p[1], dtype),
                        eval_expr(cols, p[2], dtype))
    if op == "between":
        v = eval_expr(cols, p[1], dtype)
        return (v >= p[2]) & (v <= p[3])
    if op == "in":
        return np.isin(eval_expr(cols, p[1], dtype),
                       np.asarray(p[2], dtype))
    if op == "not":
        return ~eval_pred(cols, p[1], dtype)
    if op in ("and", "or"):
        out = eval_pred(cols, p[1], dtype)
        for q in p[2:]:
            out = (out & eval_pred(cols, q, dtype)) if op == "and" \
                else (out | eval_pred(cols, q, dtype))
        return out
    raise ValueError(f"not a predicate: {p!r}")


def _exact_sum(cols, expr, mask, dtype) -> int:
    """Sum of ``expr`` over the masked rows, accumulated in Python ints
    chunk by chunk so that no int64 partial can overflow."""
    idx = np.flatnonzero(mask)
    total = 0
    for lo in range(0, idx.size, _CHUNK):
        part = {a: np.asarray(cols[a])[idx[lo:lo + _CHUNK]]
                for a in attrs_of(expr)}
        v = eval_expr(part, expr, dtype)
        total += int(np.sum(v, dtype=dtype))
    return total


def _aggregate(cols, mask, agg, dtype):
    op, expr, _ = agg
    n = int(mask.sum())
    if op == "count":
        return n
    if op == "sum":
        return _exact_sum(cols, expr, mask, dtype)
    if op == "avg":
        return None if n == 0 else (_exact_sum(cols, expr, mask, dtype), n)
    if op in ("min", "max"):
        if n == 0:
            return None
        v = eval_expr(cols, expr, dtype)[mask]
        return int(v.min() if op == "min" else v.max())
    raise ValueError(f"unknown aggregate {op!r}")


# --------------------------------------------------------------------------
# Host plans, on pandas data frames: a hash join (``merge``), a hash
# group (``groupby``) and a sort by the ORDER BY keys, so that a fault of
# the program's own sort-based join and group is not repeated here.
# --------------------------------------------------------------------------
def _columns(t: pd.DataFrame) -> Dict[str, np.ndarray]:
    return {c: t[c].to_numpy() for c in t.columns}


def _group(t: pd.DataFrame, keys, aggs, dtype) -> pd.DataFrame:
    """One row per distinct key tuple (one row in all where ``keys`` is
    empty); sums run in ``dtype`` and wrap as that type would."""
    def total(values) -> int:
        return int(np.asarray(np.sum(np.asarray(values, np.int64)))
                   .astype(dtype))

    if not keys:
        return pd.DataFrame({name: [len(t) if op == "count"
                                    else total(t[col])]
                             for name, op, col in aggs})
    spec = {}
    for name, op, col in aggs:
        if op not in ("sum", "count"):
            raise ValueError(f"host aggregate {op!r} is not used by TPC-H")
        spec[name] = (keys[0], "size") if op == "count" else (col, "sum")
    out = t.groupby(list(keys), sort=False).agg(**spec).reset_index()
    for name, op, _ in aggs:
        if op == "sum":
            out[name] = out[name].to_numpy(np.int64).astype(dtype) \
                .astype(np.int64)
    return out


def run_plan(node, scans: Dict[str, Dict[str, np.ndarray]], dtype=np.int64
             ) -> pd.DataFrame:
    op = node[0]
    if op == "scan":
        return pd.DataFrame({c: np.asarray(scans[node[1]][c], np.int64)
                             for c in node[2]})
    if op == "join":
        return run_plan(node[1], scans, dtype).merge(
            run_plan(node[2], scans, dtype), how="inner",
            left_on=node[3], right_on=node[4])
    if op == "filter":
        t = run_plan(node[1], scans, dtype)
        return t[eval_pred(_columns(t), node[2], dtype)].reset_index(
            drop=True)
    if op == "project":
        t = run_plan(node[1], scans, dtype)
        for name, e in node[2]:
            is_pred = isinstance(e, tuple) and e[0] not in ("mul", "add",
                                                            "rsub")
            cols = _columns(t)
            v = eval_pred(cols, e, dtype) if is_pred else \
                eval_expr(cols, e, dtype)
            t[name] = np.broadcast_to(np.asarray(v).astype(np.int64),
                                      (len(t),))
        return t
    if op == "group":
        return _group(run_plan(node[1], scans, dtype), node[2], node[3],
                      dtype)
    if op == "order":
        t = run_plan(node[1], scans, dtype)
        keys = node[2]
        if keys:
            t = t.sort_values([c for c, _ in keys],
                              ascending=[not desc for _, desc in keys],
                              kind="mergesort")
        return t if node[3] is None else t.head(node[3])
    raise ValueError(f"unknown plan node {op!r}")


def scan_relations(plan) -> Dict[str, Tuple[str, ...]]:
    """{relation: columns} of every scan in a host plan, in plan order."""
    out: Dict[str, Tuple[str, ...]] = {}

    def walk(x):
        if x[0] == "scan":
            out[x[1]] = x[2]
        for sub in x[1:]:
            if isinstance(sub, tuple) and sub and sub[0] in (
                    "scan", "join", "filter", "project", "group", "order"):
                walk(sub)

    walk(plan)
    return out


# --------------------------------------------------------------------------
# One query
# --------------------------------------------------------------------------
def answer(tables: Tables, q: dict, dtype=np.int64,
           relations: Optional[Sequence[str]] = None) -> dict:
    """The reference answer of ``q`` over ``tables`` (see module doc).
    ``relations`` limits the masks computed (default: every filter)."""
    masks = {rel: np.asarray(eval_pred(tables[rel], pred, dtype), bool)
             for rel, pred in q["filters"].items()
             if relations is None or rel in relations}
    aggs = None
    if q["kind"] == "full":
        rel = q["agg_relation"]
        cols = tables[rel]
        base = masks[rel] if rel in masks else \
            np.asarray(eval_pred(cols, q["filters"][rel], dtype), bool)
        aggs = {}
        for label, gpred in (q["groups"] or (("all", None),)):
            m = base if gpred is None else \
                (base & np.asarray(eval_pred(cols, gpred, dtype), bool))
            aggs[label] = {a[2]: _aggregate(cols, m, a, dtype)
                           for a in q["aggregates"]}
    rows = None
    if q["host"] is not None:
        plan, output = q["host"]
        scans = {}
        for rel, columns in scan_relations(plan).items():
            t = tables[rel]
            pred = q["filters"].get(rel)
            sel = slice(None) if pred is None else np.flatnonzero(
                eval_pred(t, pred, dtype))
            scans[rel] = {c: np.asarray(t[c])[sel] for c in columns}
        t = run_plan(plan, scans, dtype)
        rows = [tuple(int(v) for v in row) for row in
                t[list(output)].itertuples(index=False, name=None)]
    return {"aggregates": aggs, "masks": masks, "rows": rows}
