"""The benchmark's one door into the system under test.

Everything the harness takes from the program passes through here: the
database it loads, the query and refresh objects it submits (translated
from the benchmark's own specs in ``reference``), and the plain answers
it reads back for the comparison. The program lives in ``src/`` of the
checkout; nothing under ``reference`` imports it.
"""
from __future__ import annotations

import importlib
import os
import sys
from types import SimpleNamespace
from typing import Dict

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoProgram(ImportError):
    """The checkout holds the benchmark but not the program."""


def load_program() -> SimpleNamespace:
    """Import the program's modules the benchmark drives."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoProgram(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    mods = {name: importlib.import_module(f"repro.{path}") for name, path in (
        ("compiler", "db.compiler"), ("exec", "db.exec"),
        ("queries", "db.queries"), ("database", "db.database"),
        ("program", "core.program"), ("isa", "core.isa"),
        ("serve", "serve"), ("dml", "dml"))}
    return SimpleNamespace(**mods)


# --------------------------------------------------------------------------
# Specs -> the program's classes
# --------------------------------------------------------------------------
_PRED_OPS = ("eq", "ne", "lt", "le", "gt", "ge", "between", "in", "not",
             "and", "or")


def _expr(P, e):
    C = P.compiler
    if isinstance(e, str):
        return C.Col(e)
    if isinstance(e, (int, np.integer)):
        return C.Lit(int(e))
    op = e[0]
    if op == "mul":
        return C.Mul(_expr(P, e[1]), _expr(P, e[2]))
    if op == "add":
        return C.AddE(_expr(P, e[1]), _expr(P, e[2]))
    if op == "rsub":
        return C.RSubImm(int(e[1]), _expr(P, e[2]))
    raise ValueError(f"not an expression: {e!r}")


def _pred(P, p):
    C = P.compiler
    op = p[0]
    if op in ("eq", "ne", "lt", "le", "gt", "ge"):
        return C.Cmp(op, _expr(P, p[1]), _expr(P, p[2]))
    if op == "between":
        return C.Between(_expr(P, p[1]), int(p[2]), int(p[3]))
    if op == "in":
        return C.InSet(_expr(P, p[1]), tuple(int(v) for v in p[2]))
    if op == "not":
        return C.Not(_pred(P, p[1]))
    if op == "and":
        return C.And(*(_pred(P, q) for q in p[1:]))
    if op == "or":
        return C.Or(*(_pred(P, q) for q in p[1:]))
    raise ValueError(f"not a predicate: {p!r}")


def _plan(P, node):
    E = P.exec
    op = node[0]
    if op == "scan":
        return E.PimScan(node[1], tuple(node[2]))
    if op == "join":
        return E.HashJoin(_plan(P, node[1]), _plan(P, node[2]), node[3],
                          node[4])
    if op == "filter":
        return E.Filter(_plan(P, node[1]), _pred(P, node[2]))
    if op == "project":
        return E.Project(_plan(P, node[1]), tuple(
            (name, _pred(P, e) if isinstance(e, tuple) and e[0] in _PRED_OPS
             else _expr(P, e)) for name, e in node[2]))
    if op == "group":
        return E.GroupAgg(_plan(P, node[1]), tuple(node[2]),
                          tuple(E.HostAgg(n, o, c) for n, o, c in node[3]))
    if op == "order":
        return E.OrderLimit(_plan(P, node[1]), tuple(node[2]), node[3])
    raise ValueError(f"unknown plan node {op!r}")


def program_spec(P, q: dict):
    """The program's ``QuerySpec`` for one benchmark spec."""
    C = P.compiler
    host = None
    if q["host"] is not None:
        plan, output = q["host"]
        host = P.exec.HostStage(_plan(P, plan), tuple(output))
    groups = None if q["groups"] is None else [
        (label, _pred(P, g)) for label, g in q["groups"]]
    return P.queries.QuerySpec(
        name=q["name"], kind=q["kind"],
        filters={rel: _pred(P, p) for rel, p in q["filters"].items()},
        agg_relation=q["agg_relation"],
        aggregates=[C.Agg(op, None if e is None else _expr(P, e), name)
                    for op, e, name in q["aggregates"]],
        groups=groups, host=host)


def mutations(P, refresh) -> list:
    """The program's DML batch for one refresh (``reference.refresh``):
    RF1 inserts the new orders and then their lineitems; RF2 deletes the
    chosen orders and every lineitem of theirs, by key, as TPC-H's RF2
    does."""
    kind, body = refresh
    if kind == "insert":
        return [P.dml.Insert(rel, body[rel]) for rel in ("orders",
                                                          "lineitem")]
    keys = tuple(int(k) for k in body)
    C = P.compiler
    return [P.dml.Delete("orders", pred=C.InSet(C.Col("o_orderkey"), keys)),
            P.dml.Delete("lineitem",
                         pred=C.InSet(C.Col("l_orderkey"), keys))]


def plain(res) -> Dict[str, object]:
    """A result as plain values, shaped like ``reference.oracle.answer``."""
    return {"aggregates": res.aggregates if res.spec.kind == "full" else None,
            "masks": {rel: np.asarray(run.mask, bool)
                      for rel, run in res.relations.items()},
            "rows": list(res.rows) if res.spec.host is not None else None}


def block(db) -> None:
    """Wait until every plane of every relation is on the device."""
    for rel in db.relations.values():
        rel.valid.block_until_ready()
        for p in rel.planes.values():
            p.block_until_ready()


# --------------------------------------------------------------------------
# Reading the system's state back
# --------------------------------------------------------------------------
def _decode(planes: np.ndarray) -> np.ndarray:
    """``(n_bits, n_words)`` uint32 bit-planes -> the int64 value of each
    of the ``32 * n_words`` slots: bit ``j`` of word ``w`` is bit ``b``
    (the plane's row) of slot ``32 * w + j``."""
    words = np.ascontiguousarray(planes, dtype="<u4")
    bits = np.unpackbits(words.view(np.uint8), axis=1, bitorder="little")
    out = np.zeros(bits.shape[1], np.int64)
    for b in range(bits.shape[0]):
        out |= bits[b].astype(np.int64) << b
    return out


def chip_rows(db, rel_name: str):
    """What the chip holds for one relation, read back from its device
    arrays: ({attribute: value of every storage slot}, the valid bit of
    every slot)."""
    import jax

    rel = db.relations[rel_name]
    planes, valid = jax.device_get((rel.planes, rel.valid))
    return ({a: _decode(p) for a, p in planes.items()},
            _decode(np.asarray(valid)[None]).astype(bool))


def slot_log(db, rel_name: str) -> list:
    """The program's log of one relation's refreshes, in order: one
    ``(op, logical ids, storage slots)`` per insert and per delete (the
    slots of a delete are not needed: ``None``)."""
    d = db.dml_state(rel_name)
    if len(d.segments.events) != len(d.programs):
        raise RuntimeError(f"{rel_name}: {len(d.segments.events)} slot "
                           f"events for {len(d.programs)} DML programs")
    out = []
    for ev, (_, instrs) in zip(d.segments.events, d.programs):
        ids = np.asarray(ev.ids, np.int64)
        if ev.op == "insert":
            valid = next(i for i in instrs
                         if getattr(i, "dest", None) == "__valid__")
            out.append(("insert", ids, np.asarray(valid.rows, np.int64)))
        elif ev.op == "delete":
            out.append(("delete", ids, None))
        else:
            raise ValueError(f"{rel_name}: the refreshes made a {ev.op!r}")
    return out
