"""The benchmark's own copy of PIMDB's TPC-H query set, as plain data.

Nineteen of TPC-H's 22 queries (Q9, Q13 and Q18 filter only text that
is not on the device), with TPC-H's validation parameters, already
encoded like the data (``tpch``). Thirteen stop at the paper's array
stage (masks and aggregates, arXiv 2203.10486 Table 2); six carry the
host stage that completes them into TPC-H result rows.

The specs are nested tuples, read by ``oracle`` (the NumPy reference)
and by the harness's translation into the program's own classes:

* expression: ``"attr"`` (a column), an ``int`` (a literal),
  ``("mul", a, b)``, ``("add", a, b)``, ``("rsub", imm, e)`` (imm - e);
* predicate: ``(op, left, right)`` with op in eq ne lt le gt ge,
  ``("between", e, lo, hi)`` (inclusive), ``("in", e, values)``,
  ``("not", p)``, ``("and", p, ...)``, ``("or", p, ...)``;
* aggregate: ``(op, expr or None, name)``, op in sum count avg min max;
* host plan: ``("scan", relation, columns)``,
  ``("join", left, right, left_key, right_key)`` (inner equi-join),
  ``("filter", child, pred)``, ``("project", child, ((name, expr or
  pred), ...))``, ``("group", child, keys, ((name, op, column), ...))``,
  ``("order", child, ((column, descending), ...), limit or None)``.

A query is a dict: ``name``, ``kind`` ("full" aggregates on the device,
"filter" stops at masks), ``filters`` {relation: pred}, and for "full"
queries ``agg_relation``, ``aggregates`` and ``groups`` ((label, pred)
or None for the one group "all"); ``host`` is (plan, output columns)
or None.
"""
from __future__ import annotations

from typing import Dict, List

from . import tpch as T

D = T.date_to_days
NK = T.NATION_KEY

#: l_extendedprice * (1 - l_discount), at cents x percent scale.
REVENUE = ("mul", "l_extendedprice", ("rsub", 100, "l_discount"))

#: The queries whose work ends in the array stage, in TPC-H order.
ARRAY_STAGE = ("Q1", "Q6", "Q22_sub", "Q2", "Q4", "Q7", "Q8", "Q11", "Q15",
               "Q16", "Q17", "Q20", "Q21")
#: The queries completed by a host stage (joins, groups, ordering).
HOST_STAGE = ("Q3", "Q5", "Q10", "Q12", "Q14", "Q19")


def _q1():
    disc_price = ("mul", "l_extendedprice", ("rsub", 100, "l_discount"))
    charge = ("mul", disc_price, ("add", "l_tax", 100))
    groups = tuple(
        (f"{rf}/{ls}", ("and", ("eq", "l_returnflag", irf),
                        ("eq", "l_linestatus", ils)))
        for irf, rf in enumerate(T.RETURNFLAGS)
        for ils, ls in enumerate(T.LINESTATUS))
    return {
        "name": "Q1", "kind": "full",
        "filters": {"lineitem": ("le", "l_shipdate", D("1998-12-01") - 90)},
        "agg_relation": "lineitem",
        "aggregates": (("sum", "l_quantity", "sum_qty"),
                       ("sum", "l_extendedprice", "sum_base_price"),
                       ("sum", disc_price, "sum_disc_price"),
                       ("sum", charge, "sum_charge"),
                       ("avg", "l_quantity", "avg_qty"),
                       ("avg", "l_discount", "avg_disc"),
                       ("count", None, "count_order")),
        "groups": groups,
    }


def _q6():
    return {
        "name": "Q6", "kind": "full",
        "filters": {"lineitem": (
            "and", ("ge", "l_shipdate", D("1994-01-01")),
            ("lt", "l_shipdate", D("1995-01-01")),
            ("between", "l_discount", 5, 7),
            ("lt", "l_quantity", 24))},
        "agg_relation": "lineitem",
        "aggregates": (("sum", ("mul", "l_extendedprice", "l_discount"),
                        "revenue"),),
        "groups": None,
    }


def _q22():
    return {
        "name": "Q22_sub", "kind": "full",
        "filters": {"customer": (
            "and", ("gt", "c_acctbal", T.ACCTBAL_OFFSET),
            ("in", "c_phone_cc", (13, 31, 23, 29, 30, 18, 17)))},
        "agg_relation": "customer",
        "aggregates": (("avg", "c_acctbal", "avg_acctbal"),),
        "groups": None,
    }


def _year(attr, start, end):
    return ("and", ("ge", attr, D(start)), ("lt", attr, D(end)))


def _filters() -> Dict[str, Dict[str, object]]:
    mail_ship = (T.SHIPMODES.index("MAIL"), T.SHIPMODES.index("SHIP"))
    air = (T.SHIPMODES.index("AIR"), T.SHIPMODES.index("REG AIR"))
    fr_de = (NK["FRANCE"], NK["GERMANY"])
    brand = T.brand_name_to_id
    cont = T.container_name_to_id

    def q19_branch(b, containers, size_hi):
        return ("and", ("eq", "p_brand", brand(b)),
                ("in", "p_container", tuple(cont(c) for c in containers)),
                ("between", "p_size", 1, size_hi))

    return {
        "Q2": {"part": ("and", ("eq", "p_size", 15),
                        ("eq", "p_type_syl3", T.TYPE_SYL3.index("BRASS"))),
               "supplier": ("in", "s_nationkey",
                            T.NATIONS_IN_REGION["EUROPE"])},
        "Q3": {"customer": ("eq", "c_mktsegment",
                            T.SEGMENTS.index("BUILDING")),
               "orders": ("lt", "o_orderdate", D("1995-03-15")),
               "lineitem": ("gt", "l_shipdate", D("1995-03-15"))},
        "Q4": {"orders": _year("o_orderdate", "1993-07-01", "1993-10-01"),
               "lineitem": ("lt", "l_commitdate", "l_receiptdate")},
        "Q5": {"supplier": ("in", "s_nationkey", T.NATIONS_IN_REGION["ASIA"]),
               "customer": ("in", "c_nationkey", T.NATIONS_IN_REGION["ASIA"]),
               "orders": _year("o_orderdate", "1994-01-01", "1995-01-01")},
        "Q7": {"supplier": ("in", "s_nationkey", fr_de),
               "customer": ("in", "c_nationkey", fr_de),
               "lineitem": ("between", "l_shipdate", D("1995-01-01"),
                            D("1996-12-31"))},
        "Q8": {"part": ("eq", "p_type",
                        T.type_name_to_id("ECONOMY ANODIZED STEEL")),
               "orders": ("between", "o_orderdate", D("1995-01-01"),
                          D("1996-12-31")),
               "customer": ("in", "c_nationkey",
                            T.NATIONS_IN_REGION["AMERICA"])},
        "Q10": {"orders": _year("o_orderdate", "1993-10-01", "1994-01-01"),
                "lineitem": ("eq", "l_returnflag", T.RETURNFLAGS.index("R"))},
        "Q11": {"supplier": ("eq", "s_nationkey", NK["GERMANY"])},
        "Q12": {"lineitem": (
            "and", ("in", "l_shipmode", mail_ship),
            ("lt", "l_commitdate", "l_receiptdate"),
            ("lt", "l_shipdate", "l_commitdate"),
            ("ge", "l_receiptdate", D("1994-01-01")),
            ("lt", "l_receiptdate", D("1995-01-01")))},
        "Q14": {"lineitem": _year("l_shipdate", "1995-09-01", "1995-10-01")},
        "Q15": {"lineitem": _year("l_shipdate", "1996-01-01", "1996-04-01")},
        "Q16": {"part": (
            "and", ("ne", "p_brand", brand("Brand#45")),
            ("not", ("eq", "p_type_syl12",
                     T.TYPE_SYL1.index("MEDIUM") * len(T.TYPE_SYL2)
                     + T.TYPE_SYL2.index("POLISHED"))),
            ("in", "p_size", (49, 14, 23, 45, 19, 3, 36, 9)))},
        "Q17": {"part": ("and", ("eq", "p_brand", brand("Brand#23")),
                         ("eq", "p_container", cont("MED BOX")))},
        "Q19": {"part": ("or",
                         q19_branch("Brand#12",
                                    ("SM CASE", "SM BOX", "SM PACK", "SM PKG"),
                                    5),
                         q19_branch("Brand#23",
                                    ("MED BAG", "MED BOX", "MED PKG",
                                     "MED PACK"), 10),
                         q19_branch("Brand#34",
                                    ("LG CASE", "LG BOX", "LG PACK", "LG PKG"),
                                    15)),
                "lineitem": ("and", ("in", "l_shipmode", air),
                             ("eq", "l_shipinstruct",
                              T.SHIPINSTRUCT.index("DELIVER IN PERSON")),
                             ("between", "l_quantity", 1, 30))},
        "Q20": {"supplier": ("eq", "s_nationkey", NK["CANADA"]),
                "lineitem": _year("l_shipdate", "1994-01-01", "1995-01-01")},
        "Q21": {"supplier": ("eq", "s_nationkey", NK["SAUDI ARABIA"]),
                "orders": ("eq", "o_orderstatus", T.ORDERSTATUS.index("F")),
                "lineitem": ("gt", "l_receiptdate", "l_commitdate")},
    }


def _host_plans() -> Dict[str, tuple]:
    """The host half of Q3, Q5, Q10, Q12, Q14 and Q19: (plan, output)."""
    sum_rev = (("revenue", "sum", "revenue"),)
    q3 = ("join",
          ("join", ("scan", "customer", ("c_custkey",)),
           ("scan", "orders", ("o_orderkey", "o_custkey", "o_orderdate",
                               "o_shippriority")),
           "c_custkey", "o_custkey"),
          ("scan", "lineitem", ("l_orderkey", "l_extendedprice",
                                "l_discount")),
          "o_orderkey", "l_orderkey")
    q3 = ("order",
          ("group", ("project", q3, (("revenue", REVENUE),)),
           ("l_orderkey", "o_orderdate", "o_shippriority"), sum_rev),
          (("revenue", True), ("o_orderdate", False), ("l_orderkey", False)),
          10)

    q5 = ("join",
          ("join",
           ("join", ("scan", "customer", ("c_custkey", "c_nationkey")),
            ("scan", "orders", ("o_orderkey", "o_custkey")),
            "c_custkey", "o_custkey"),
           ("scan", "lineitem", ("l_orderkey", "l_suppkey",
                                 "l_extendedprice", "l_discount")),
           "o_orderkey", "l_orderkey"),
          ("scan", "supplier", ("s_suppkey", "s_nationkey")),
          "l_suppkey", "s_suppkey")
    q5 = ("order",
          ("group",
           ("project", ("filter", q5, ("eq", "c_nationkey", "s_nationkey")),
            (("revenue", REVENUE),)),
           ("s_nationkey",), sum_rev),
          (("revenue", True), ("s_nationkey", False)), None)

    q10 = ("join",
           ("join", ("scan", "customer", ("c_custkey", "c_nationkey",
                                          "c_acctbal")),
            ("scan", "orders", ("o_orderkey", "o_custkey")),
            "c_custkey", "o_custkey"),
           ("scan", "lineitem", ("l_orderkey", "l_extendedprice",
                                 "l_discount")),
           "o_orderkey", "l_orderkey")
    q10 = ("order",
           ("group", ("project", q10, (("revenue", REVENUE),)),
            ("c_custkey", "c_nationkey", "c_acctbal"), sum_rev),
           (("revenue", True), ("c_custkey", False)), 20)

    high = ("in", "o_orderpriority", (T.PRIORITIES.index("1-URGENT"),
                                      T.PRIORITIES.index("2-HIGH")))
    q12 = ("join", ("scan", "lineitem", ("l_orderkey", "l_shipmode")),
           ("scan", "orders", ("o_orderkey", "o_orderpriority")),
           "l_orderkey", "o_orderkey")
    q12 = ("order",
           ("group", ("project", q12, (("high", high),
                                       ("low", ("not", high)))),
            ("l_shipmode",), (("high_line_count", "sum", "high"),
                              ("low_line_count", "sum", "low"))),
           (("l_shipmode", False),), None)

    promo = T.TYPE_SYL1.index("PROMO")
    q14 = ("join", ("scan", "lineitem", ("l_partkey", "l_extendedprice",
                                         "l_discount")),
           ("scan", "part", ("p_partkey", "p_type")),
           "l_partkey", "p_partkey")
    q14 = ("group",
           ("project", q14,
            (("revenue", REVENUE),
             ("is_promo", ("between", "p_type", T.type_id(promo, 0, 0),
                           T.type_id(promo, len(T.TYPE_SYL2) - 1,
                                     len(T.TYPE_SYL3) - 1))),
             ("promo_revenue", ("mul", "revenue", "is_promo")))),
           (), (("promo_revenue", "sum", "promo_revenue"),
                ("revenue", "sum", "revenue")))

    def branch(b, containers, size_hi, qty_lo, qty_hi):
        return ("and", ("eq", "p_brand", T.brand_name_to_id(b)),
                ("in", "p_container",
                 tuple(T.container_name_to_id(c) for c in containers)),
                ("between", "p_size", 1, size_hi),
                ("between", "l_quantity", qty_lo, qty_hi))

    residual = ("or",
                branch("Brand#12", ("SM CASE", "SM BOX", "SM PACK", "SM PKG"),
                       5, 1, 11),
                branch("Brand#23", ("MED BAG", "MED BOX", "MED PKG",
                                    "MED PACK"), 10, 10, 20),
                branch("Brand#34", ("LG CASE", "LG BOX", "LG PACK", "LG PKG"),
                       15, 20, 30))
    q19 = ("join", ("scan", "lineitem", ("l_partkey", "l_quantity",
                                         "l_extendedprice", "l_discount")),
           ("scan", "part", ("p_partkey", "p_brand", "p_container", "p_size")),
           "l_partkey", "p_partkey")
    q19 = ("group", ("project", ("filter", q19, residual),
                     (("revenue", REVENUE),)), (), sum_rev)
    return {
        "Q3": (q3, ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")),
        "Q5": (q5, ("s_nationkey", "revenue")),
        "Q10": (q10, ("c_custkey", "revenue", "c_acctbal", "c_nationkey")),
        "Q12": (q12, ("l_shipmode", "high_line_count", "low_line_count")),
        "Q14": (q14, ("promo_revenue", "revenue")),
        "Q19": (q19, ("revenue",)),
    }


def all_queries() -> Dict[str, dict]:
    """Every supported query by name, in TPC-H order."""
    out = {q["name"]: q for q in (_q1(), _q6(), _q22())}
    hosts = _host_plans()
    for name, filters in _filters().items():
        out[name] = {"name": name, "kind": "filter", "filters": filters,
                     "agg_relation": None, "aggregates": (), "groups": None,
                     "host": hosts.get(name)}
    for q in out.values():
        q.setdefault("host", None)
    order = sorted(out, key=lambda n: int(n[1:].split("_")[0]))
    return {n: out[n] for n in order}


def names(kind: str) -> List[str]:
    """Query names of one mix: ``array`` (the 13 array-stage queries),
    ``host`` (the 6 with a host stage) or ``all`` (all 19)."""
    if kind == "array":
        return list(ARRAY_STAGE)
    if kind == "host":
        return list(HOST_STAGE)
    if kind == "all":
        return list(all_queries())
    raise ValueError(f"unknown query mix {kind!r}")
