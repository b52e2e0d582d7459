"""The benchmark's own TPC-H data: encodings and a seeded generator.

A frozen copy of the attribute subset PIMDB keeps on the device (arXiv
2203.10486, section 5.1) and of its dbgen-alike generator, kept here so
that the data the benchmark measures cannot move with the program.
Every value is already encoded the way the PIM copy stores it: scaled
integers (cents, percent), day offsets from 1992-01-01, dictionary ids.

``generate(sf, seed)`` returns ``{relation: {attribute: int64 column}}``
with TPC-H's row counts: lineitem about 6M x SF (drawn as 6,000,000 x
SF rows with 0 to many lines per order), orders 1.5M x SF, customer
150k x SF, part 200k x SF, supplier 10k x SF, partsupp 800k x SF, and
the DRAM-resident nation (25) and region (5).
"""
from __future__ import annotations

import datetime as _dt
from typing import Dict

import numpy as np

EPOCH = _dt.date(1992, 1, 1)
MAX_DATE = 2556  # 1998-12-31


def date_to_days(iso: str) -> int:
    y, m, d = map(int, iso.split("-"))
    return (_dt.date(y, m, d) - EPOCH).days


# Dictionary vocabularies, fixed by the TPC-H specification.
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, regionkey)
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
NATION_KEY = {name: i for i, (name, _) in enumerate(NATIONS)}
NATIONS_IN_REGION = {
    r: tuple(i for i, (_, rk) in enumerate(NATIONS) if rk == ri)
    for ri, r in enumerate(REGIONS)
}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
RETURNFLAGS = ["R", "A", "N"]
LINESTATUS = ["O", "F"]
ORDERSTATUS = ["F", "O", "P"]
TYPE_SYL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_SYL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_SYL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_SYL1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_SYL2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
BRAND_COUNT = 25            # Brand#11..Brand#55 as dense ids 0..24
ACCTBAL_OFFSET = 100_000    # acctbal cents + offset, so values are >= 0
CURRENT_DATE = "1995-06-17"  # TPC-H's CURRENTDATE for returnflag/linestatus

#: Relations that live on the device; nation and region stay in DRAM.
PIM_RELATIONS = ("lineitem", "orders", "customer", "part", "supplier",
                 "partsupp")


def type_id(s1: int, s2: int, s3: int) -> int:
    return (s1 * len(TYPE_SYL2) + s2) * len(TYPE_SYL3) + s3


def type_name_to_id(name: str) -> int:
    a, b, c = name.split(" ")
    return type_id(TYPE_SYL1.index(a), TYPE_SYL2.index(b), TYPE_SYL3.index(c))


def container_name_to_id(name: str) -> int:
    a, b = name.split(" ")
    return CONTAINER_SYL1.index(a) * len(CONTAINER_SYL2) + \
        CONTAINER_SYL2.index(b)


def brand_name_to_id(name: str) -> int:
    """Brand#MN with M, N in 1..5 -> dense id (M-1)*5 + (N-1)."""
    m, n = divmod(int(name.split("#")[1]), 10)
    return (m - 1) * 5 + (n - 1)


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """TPC-H P_RETAILPRICE in cents: 90000 + (key/10) % 20001 + 100 *
    (key % 1000)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def order_dates(rng, n: int) -> np.ndarray:
    return rng.integers(0, MAX_DATE - 151, n)


def lineitems(rng, orderkeys: np.ndarray, odates: np.ndarray,
              n_part: int, n_supp: int) -> Dict[str, np.ndarray]:
    """Lineitem rows for parent orders given row by row (``orderkeys``
    and ``odates`` aligned): TPC-H's distributions of quantity, prices,
    dates, flags and modes."""
    n = orderkeys.shape[0]
    pkey = rng.integers(1, n_part + 1, n)
    qty = rng.integers(1, 51, n)
    ship = odates + rng.integers(1, 122, n)        # orderdate + 1..121
    commit = odates + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    cur = date_to_days(CURRENT_DATE)
    rf = np.where(receipt <= cur, rng.integers(0, 2, n), 2)
    ls = np.where(ship > cur, 0, 1)
    return {
        "l_orderkey": orderkeys,
        "l_partkey": pkey,
        "l_suppkey": rng.integers(1, n_supp + 1, n),
        "l_quantity": qty,
        "l_extendedprice": qty * retail_price(pkey),
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": rf,
        "l_linestatus": ls,
        "l_shipdate": np.minimum(ship, MAX_DATE),
        "l_commitdate": np.minimum(commit, MAX_DATE),
        "l_receiptdate": np.minimum(receipt, MAX_DATE),
        "l_shipinstruct": rng.integers(0, len(SHIPINSTRUCT), n),
        "l_shipmode": rng.integers(0, len(SHIPMODES), n),
    }


def orders(rng, orderkeys: np.ndarray, n_cust: int) -> Dict[str, np.ndarray]:
    n = orderkeys.shape[0]
    odate = order_dates(rng, n)
    return {
        "o_orderkey": orderkeys,
        "o_custkey": rng.integers(1, n_cust + 1, n),
        "o_orderstatus": rng.integers(0, len(ORDERSTATUS), n),
        "o_totalprice": rng.integers(85000, 55528700, n),
        "o_orderdate": odate,
        "o_orderpriority": rng.integers(0, len(PRIORITIES), n),
        "o_shippriority": np.zeros(n, np.int64),
    }


def row_counts(sf: float) -> Dict[str, int]:
    return {
        "lineitem": max(1000, int(6_000_000 * sf)),
        "orders": max(250, int(1_500_000 * sf)),
        "customer": max(64, int(150_000 * sf)),
        "part": max(64, int(200_000 * sf)),
        "supplier": max(16, int(10_000 * sf)),
        "partsupp": max(128, int(800_000 * sf)),
    }


def generate(sf: float, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The database at scale factor ``sf``; the same seed gives the same
    tables."""
    rng = np.random.default_rng(seed)
    n = row_counts(sf)
    n_pa, n_su, n_cu = n["part"], n["supplier"], n["customer"]
    t: Dict[str, Dict[str, np.ndarray]] = {}

    s1 = rng.integers(0, len(TYPE_SYL1), n_pa)
    s2 = rng.integers(0, len(TYPE_SYL2), n_pa)
    s3 = rng.integers(0, len(TYPE_SYL3), n_pa)
    c1 = rng.integers(0, len(CONTAINER_SYL1), n_pa)
    c2 = rng.integers(0, len(CONTAINER_SYL2), n_pa)
    partkey = np.arange(1, n_pa + 1)
    t["part"] = {
        "p_partkey": partkey,
        "p_brand": rng.integers(0, BRAND_COUNT, n_pa),
        "p_type": (s1 * len(TYPE_SYL2) + s2) * len(TYPE_SYL3) + s3,
        "p_type_syl2": s2,
        "p_type_syl3": s3,
        "p_type_syl12": s1 * len(TYPE_SYL2) + s2,
        "p_size": rng.integers(1, 51, n_pa),
        "p_container": c1 * len(CONTAINER_SYL2) + c2,
        "p_retailprice": retail_price(partkey),
    }
    t["supplier"] = {
        "s_suppkey": np.arange(1, n_su + 1),
        "s_nationkey": rng.integers(0, 25, n_su),
        "s_acctbal": rng.integers(-99999, 999999, n_su) + ACCTBAL_OFFSET,
    }
    t["partsupp"] = {
        "ps_partkey": rng.integers(1, n_pa + 1, n["partsupp"]),
        "ps_suppkey": rng.integers(1, n_su + 1, n["partsupp"]),
        "ps_availqty": rng.integers(1, 10000, n["partsupp"]),
        "ps_supplycost": rng.integers(100, 100001, n["partsupp"]),
    }
    t["customer"] = {
        "c_custkey": np.arange(1, n_cu + 1),
        "c_nationkey": rng.integers(0, 25, n_cu),
        "c_acctbal": rng.integers(-99999, 999999, n_cu) + ACCTBAL_OFFSET,
        "c_mktsegment": rng.integers(0, len(SEGMENTS), n_cu),
        "c_phone_cc": rng.integers(10, 35, n_cu),
    }
    t["orders"] = orders(rng, np.arange(1, n["orders"] + 1), n_cu)
    parent = rng.integers(0, n["orders"], n["lineitem"])
    t["lineitem"] = lineitems(rng, t["orders"]["o_orderkey"][parent],
                              t["orders"]["o_orderdate"][parent], n_pa, n_su)
    t["nation"] = {"n_nationkey": np.arange(25),
                   "n_regionkey": np.asarray([rk for _, rk in NATIONS])}
    t["region"] = {"r_regionkey": np.arange(5)}
    for cols in t.values():
        for k in cols:
            cols[k] = np.asarray(cols[k], np.int64)
    return t
