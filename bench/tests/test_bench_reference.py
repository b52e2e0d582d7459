"""The yardstick: traffic generation, refreshes, the reference and the
comparison that decides ``correct``, on the CPU at a tiny scale."""
import numpy as np
import pytest

import compare
from reference import oracle, queries, refresh, tpch

SF = 0.002


@pytest.fixture(scope="module")
def tables():
    return tpch.generate(SF, 2 ** 31 + 3)


def test_generator_is_seeded(tables):
    again = tpch.generate(SF, 2 ** 31 + 3)
    other = tpch.generate(SF, 4)
    for rel in tables:
        for a in tables[rel]:
            assert np.array_equal(tables[rel][a], again[rel][a])
    assert not np.array_equal(tables["lineitem"]["l_shipdate"],
                              other["lineitem"]["l_shipdate"])
    assert {r: len(next(iter(c.values()))) for r, c in other.items()} == \
        {r: len(next(iter(c.values()))) for r, c in tables.items()}


def test_power_passes_are_seeded_permutations():
    import traffic_probe

    a = traffic_probe.power_orders(seed=7, passes=3)
    b = traffic_probe.power_orders(seed=7, passes=3)
    c = traffic_probe.power_orders(seed=8, passes=3)
    assert a == b and a != c
    for p in a:
        assert sorted(p) == sorted(queries.names("array"))
    assert len(set(map(tuple, a))) > 1


def test_query_mixes():
    assert len(queries.names("array")) == 13
    assert len(queries.names("all")) == 19
    assert set(queries.names("array")) | set(queries.names("host")) == \
        set(queries.names("all"))
    for name in queries.names("host"):
        assert queries.all_queries()[name]["host"] is not None


def test_rf1_inserts_fresh_keys_with_one_to_seven_lines(tables):
    rs = refresh.Refreshes(tables, 1.0, 99)
    kind, body = rs[0]
    assert kind == "insert"
    keys = body["orders"]["o_orderkey"]
    assert len(keys) == 1500 and len(np.unique(keys)) == 1500
    assert keys.min() > tables["orders"]["o_orderkey"].max()
    per_order = np.bincount(np.searchsorted(keys, body["lineitem"]
                                            ["l_orderkey"]))
    assert per_order.min() >= 1 and per_order.max() <= 7
    assert set(body["lineitem"]) == set(tables["lineitem"])
    # The next RF1 takes the next keys; the same seed gives the same rows.
    assert rs[2][1]["orders"]["o_orderkey"].min() == keys.max() + 1
    again = refresh.Refreshes(tables, 1.0, 99)[0][1]
    assert np.array_equal(again["lineitem"]["l_extendedprice"],
                          body["lineitem"]["l_extendedprice"])


def test_rf2_deletes_initial_orders_once(tables):
    rs = refresh.Refreshes(tables, SF, 99)
    n = rs.n_orders
    k1, k3 = rs[1][1], rs[3][1]
    assert rs[1][0] == "delete" and len(k1) == n
    assert not set(k1) & set(k3)
    assert np.isin(k1, tables["orders"]["o_orderkey"]).all()
    after = refresh.apply(tables, rs[1])
    assert not np.isin(after["orders"]["o_orderkey"], k1).any()
    assert not np.isin(after["lineitem"]["l_orderkey"], k1).any()
    gone = len(tables["lineitem"]["l_orderkey"]) - \
        len(after["lineitem"]["l_orderkey"])
    assert refresh.n_rows(rs[1], tables) == n + gone


def test_refreshes_keep_row_order(tables):
    rs = refresh.Refreshes(tables, SF, 5)
    t = refresh.apply(refresh.apply(tables, rs[0]), rs[1])
    n0 = len(tables["orders"]["o_orderkey"])
    keep = ~np.isin(tables["orders"]["o_orderkey"], rs[1][1])
    want = np.concatenate([tables["orders"]["o_orderkey"][keep],
                           rs[0][1]["orders"]["o_orderkey"]])
    assert np.array_equal(t["orders"]["o_orderkey"], want)
    assert len(want) == n0  # RF1 and RF2 move the same number of orders


def _answers(t, dtype=np.int64):
    return {n: oracle.answer(t, q, dtype)
            for n, q in queries.all_queries().items()}


def test_comparison_accepts_the_reference(tables):
    for name, a in _answers(tables).items():
        assert compare.differences(a, a) == [], name


def test_comparison_fails_a_corrupted_aggregate(tables):
    a = oracle.answer(tables, queries.all_queries()["Q6"])
    bad = {**a, "aggregates": {"all": {"revenue":
                                       a["aggregates"]["all"]["revenue"] + 1}}}
    assert compare.differences(bad, a)
    q1 = oracle.answer(tables, queries.all_queries()["Q1"])
    groups = dict(q1["aggregates"])
    groups["R/F"] = dict(groups["R/F"], count_order=groups["R/F"]
                         ["count_order"] - 1)
    assert compare.differences({**q1, "aggregates": groups}, q1)


def test_comparison_fails_half_a_mask(tables):
    a = oracle.answer(tables, queries.all_queries()["Q4"])
    m = a["masks"]["lineitem"].copy()
    m[len(m) // 2:] = False
    bad = {**a, "masks": {**a["masks"], "lineitem": m}}
    assert compare.differences(bad, a)
    # and where the rows sit in storage slots of their own
    slots = np.arange(m.size)[::-1] * 2
    stored = np.zeros(2 * m.size, bool)
    stored[slots] = a["masks"]["lineitem"]
    moved = {**a, "masks": {**a["masks"], "lineitem": stored}}
    assert compare.differences(moved, a, {"lineitem": slots}) == []
    stored[slots] = m
    assert compare.differences(moved, a, {"lineitem": slots})
    stored[slots + 1] = True                  # a dead slot selected
    stored[slots] = a["masks"]["lineitem"]
    assert compare.differences(moved, a, {"lineitem": slots})


@pytest.mark.parametrize("name", ["Q1", "Q12", "Q14"])
def test_comparison_fails_a_dropped_refresh(tables, name):
    rs = refresh.Refreshes(tables, 0.2, 17)    # 300 of 3,000 orders
    applied = refresh.apply(refresh.apply(tables, rs[0]), rs[1])
    dropped = refresh.apply(tables, rs[0])          # RF2 never applied
    q = queries.all_queries()[name]
    assert compare.differences(oracle.answer(dropped, q),
                               oracle.answer(applied, q))
    assert compare.table_differences(dropped["lineitem"],
                                     applied["lineitem"]) > 0
    assert compare.table_differences(applied["orders"],
                                     applied["orders"]) == 0


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 9])
def test_control_one_precision_below_fails(seed):
    """The control: the reference computed in int32, one step below the
    configuration's exact 64-bit integers, fails the comparison."""
    t = tpch.generate(0.01, seed)
    want, low = _answers(t), _answers(t, np.int32)
    wrong = [n for n in want
             if compare.differences(low[n], want[n])]
    assert {"Q1", "Q6"} <= set(wrong)
