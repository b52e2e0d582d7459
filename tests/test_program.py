"""Fused program execution: one-dispatch compiled path == eager engine ==
numpy oracle, over every evaluated TPC-H query plus edge cases."""
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import program as prog
from repro.db import database, queries, tpch
from repro.db.compiler import Agg, And, Between, Cmp, Col, Compiler, InSet, Lit

# Same generator parameters as test_queries.py so the program-executable
# cache is shared across both modules (identical layouts -> identical sigs).
SF, SEED = 0.002, 123


@pytest.fixture(scope="module")
def tables():
    return tpch.generate(sf=SF, seed=SEED)


@pytest.fixture(scope="module")
def db(tables):
    return database.PimDatabase(tables)


@pytest.fixture(scope="module")
def db_pallas(tables):
    return database.PimDatabase(tables, backend="pallas")


@pytest.mark.parametrize("qname", [q.name for q in queries.all_queries()])
def test_fused_matches_eager_and_oracle(db, qname):
    """Acceptance: bit-identical masks and aggregates, fused vs eager."""
    spec = queries.get_query(qname)
    fused = db.run_pim(spec, fused=True)
    eager = db.run_pim(spec, fused=False)
    base = db.run_baseline(spec)
    for rel in spec.filters:
        np.testing.assert_array_equal(fused.relations[rel].mask,
                                      eager.relations[rel].mask, err_msg=rel)
        np.testing.assert_array_equal(fused.relations[rel].mask,
                                      base.relations[rel].mask, err_msg=rel)
    assert fused.aggregates == eager.aggregates
    assert fused.aggregates == base.aggregates


@pytest.mark.parametrize("qname", ["Q6", "Q12", "Q19", "Q22_sub"])
def test_pallas_program_kernel_matches_jnp(db, db_pallas, qname):
    """The whole-program Pallas kernel (interpret mode on CPU) produces the
    same masks/aggregates as the fused jnp lowering."""
    spec = queries.get_query(qname)
    fp = db_pallas.run_pim(spec, fused=True)
    fj = db.run_pim(spec, fused=True)
    for rel in spec.filters:
        np.testing.assert_array_equal(fp.relations[rel].mask,
                                      fj.relations[rel].mask, err_msg=rel)
    assert fp.aggregates == fj.aggregates


def test_fused_trace_identical_to_eager(db):
    """Cost model input is unchanged: the fused run reports the same
    instruction trace the eager engine executes."""
    spec = queries.get_query("Q6")
    fused = db.run_pim(spec, fused=True)
    eager = db.run_pim(spec, fused=False)
    assert fused.relations["lineitem"].trace == eager.relations["lineitem"].trace


def test_single_dispatch_per_relation(db):
    spec = queries.get_query("Q6")
    rel = db.relations["lineitem"]
    c, mask_reg, _ = db._compile_relation(rel, spec, spec.filters["lineitem"])
    cp = prog.compile_program(rel, c.program, mask_outputs=(mask_reg,))
    assert cp.n_dispatches == 1
    assert len(cp.instrs) > 5          # the whole program fused behind it
    assert cp.paper_cycles() > 0


@pytest.mark.parametrize("rel_name", ["lineitem", "part"])
def test_program_module_named_after_its_relation(db, rel_name):
    """The jitted program is ``pimdb_<relation>``, so a trace's module
    line says which relation a device program belongs to."""
    spec = next(q for q in queries.all_queries()
                if rel_name in q.filters and q.host is None)
    rel = db.relations[rel_name]
    c, mask_reg, _ = db._compile_relation(rel, spec, spec.filters[rel_name])
    cp = prog.compile_program(rel, c.program, mask_outputs=(mask_reg,))
    planes = {a: rel.planes[a] for a in cp.analysis.source_attrs}
    text = cp._fn.lower(planes, rel.valid).as_text()
    assert f"@jit_pimdb_{rel_name}" in text


def test_liveness_shrinks_live_planes(db):
    """Register liveness must find dead intermediates to reuse: the peak
    simultaneously-live plane count is below the no-reuse total."""
    spec = queries.get_query("Q1")
    rel = db.relations["lineitem"]
    c, mask_reg, _ = db._compile_relation(rel, spec, spec.filters["lineitem"])
    cp = prog.compile_program(rel, c.program, mask_outputs=(mask_reg,))
    assert 0 < cp.peak_live_planes < cp.total_reg_planes


def test_empty_selection_minmax_is_none(db, db_pallas):
    """MIN/MAX over an empty selection: the ReduceMinMax found flag must
    surface as None (previously a garbage 0/all-ones value) — including
    through the Pallas path, where narrowing now runs *inside* the kernel
    per tile and no tile raises the found flag (the distributed-fused
    side lives in test_distributed_program.py)."""
    spec = queries.QuerySpec(
        "Qmm_empty", "full",
        filters={"customer": Cmp("gt", Col("c_acctbal"), Lit(1 << 40))},
        agg_relation="customer",
        aggregates=[Agg("min", Col("c_acctbal"), "mn"),
                    Agg("max", Col("c_acctbal"), "mx"),
                    Agg("sum", Col("c_acctbal"), "s"),
                    Agg("count", None, "c")])
    want = {"all": {"mn": None, "mx": None, "s": 0, "c": 0}}
    assert db.run_baseline(spec).aggregates == want
    assert db.run_pim(spec, fused=True).aggregates == want
    assert db.run_pim(spec, fused=False).aggregates == want
    assert db_pallas.run_pim(spec, fused=True).aggregates == want


def test_minmax_nonempty_and_derived_expr(db, db_pallas):
    """MIN/MAX over a derived arithmetic expression — exercises the Pallas
    path's full-width recompute of non-exported operands."""
    from repro.db.compiler import Mul, RSubImm
    spec = queries.QuerySpec(
        "Qmm_expr", "full",
        filters={"lineitem": Cmp("lt", Col("l_quantity"), Lit(10))},
        agg_relation="lineitem",
        aggregates=[Agg("max", Mul(Col("l_extendedprice"),
                                   RSubImm(100, Col("l_discount"))), "mx"),
                    Agg("min", Col("l_quantity"), "mn")])
    base = db.run_baseline(spec)
    assert base.aggregates["all"]["mx"] is not None
    assert db.run_pim(spec, fused=True).aggregates == base.aggregates
    assert db.run_pim(spec, fused=False).aggregates == base.aggregates
    assert db_pallas.run_pim(spec, fused=True).aggregates == base.aggregates


def test_empty_inset_compiles_to_false(db):
    """InSet with no values: constant-false mask instead of the acc=None
    crash inside the enclosing BitwiseAnd."""
    spec = queries.QuerySpec(
        "Qin_empty", "filter",
        filters={"customer": And(Cmp("gt", Col("c_acctbal"), Lit(0)),
                                 InSet(Col("c_nationkey"), ()))})
    for run in (db.run_pim(spec, fused=True), db.run_pim(spec, fused=False),
                db.run_baseline(spec)):
        assert not run.relations["customer"].mask.any()


def test_empty_inset_compiler_regression():
    cols = {"a": np.arange(100), "b": np.arange(100) % 7}
    rel = eng.PimRelation.from_columns("t", cols)
    c = Compiler(rel)
    mask_reg = c.compile_filter(And(Cmp("ge", Col("a"), Lit(0)),
                                    InSet(Col("b"), ())))
    e = eng.Engine(rel)
    e.run(c.program)                      # used to raise on BitwiseAnd
    assert not e.read_mask(mask_reg).any()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_program_multi_tile_grid(backend):
    """>1 grid step: per-tile popcount partials must combine exactly and
    mask tiles must land in the right output columns."""
    rng = np.random.default_rng(7)
    n = 100_000                      # W = 4096 words -> 2 tiles at BLOCK_W
    cols = {"k": rng.integers(0, 1 << 12, n),
            "v": rng.integers(0, 1 << 9, n)}
    rel = eng.PimRelation.from_columns("t", cols)
    c = Compiler(rel)
    m = c.compile_filter(Between(Col("k"), 500, 3000), with_transform=False)
    regs = c.compile_aggregates(m, [Agg("sum", Col("v"), "s"),
                                    Agg("count", None, "c"),
                                    Agg("max", Col("v"), "mx")])
    sel = (cols["k"] >= 500) & (cols["k"] <= 3000)
    cp = prog.compile_program(rel, c.program, mask_outputs=(m,),
                              backend=backend)
    res = prog.run_program(cp, rel)
    np.testing.assert_array_equal(res.mask(m), sel)
    assert res.scalar(regs["s"][1]) == int(cols["v"][sel].sum())
    assert res.scalar(regs["c"][1]) == int(sel.sum())
    assert res.scalar(regs["mx"][1]) == int(cols["v"][sel].max())


def test_fn_cache_lru_eviction(monkeypatch):
    """The compiled-executable cache is a bounded LRU: filling it past
    capacity evicts the least-recently-used executable (a long-lived
    serving process must not leak compiled programs), and an evicted
    signature recompiles correctly on next use."""
    small = prog.LruFnCache(capacity=2)
    monkeypatch.setattr(prog, "_FN_CACHE", small)
    rng = np.random.default_rng(3)
    cols = {"a": rng.integers(0, 1 << 8, 2000)}
    rel = eng.PimRelation.from_columns("lru_t", cols)

    def compile_for(imm):
        c = Compiler(rel)
        m = c.compile_filter(Cmp("lt", Col("a"), Lit(imm)),
                             with_transform=False)
        return prog.compile_program(rel, c.program, mask_outputs=(m,)), m

    compile_for(10)
    compile_for(20)
    assert len(small) == 2 and small.evictions == 0
    compile_for(30)                      # pushes imm=10 out
    assert len(small) == 2 and small.evictions == 1
    misses = small.misses
    compile_for(30)                      # LRU hit: no rebuild
    assert small.misses == misses and small.hits >= 1
    cp1, m1 = compile_for(10)            # evicted sig: rebuilt, still exact
    assert small.evictions >= 2
    res = prog.run_program(cp1, rel)
    np.testing.assert_array_equal(res.mask(m1), cols["a"] < 10)
    small.set_capacity(1)                # shrinking evicts immediately
    assert len(small) == 1
    with pytest.raises(ValueError):
        small.set_capacity(0)


def test_program_api_minimal():
    """compile_program/run_program on a hand-built relation program."""
    rng = np.random.default_rng(0)
    cols = {"k": rng.integers(0, 1 << 10, 5000),
            "v": rng.integers(0, 1 << 8, 5000)}
    rel = eng.PimRelation.from_columns("t", cols)
    c = Compiler(rel)
    mask_reg = c.compile_filter(Between(Col("k"), 100, 600),
                                with_transform=False)
    regs = c.compile_aggregates(mask_reg, [Agg("sum", Col("v"), "s"),
                                           Agg("count", None, "c"),
                                           Agg("min", Col("v"), "mn")])
    cp = prog.compile_program(rel, c.program, mask_outputs=(mask_reg,))
    res = prog.run_program(cp, rel)
    sel = (cols["k"] >= 100) & (cols["k"] <= 600)
    np.testing.assert_array_equal(res.mask(mask_reg), sel)
    assert res.scalar(regs["s"][1]) == int(cols["v"][sel].sum())
    assert res.scalar(regs["c"][1]) == int(sel.sum())
    assert res.scalar(regs["mn"][1]) == int(cols["v"][sel].min())
