"""Paper Table 4 / §6 analytics: cycle formulas, energy, endurance, and
the bands of Figs. 8a, 8b, 11, 15 and Table 5 over every query."""
import pytest

from repro.core import cost_model as cm
from repro.core import isa


def test_table4_cycle_formulas():
    # spot checks against Table 4 with hand-computed imm0/imm1
    assert isa.EqualImm(dest="", attr="a", imm=0b1011, n_bits=4).cycles() \
        == 1 + 3 * 3 + 1          # imm0=1, imm1=3
    assert isa.NotEqualImm(dest="", attr="a", imm=0, n_bits=5).cycles() \
        == 5 + 0 + 3
    assert isa.LessThanImm(dest="", attr="a", imm=0b11, n_bits=4).cycles() \
        == 11 * 2 + 3 * 2 + 4
    assert isa.GreaterThanImm(dest="", attr="a", imm=0b1111, n_bits=4).cycles() \
        == 0 + 3 * 4 + 2
    assert isa.AddImm(dest="", attr="a", imm=1, n_bits=8).cycles() == 18 * 8 + 3
    assert isa.Equal(dest="", attr_a="a", attr_b="b", n_bits=12).cycles() \
        == 11 * 12 + 3
    assert isa.LessThan(dest="", attr_a="a", attr_b="b", n_bits=7).cycles() \
        == 16 * 7 + 2
    assert isa.Add(dest="", attr_a="a", attr_b="b", n_bits=16).cycles() \
        == 18 * 16 + 1
    assert isa.Multiply(dest="", attr_a="a", attr_b="b",
                        n_bits=8, m_bits=4).cycles() \
        == 24 * 8 * 4 - 19 * 8 + 2 * 4 - 1
    assert isa.ReduceSum(dest="", attr="a", mask="m", n_bits=10).cycles() \
        == 2254 * 10 + 3006
    assert isa.ReduceMinMax(dest="", attr="a", mask="m", n_bits=10).cycles() \
        == 2306 * 10 + 200
    assert isa.ColumnTransform(dest="", mask="m").cycles() == 2050
    assert isa.SetReset(dest="", value=1, n_bits=3).cycles() == 3
    assert isa.BitwiseAnd(dest="", src_a="a", src_b="b", n_bits=1).cycles() == 6
    assert isa.BitwiseOr(dest="", src_a="a", src_b="b", n_bits=1).cycles() == 4
    assert isa.BitwiseNot(dest="", src="a", n_bits=1).cycles() == 2


def test_intermediate_cells_match_table4():
    assert isa.LessThanImm(dest="", attr="a", imm=1, n_bits=4).intermediate_cells() == 5
    assert isa.ReduceSum(dest="", attr="a", mask="m", n_bits=10).intermediate_cells() == 25
    assert isa.ReduceMinMax(dest="", attr="a", mask="m", n_bits=10).intermediate_cells() == 17


def test_program_classification():
    prog = [isa.EqualImm(dest="m", attr="a", imm=3, n_bits=4),
            isa.ReduceSum(dest="s", attr="b", mask="m", n_bits=8),
            isa.ColumnTransform(dest="c", mask="m")]
    cost = cm.classify_program(prog)
    assert cost.cycles_filter > 0
    assert cost.cycles_reduce_row > 0 and cost.cycles_reduce_col > 0
    assert cost.cycles_col_transform == 2050
    assert cost.cycles_total == sum(cost.breakdown().values())


def test_timing_read_reduction_drives_speedup():
    cost = cm.ProgramCost(cycles_filter=500)
    n = 10_000_000
    base_bytes = n * 4                       # 32-bit attribute scan
    pim_bytes = cm.pim_read_bytes_filter(n)  # 1 bit per record
    t = cm.query_timing(cost, n, n // 1024, base_bytes, pim_bytes)
    assert t.read_reduction == pytest.approx(32.0, rel=0.01)
    assert t.speedup > 1.0


def test_energy_and_endurance_positive():
    cost = cm.ProgramCost(cycles_filter=500, cycles_reduce_col=2000,
                          cycles_reduce_row=20000)
    t = cm.query_timing(cost, 10**7, 10**4, 10**7, 10**5)
    e = cm.query_energy(cost, t, 10**4)
    assert e.pimdb_total_j > 0 and e.baseline_j > 0
    end = cm.endurance_ops_per_cell(cost, exec_time_s=t.pimdb_total_s)
    # paper Fig. 15: well under RRAM's 1e12 for realistic queries
    assert 0 < end < 1e14


def test_baseline_cacheline_model():
    # selective later columns cost less, but never more than a full scan
    full = cm.baseline_scan_bytes(10**6, [32, 32], [1.0, 1.0])
    sel = cm.baseline_scan_bytes(10**6, [32, 32], [0.001, 1.0])
    assert sel < full
    assert sel >= 10**6 * 4        # first column always fully scanned


# --------------------------------------------------------------------------
# Paper figures at paper scale: every query's cost report in the band the
# paper reports (SF 0.003 generated, projected to the paper's SF 1000).
# --------------------------------------------------------------------------
FIG_SF = 0.003
FIG_SCALE = 1000 / FIG_SF
RRAM_ENDURANCE = 1e12


@pytest.fixture(scope="module")
def paper_reports():
    from repro.db import database, queries, tpch
    db = database.PimDatabase(tpch.generate(sf=FIG_SF, seed=42))
    return {spec.name: (spec, database.cost_report(
        db.execute(spec.filter_only()), FIG_SCALE))
        for spec in queries.all_queries()}


def _in_band(lo, hi, value):
    return lo <= value <= hi


def _fig8a(spec, rep):
    # paper: filter-only speedups 0.82x-18x
    return spec.kind != "filter" or _in_band(0.7, 30.0, rep.speedup)


def _fig8b(spec, rep):
    # paper: full-query speedups 56x-608x
    return spec.kind != "full" or _in_band(40.0, 900.0, rep.speedup)


def _table5(spec, rep):
    # filter queries end in a column transform and reduce nothing; full
    # queries spend more cycles reducing than filtering
    b = rep.cycles
    if spec.kind == "filter":
        return b["col_transform"] > 0 and b["reduce_col"] == 0
    return b["reduce_col"] + b["reduce_row"] > b["filter"]


def _fig11(spec, rep):
    # paper: energy savings 0.88x-15.3x (filter), 0.81x-12x (full)
    lo, hi = (0.5, 40.0) if spec.kind == "filter" else (0.7, 30.0)
    return _in_band(lo, hi, rep.energy_saving)


def _fig15(spec, rep):
    # every query stays within RRAM endurance except Q22_sub, whose small
    # relation concentrates back-to-back executions on the same cells
    within = rep.endurance_ops_per_cell_10y < RRAM_ENDURANCE
    return within == (spec.name != "Q22_sub")


FIGURES = {"fig8a": _fig8a, "fig8b": _fig8b, "table5": _table5,
           "fig11": _fig11, "fig15": _fig15}


@pytest.mark.parametrize("figure,only,skip", [
    pytest.param("fig8a", None, (), id="fig8a"),
    pytest.param("fig8b", None, (), id="fig8b"),
    pytest.param("table5", None, (), id="table5"),
    pytest.param("fig11", None, (), id="fig11"),
    pytest.param("fig15", None, ("Q6",), id="fig15"),
    pytest.param("fig15", "Q6", (), id="fig15-Q6", marks=pytest.mark.xfail(
        strict=True, reason="Q6 needs 1.42e12 writes per cell over 10 "
        "years at SF 1000; the paper puts it within RRAM's 1e12")),
])
def test_paper_figure_bands(paper_reports, figure, only, skip):
    check = FIGURES[figure]
    names = [only] if only else [n for n in paper_reports if n not in skip]
    out = [n for n in names if not check(*paper_reports[n])]
    assert not out, f"{figure}: out of band: {out}"
