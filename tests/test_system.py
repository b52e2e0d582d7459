"""End-to-end behaviour: the paper's system (bit-sliced analytics) plus
framework glue — quick integration checks."""
from repro.db import database, queries, tpch


def test_full_query_pipeline_end_to_end():
    """generate -> bit-slice -> compile -> execute -> aggregate == oracle,
    plus paper-style cost report fields."""
    db = database.PimDatabase(tpch.generate(sf=0.001, seed=7))
    spec = queries.get_query("Q6")
    pim = db.run_pim(spec)
    base = db.run_baseline(spec)
    assert pim.aggregates == base.aggregates
    rep = database.cost_report(pim, sf_scale=1000 / 0.001)
    assert rep.kind == "full"
    assert rep.speedup > 1
    assert rep.read_reduction > 50     # paper: >99% reads eliminated


def test_filter_only_read_reduction_headline():
    """Filter queries read ~1 bit/record instead of whole attributes."""
    db = database.PimDatabase(tpch.generate(sf=0.001, seed=7))
    spec = queries.get_query("Q14")     # single date-range filter
    rep = database.cost_report(db.run_pim(spec), sf_scale=1000 / 0.001)
    assert rep.read_reduction > 8      # 12-bit date attr vs 1 bit


def test_compile_cache_dir_is_env_var_or_checkout():
    """The persistent compile cache sits where JAX_COMPILATION_CACHE_DIR
    says, else at a fixed <checkout>/.jax_cache."""
    import os
    from pathlib import Path

    from repro.core import compile_cache

    checkout = Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == checkout / ".jax_cache"
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        compile_cache.DEFAULT_DIR)
    assert compile_cache.cache_dir() == want
    assert compile_cache.enable_persistent_cache() == want
