"""Distributed behaviour: runs subprocesses with a multi-device host so
the main pytest process keeps seeing exactly 1 CPU device."""
from _mesh_subprocess import run_forced_multidevice as _run


def test_distributed_filter_and_aggregate():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import bitslice, distributed, engine
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        n = 4 * bitslice.TILE_RECORDS
        key = rng.integers(0, 1 << 16, n)
        val = rng.integers(0, 1 << 12, n)
        kp = jnp.asarray(bitslice.pack_bits(key, 16))
        vp = jnp.asarray(bitslice.pack_bits(val, 12))
        valid = jnp.asarray(bitslice.pack_mask(np.ones(n, bool)))
        kp = distributed.shard_relation_planes(kp, mesh)
        vp = distributed.shard_relation_planes(vp, mesh)
        valid = distributed.shard_relation_planes(valid, mesh)
        lo, hi = 1000, 30000
        prog = distributed.make_sum_where_program(lo, hi)
        run = distributed.distributed_filter_aggregate(mesh, prog)
        pcs = np.asarray(jax.jit(run)(kp, vp, valid))
        got = sum(int(pcs[b]) << b for b in range(12))
        want = int(val[(key >= lo) & (key < hi)].sum())
        assert got == want, (got, want)
        # pure filter: no collectives, sharded mask out
        filt = distributed.distributed_filter(
            mesh, lambda p: engine.cmp_imm_planes(p, hi)[0])
        mask = np.asarray(jax.jit(filt)(kp, valid))
        assert (bitslice.unpack_mask(mask, n) == (key < hi)).all()
        print("DIST-OK")
    """)
    assert "DIST-OK" in out


def test_distributed_valid_plane_padding_regression():
    """n_records NOT a multiple of TILE_RECORDS: the zero-padded tail
    records would satisfy `key >= 0 AND key < hi` (and add their val=0
    rows to popcounts via the mask) if the valid plane were not threaded
    through the distributed entry points. Covers the eager-distributed
    wrappers AND the fused-distributed program path."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import bitslice, distributed, engine
        from repro.core import program as prog
        from repro.db.compiler import Agg, Between, Col, Compiler
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(1)
        n = 2 * bitslice.TILE_RECORDS + 12345       # NOT a tile multiple
        W = bitslice.pad_words(n)
        assert n % bitslice.TILE_RECORDS != 0 and W * 32 > n
        key = rng.integers(1, 1 << 16, n)
        val = rng.integers(0, 1 << 12, n)
        kp = distributed.shard_relation_planes(
            jnp.asarray(bitslice.pack_bits(key, 16, W)), mesh)
        vp = distributed.shard_relation_planes(
            jnp.asarray(bitslice.pack_bits(val, 12, W)), mesh)
        valid = distributed.shard_relation_planes(
            jnp.asarray(bitslice.pack_mask(np.ones(n, bool), W)), mesh)
        lo, hi = 0, 30000     # lo=0: every zero-padded record passes the cmp
        run = distributed.distributed_filter_aggregate(
            mesh, distributed.make_sum_where_program(lo, hi))
        pcs = np.asarray(jax.jit(run)(kp, vp, valid))
        got = sum(int(pcs[b]) << b for b in range(12))
        want = int(val[(key >= lo) & (key < hi)].sum())
        assert got == want, (got, want)
        # eager filter: padding words must come back all-zero
        filt = distributed.distributed_filter(
            mesh, lambda p: engine.cmp_imm_planes(p, hi)[0])
        mask = np.asarray(jax.jit(filt)(kp, valid))
        assert (bitslice.unpack_mask(mask, n) == (key < hi)).all()
        assert not bitslice.unpack_bits(mask[None], W * 32)[n:].any()
        # fused-distributed program path on the same non-tile-multiple rel
        rel = engine.PimRelation.from_columns(
            "t", {"k": key, "v": val}).shard(mesh)
        c = Compiler(rel)
        m = c.compile_filter(Between(Col("k"), 0, hi - 1),
                             with_transform=False)
        regs = c.compile_aggregates(m, [Agg("sum", Col("v"), "s"),
                                        Agg("count", None, "c"),
                                        Agg("min", Col("k"), "mn")])
        cp = prog.compile_program(rel, c.program, mask_outputs=(m,),
                                  mesh=mesh)
        res = prog.run_program(cp, rel)
        sel = key < hi
        np.testing.assert_array_equal(res.mask(m), sel)
        assert not bitslice.unpack_bits(
            res.mask_packed(m)[None], W * 32)[n:].any()
        assert res.scalar(regs["s"][1]) == int(val[sel].sum())
        assert res.scalar(regs["c"][1]) == int(sel.sum())
        # MIN would be 0 (a padding record) without valid threading
        assert res.scalar(regs["mn"][1]) == int(key[sel].min())
        print("PAD-OK")
    """)
    assert "PAD-OK" in out

