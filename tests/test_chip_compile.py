"""Main-path kernels compile for a TPU v5e — no chip needed.

The TPU compiler is installed next to JAX, and it compiles for a chip
that is described rather than attached. These tests lower the fused
relation programs at TPC-H SF 1 lineitem width (6M records, 188,416
plane words) and compile them for one described v5e chip, and the
sharded Q6 program for four. Mosaic refuses here what it would refuse
on the chip (block shapes off the (8, 128) tiling, zero-sized vectors,
unsupported ops), so a kernel that only ever ran in interpret mode
cannot slip through. Nothing runs: results are the interpret-mode
suites' job.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import bitslice
from repro.core import program as prog
from repro.db import queries, tpch
from repro.db.database import PimDatabase
from repro.kernels import materialize as kmat

SF1_LINEITEM = 6_000_000
SHARD_AXES = ("pod", "data")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip would be written to the persistent
    # cache but could never be read back without one.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def lineitem():
    """(db, lineitem relation at SF 1 width): the attribute widths of a
    small generated lineitem, the word count of 6M records. Only shapes
    are compiled, so the planes themselves are never needed."""
    db = PimDatabase(tpch.generate(sf=0.001, seed=0))
    small = db.relations["lineitem"]
    layout = bitslice.RelationLayout(small.layout.attributes, SF1_LINEITEM)
    rel = dataclasses.replace(small, layout=layout, n_records=SF1_LINEITEM)
    assert rel.layout.n_words == 188_416
    return db, rel


def _program(db, rel, qname, backend, mesh=None):
    spec = queries.get_query(qname)
    c, mask_reg, _ = db._compile_relation(rel, spec,
                                          spec.filters["lineitem"])
    return prog.compile_program(rel, c.program, mask_outputs=(mask_reg,),
                                backend=backend, interpret=False, mesh=mesh)


def _args(cp, rel, plane_sharding, valid_sharding):
    w = rel.layout.n_words
    planes = {a: jax.ShapeDtypeStruct((rel.width_of(a), w), jnp.uint32,
                                      sharding=plane_sharding)
              for a in cp.analysis.source_attrs}
    return planes, jax.ShapeDtypeStruct((w,), jnp.uint32,
                                        sharding=valid_sharding)


@pytest.mark.parametrize("qname,backend", [("Q6", "jnp"), ("Q1", "jnp"),
                                           ("Q6", "pallas")])
def test_program_compiles_for_one_v5e(lineitem, one_chip, qname, backend):
    db, rel = lineitem
    cp = _program(db, rel, qname, backend)
    compiled = cp._fn.lower(*_args(cp, rel, one_chip, one_chip)).compile()
    if backend == "pallas":
        assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_materialize_pallas_compiles_for_one_v5e(lineitem, one_chip):
    _, rel = lineitem
    w = rel.layout.n_words
    planes = [jax.ShapeDtypeStruct((n, w), jnp.uint32, sharding=one_chip)
              for n in (26, 4)]
    mask = jax.ShapeDtypeStruct((w,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(lambda a, b, m: kmat.materialize_pallas([a, b], m,
                                                         interpret=False))
    compiled = fn.lower(*planes, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_q6_compiles_with_one_all_reduce(lineitem, topo):
    db, rel = lineitem
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), SHARD_AXES)
    cp = _program(db, rel, "Q6", "jnp", mesh=mesh)
    args = _args(cp, rel, NamedSharding(mesh, P(None, SHARD_AXES)),
                 NamedSharding(mesh, P(SHARD_AXES)))
    hlo = cp._fn.lower(*args).compile().as_text()
    n_all_reduce = sum(1 for line in hlo.splitlines()
                       if " all-reduce(" in line
                       or " all-reduce-start(" in line)
    assert n_all_reduce == 1, n_all_reduce
