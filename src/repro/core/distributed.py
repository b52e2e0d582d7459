"""Distributed bulk-bitwise analytics: record-sharded relations on a mesh.

The paper's scale-out story (PIMDB §4; arXiv:2307.00658 §4): a relation
spans many huge-pages across many PIM modules; ONE PIM request is
broadcast to every page, each module's crossbars compute their local
pages, and the host combines the per-module reduce partials. Mapped to
JAX: relations are sharded along the packed-word (record) axis over the
``("pod", "data")`` mesh axes, every device executes the same compiled
bit-serial program on its shard (pure SPMD — the broadcast *is* the
program), and the host combine is a collective over the shard axes.

Mesh execution model
--------------------
The fused per-relation executable built by :func:`repro.core.program.
compile_program` is a pure function ``(planes dict, valid) -> outputs``,
so it is lowered once and wrapped with ``shard_map``
(:func:`shard_program_fn`):

* **inputs** — every ``(n_bits, W)`` bit-plane is partitioned
  ``P(None, shard_axes)`` (word axis sharded, bit axis replicated); the
  ``(W,)`` valid plane is partitioned ``P(shard_axes)``. Padding words
  beyond ``n_records`` are zeros in ``valid``, so shards holding the tail
  tile mask them off locally — valid-plane threading is what keeps
  zero-padded records from satisfying predicates on any shard.
* **filters** — each shard computes its packed result mask locally; the
  output mask stays sharded ``P(shard_axes)``. A pure filter needs NO
  collective at all ("each module computes its pages independently").
* **SUM/COUNT** — each shard emits the per-(group, bit) masked popcount
  partials of its *grouped* reduce jobs (every ReduceSum sharing a
  source plane stack rides one job — see ``core.program.plan_reduces``);
  one ``psum`` per job over the shard axes yields exact int32 totals,
  and the exact 2^b weighting still happens in host Python ints. This
  is the paper's "host combines per-crossbar reduce outputs", fused
  into the same single dispatch.
* **MIN/MAX** — each shard narrows its own candidates to a per-shard
  extremum (bit vector + found flag; inside the Pallas kernel this is
  itself a per-tile narrowing + cross-tile combine); an ``all_gather``
  over the shard axes followed by the same MSB-first bitwise combine
  (:func:`combine_minmax_candidates`) selects the global extremum,
  still inside the one dispatch and exact at any bit width.

Everything above is ONE logical dispatch per relation program: the
``jax.jit(shard_map(...))``-compiled executable.

Multi-query linked programs (``core.program.link_programs``) ride the
same wrapper with no distribution-specific handling: output masks stay
``P(shard_axes)`` regardless of how many queries contributed them, each
query's Materialize output keeps its own per-shard counts for the
host-side prefix stitch, and reduce partials psum per *job* — jobs
already batch across queries when linking lets their ReduceSums share a
source stack. A batch of N queries over one relation is therefore still
exactly one broadcast request to every module, now carrying N queries'
worth of outputs; per-query demultiplexing (``query_slots``) happens on
the host after the collective.

Harness API
-----------
``PimDatabase(tables, mesh=mesh, shard_axes=("pod", "data"))`` shards
every PIM-resident relation at load time (``PimRelation.shard``), and
``run_pim(spec)`` then transparently executes every TPC-H query via the
sharded fused path; ``fused=False`` keeps the eager oracle (which also
operates correctly on sharded arrays, via global ops). The thin eager
wrappers below (:func:`distributed_filter`,
:func:`distributed_filter_aggregate`) remain for word-level ad-hoc
programs; both now require the relation's valid plane.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import engine as eng


def mesh_shard_axes(mesh: Mesh,
                    axes: Optional[Sequence[str]] = None) -> Tuple[str, ...]:
    """Normalise the record-sharding axes: default = every mesh axis."""
    return tuple(axes) if axes else tuple(mesh.axis_names)


def shard_relation_planes(planes: jnp.ndarray, mesh: Mesh,
                          axes: Sequence[str] = ("data",)) -> jnp.ndarray:
    """Place planes with the packed-word axis sharded over ``axes``.

    Accepts ``(n_bits, W)`` attribute planes or a ``(W,)`` valid/mask
    plane — the word axis is always the last one.
    """
    ax = tuple(axes)
    spec = P(ax) if planes.ndim == 1 else P(*([None] * (planes.ndim - 1)), ax)
    return jax.device_put(planes, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Thin eager wrappers (word-level ad-hoc programs)
# --------------------------------------------------------------------------
def distributed_filter(mesh: Mesh, predicate_fn: Callable[..., jnp.ndarray],
                       shard_axes: Sequence[str] = ("data",)):
    """Wrap a word-level predicate (planes... -> packed mask) for a
    record-sharded relation. The result is ANDed with the relation's
    valid plane on each shard, so padding words beyond ``n_records``
    never pass. Output mask stays sharded like the input — no collective
    at all for a pure filter, exactly the paper's "each module computes
    its pages independently".
    """
    ax = mesh_shard_axes(mesh, shard_axes)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(None, ax), P(ax)),
             out_specs=P(ax), check_vma=False)
    def _run(planes, valid):
        return predicate_fn(planes) & valid

    return _run


def distributed_filter_aggregate(mesh: Mesh,
                                 program_fn: Callable[..., jnp.ndarray],
                                 shard_axes: Sequence[str] = ("data",)):
    """Filter + local aggregate + psum combine (paper §4.2: host combines
    the per-crossbar reduce outputs; here the 'host combine' is one psum
    over the record-sharding axes). ``program_fn(filter_planes,
    agg_planes, valid)`` must mask its selection with ``valid`` — see
    :func:`make_sum_where_program`."""
    ax = mesh_shard_axes(mesh, shard_axes)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(None, ax), P(None, ax), P(ax)), out_specs=P(),
             check_vma=False)
    def _run(filter_planes, agg_planes, valid):
        partial_val = program_fn(filter_planes, agg_planes, valid)
        return jax.lax.psum(partial_val, ax)

    return _run


def make_sum_where_program(imm_lo: int, imm_hi: int):
    """Example program: SUM(agg) WHERE lo <= key < hi — the canonical
    filter+aggregate kernel shape of the paper's full queries.

    Returns per-bit popcount partials (int32, in-graph safe); the caller
    weights them by 2^b in Python ints (the paper's host combine). The
    selection mask is ANDed with ``valid`` so zero-padded records beyond
    ``n_records`` (which would otherwise satisfy e.g. ``key < hi``)
    contribute nothing.
    """

    def program(filter_planes, agg_planes, valid):
        lt_lo, _ = eng.cmp_imm_planes(filter_planes, imm_lo)
        lt_hi, _ = eng.cmp_imm_planes(filter_planes, imm_hi)
        mask = ~lt_lo & lt_hi & valid
        return eng.reduce_sum_bits(agg_planes, mask)

    return program


# --------------------------------------------------------------------------
# Compiled-program sharding (the fused executor's distributed path)
# --------------------------------------------------------------------------
def combine_minmax_candidates(bits: jnp.ndarray, found: jnp.ndarray,
                              is_max: bool):
    """MIN/MAX candidate combine, exact at any bit width.

    ``bits`` is ``(n_candidates, n_bits)`` int32 per-candidate extremum
    bits (LSB-first), ``found`` is ``(n_candidates,)`` bool. MSB-first
    narrowing over the candidate axis — the same candidate-elimination
    the paper runs over crossbar rows, re-run over partial extrema. The
    candidate axis is *tiles* when the program kernel's per-tile MIN/MAX
    outputs are reduced (``core.program``), and *shards* when the
    per-shard extrema of the SPMD path are reduced below — one mechanism,
    both levels of the hierarchy. Returns ``((n_bits,) int32 global
    extremum bits, () bool any-found)``.
    """
    n_bits = bits.shape[1]
    cand = found
    out = [None] * n_bits
    for b in range(n_bits - 1, -1, -1):
        vb = bits[:, b] != 0
        if is_max:
            t = cand & vb
            has = jnp.any(t)
            out[b] = has.astype(jnp.int32)
            cand = jnp.where(has, t, cand)
        else:
            t = cand & ~vb
            has = jnp.any(t)
            out[b] = jnp.logical_not(has).astype(jnp.int32)
            cand = jnp.where(has, t, cand)
    return jnp.stack(out), jnp.any(found)


# Backwards-compatible name for the cross-shard call sites.
combine_minmax_shards = combine_minmax_candidates


def _gather_shards(x: jnp.ndarray, ax: Tuple[str, ...]) -> jnp.ndarray:
    """all_gather over the shard axes -> leading (n_shards,) axis."""
    return jax.lax.all_gather(x, ax)


def shard_program_fn(local_fn: Callable, mesh: Mesh,
                     shard_axes: Sequence[str], *,
                     source_attrs: Sequence[str],
                     mask_outputs: Sequence[str],
                     pc_job_keys: Sequence[str],
                     mm_items: Sequence[Tuple[str, bool]],
                     mat_items: Sequence[str] = ()) -> Callable:
    """Lift a compiled per-relation program function to SPMD on ``mesh``.

    ``local_fn(planes dict, valid) -> {"masks", "job_pc", "mm_bits",
    "mm_found"}`` is the pure single-device executable from
    ``core.program``; the returned function has the same signature and
    output structure but runs one shard per device: masks stay sharded,
    the per-(group, bit) popcount partials of each *grouped* reduce job
    are psum-combined as one ``(n_groups, n_bits)`` matrix — a single
    collective per source plane stack, however many group masks share it
    — and per-shard MIN/MAX candidate bits are gathered and reduced by
    :func:`combine_minmax_candidates`, the same combine the kernel's
    cross-tile reduction uses one level down. ``mat_items`` names the
    Materialize outputs: each shard compacts its own selected records
    against its local mask slice (masks never leave a device unsharded),
    the value buffer stays word-axis-sharded — shard ``s`` owns capacity
    columns ``[s*cap, (s+1)*cap)`` — and the per-shard counts come back
    as one ``(n_shards,)`` vector for the host-side prefix stitch
    (``ProgramResult.materialized``). No collective touches the values.
    Exactly ONE logical dispatch per relation program once jitted.
    """
    ax = mesh_shard_axes(mesh, shard_axes)
    in_specs = ({a: P(None, ax) for a in source_attrs}, P(ax))
    out_specs = {
        "masks": {m: P(ax) for m in mask_outputs},
        "job_pc": {k: P() for k in pc_job_keys},
        "mm_bits": {d: P() for d, _ in mm_items},
        "mm_found": {d: P() for d, _ in mm_items},
        "mat_vals": {d: P(None, ax) for d in mat_items},
        "mat_cnt": {d: P(ax) for d in mat_items},
    }

    @partial(jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
             check_vma=False)
    def _run(planes: Dict[str, jnp.ndarray], valid: jnp.ndarray):
        raw = local_fn(planes, valid)
        job_pc = {k: jax.lax.psum(raw["job_pc"][k], ax) for k in pc_job_keys}
        mm_bits: Dict[str, jnp.ndarray] = {}
        mm_found: Dict[str, jnp.ndarray] = {}
        for d, is_max in mm_items:
            gb = _gather_shards(raw["mm_bits"][d], ax)
            gf = _gather_shards(raw["mm_found"][d], ax)
            mm_bits[d], mm_found[d] = combine_minmax_candidates(gb, gf,
                                                                is_max)
        return {"masks": {m: raw["masks"][m] for m in mask_outputs},
                "job_pc": job_pc, "mm_bits": mm_bits, "mm_found": mm_found,
                "mat_vals": {d: raw["mat_vals"][d] for d in mat_items},
                "mat_cnt": {d: raw["mat_cnt"][d] for d in mat_items}}

    return _run
