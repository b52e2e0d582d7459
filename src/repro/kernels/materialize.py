"""Pallas TPU kernel: mask-selected bit-plane materialization in one pass.

The inverse of ``bitslice.pack``: given the bit-sliced planes of one or
more attributes and a packed selection mask (the output of a PIM filter
program), produce the *compacted* integer column values of the selected
records — the step that turns a PIM selection back into host-joinable
rows (arXiv:2302.01675 / arXiv:2307.00658: PIM selection + host
join/aggregation).

Decoding is the one pass over the planes: bit ``b`` of plane word ``w``
is record ``w*32 + b``, so each attribute decodes to a ``(32, W)`` int32
block (row ``b``, column ``w``) with nothing but shifts, masks and ORs —
per tile inside the Pallas kernel, full-width in the jnp lowering. The
compaction that follows touches only the decoded values: a selected
record's output slot is the popcount of the mask words before its own
plus the popcount of the lower bits of its own word, and one scatter
moves it there. Capacity equals the padded record count — the host
reads back only ``count`` rows, which is the paper's readout-traffic
win; device memory holds the (zero) tail.

``materialize`` is the standalone entry point (property-tested against
the NumPy unpack+gather oracle); ``materialize_planes`` is the jnp
lowering the fused executor's jnp backend calls, and
``materialize_pallas`` the kernel-backed one (``kernels/program`` wires
it behind the ``isa.Materialize`` instruction).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import interpret_off_tpu, pick_block as _pick_block, \
    popcount as _popcount

U32 = jnp.uint32
# Words per materialize tile: 512 words = 16 384 records; the per-tile
# decoded block is (n_attrs, 32, 512) int32 = 64 KiB per attribute in
# VMEM, well under budget for the handful of columns a query materializes.
BLOCK_W = 512
WORD_BITS = 32


def decode_planes(planes: jnp.ndarray) -> jnp.ndarray:
    """(n_bits, n_words) uint32 planes -> (32, n_words) int32 values:
    element ``[b, w]`` is record ``w*32 + b`` — the bit-transpose half of
    the inverse of ``bitslice.pack_bits``, usable inside kernel bodies."""
    shape = (WORD_BITS, planes.shape[1])
    shift = jax.lax.broadcasted_iota(U32, shape, 0)
    out = jnp.zeros(shape, jnp.int32)
    for b in range(planes.shape[0]):
        bit = (planes[b:b + 1] >> shift) & U32(1)
        out = out | (bit.astype(jnp.int32) << b)
    return out


def _compact(vals: jnp.ndarray, mask: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable stream compaction of decoded ``(n_attrs, 32, W)`` values
    under the packed ``(W,)`` mask: the selected records move to the
    front of an ``(n_attrs, W*32)`` buffer, in record order; the tail is
    zeros. Returns ``(values, (1,) count)``.

    Every record, in record order, adds its value (zero when unselected)
    to slot "selected records before it" — an index that never
    decreases, so the scatter is a sorted one, which XLA compiles in
    about a second at any width (an unsorted scatter of a small relation
    takes the TPU compiler tens of seconds)."""
    n_attrs, _, w = vals.shape
    counts = _popcount(mask).astype(jnp.int32)
    before = jnp.cumsum(counts) - counts          # exclusive word prefix
    b = jax.lax.broadcasted_iota(U32, (w, WORD_BITS), 1)   # [w, b] order
    low = (U32(1) << b) - U32(1)                  # bits below b in a word
    pos = before[:, None] + _popcount(mask[:, None] & low).astype(jnp.int32)
    hit = ((mask[:, None] >> b) & U32(1)) != 0
    rec = jnp.where(hit[None], jnp.transpose(vals, (0, 2, 1)), 0)
    out = jnp.zeros((n_attrs, w * WORD_BITS), jnp.int32).at[
        :, pos.reshape(-1)].add(rec.reshape(n_attrs, -1),
                                indices_are_sorted=True,
                                mode="promise_in_bounds")
    return out, jnp.sum(counts)[None]


def materialize_planes(attr_planes: Sequence[jnp.ndarray],
                       mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp lowering: full-width decode + compaction in one traced graph.

    attr_planes: per-attribute ``(n_bits_a, W)`` uint32 plane stacks;
    mask: ``(W,)`` packed uint32 selection (must already include the
    relation's valid plane, so padding records are never selected).
    Returns ``((n_attrs, W*32) int32 values, (1,) int32 count)`` — the
    first ``count`` columns are the selected records, in record order.
    """
    return _compact(jnp.stack([decode_planes(p) for p in attr_planes]), mask)


# --------------------------------------------------------------------------
# Pallas kernel: per-tile decode, then the compaction over decoded values
# --------------------------------------------------------------------------
def _materialize_kernel(stacked_ref, vals_ref, *, attr_rows):
    allp = stacked_ref[...]                       # (rows, block_w) in VMEM
    for k, (r0, r1) in enumerate(attr_rows):
        vals_ref[k] = decode_planes(allp[r0:r1])


def materialize_pallas(attr_planes: Sequence[jnp.ndarray],
                       mask: jnp.ndarray, *, block_w: int = BLOCK_W,
                       interpret: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel-backed materialization: ONE pass over the attribute planes.

    Same contract as :func:`materialize_planes`. The kernel decodes each
    tile of the planes; the compaction (a scatter, which Mosaic cannot
    lower) runs on the decoded values in the surrounding jit — the
    planes are never re-read.
    """
    attr_rows: List[Tuple[int, int]] = []
    r0 = 0
    for p in attr_planes:
        attr_rows.append((r0, r0 + p.shape[0]))
        r0 += p.shape[0]
    stacked = jnp.concatenate(list(attr_planes), axis=0)
    rows, w = stacked.shape
    block_w = _pick_block(w, block_w)
    n_attrs = len(attr_rows)

    kernel = functools.partial(_materialize_kernel,
                               attr_rows=tuple(attr_rows))
    vals = pl.pallas_call(
        kernel,
        grid=(w // block_w,),
        in_specs=[pl.BlockSpec((rows, block_w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n_attrs, WORD_BITS, block_w),
                               lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n_attrs, WORD_BITS, w), jnp.int32),
        interpret=interpret,
    )(stacked)
    return _compact(vals, mask)


# --------------------------------------------------------------------------
# Standalone entry point (property-tested against the NumPy oracle)
# --------------------------------------------------------------------------
def materialize(planes, mask, backend: str = "jnp",
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, int]:
    """Materialize one attribute (or a list of attributes) under ``mask``.

    planes: ``(n_bits, W)`` uint32 plane stack, or a sequence of them;
    mask: ``(W,)`` packed uint32. Returns ``(values, count)`` where
    ``values[..., :count]`` are the selected records' integers in record
    order — equal to ``unpack_bits(planes, n)[unpack_mask(mask, n)]``.
    """
    single = hasattr(planes, "ndim")
    plane_list = [jnp.asarray(planes)] if single else \
        [jnp.asarray(p) for p in planes]
    m = jnp.asarray(mask)
    if interpret is None:
        interpret = interpret_off_tpu()
    if backend == "pallas":
        vals, cnt = materialize_pallas(plane_list, m, interpret=interpret)
    else:
        vals, cnt = materialize_planes(plane_list, m)
    count = int(jax.device_get(cnt)[0])
    return (vals[0] if single else vals), count
