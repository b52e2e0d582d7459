"""Pallas TPU kernel: a whole compiled filter program in one plane pass.

Where ``bitwise_filter.py`` evaluates one predicate per launch, this kernel
evaluates an *arbitrary compiled program DAG* — every comparison, mask
combine and bit-serial arithmetic op the ``db.compiler`` emitted for one
relation — over a single ``(n_bits, BLOCK_W)`` tile stream, plus **every
reduce** of the program:

* **Grouped popcounts** (``SumJob``): all ReduceSums sharing a source
  plane stack run as ONE job — each tile of the aggregate planes is
  popcounted once against the whole *stack* of group masks, and the
  per-(group, bit) int32 lane partials accumulate into a VMEM-resident
  accumulator block (constant output index map: the ``(n_pc, 128)`` block
  is revisited every grid step, zeroed at step 0; its lanes are summed
  once, after the last tile). TPC-H Q1's 6 group masks cost one read of
  each aggregate plane per tile instead of six — the paper's grouped
  aggregation inside the array (arXiv:2307.00658 §4).
* **MIN/MAX** (``MinMaxJob``): per-tile MSB-first candidate narrowing at
  the instruction's program position, emitting ``width`` candidate bits +
  a found flag per tile (one ``(8, 128k)`` output block per tile, so every
  block obeys Mosaic's (8, 128) tiling rule); the surrounding jit reduces
  them with the same cross-candidate combine the distributed path applies
  across shards (``core.distributed.combine_minmax_candidates``).
* **Derived arithmetic** lowers carry-save (``core.program.plan_arith``):
  every Multiply's partial products reduce in a log-depth 3:2 compressor
  tree (``engine.csa_reduce``) followed by ONE carry-propagate pass, and
  consecutive independent Add/Multiply instructions share one *batched*
  final pass — the serialized carry-chain depth (and with it the unrolled
  op count Mosaic/XLA must compile) drops from O(addends x bits) to
  O(addends + bits) per instruction.

Each grid step stages one tile of every *touched* source plane into VMEM
exactly once; the unrolled op sequence (immediates specialise it at trace
time, paper Algorithm 1) runs entirely on VPU registers. Register liveness
from ``core.program.plan_reduces`` (extended across grouped-job deferral)
is honoured inside the kernel body via the precomputed ``frees`` table, so
the per-tile VMEM working set tracks ``peak_live_planes``.

VMEM budget per grid step: (source rows + peak live planes) x BLOCK_W x
4 B plus the (n_pc, 128) accumulator — the worst evaluated program (TPC-H
Q1: ~55 source + ~90 live derived planes, ~200 accumulator rows) stays
under 1.5 MiB at BLOCK_W = 2048. The CSA tree transiently holds one
multiply's ungated partial-product stacks (Q1's widest: 8 x 39 planes per
tile) before compression collapses them; Mosaic is free to schedule the
3:2 levels eagerly, keeping the peak well under the ~2x headroom left.

Cross-query fusion (``core.program.link_programs``) feeds this kernel
*linked* multi-query programs unchanged: the kernel is agnostic to how
many queries produced the DAG — output masks are a list (one VMEM block
per mask, any count), every Materialize output compacts against its own
mask, and grouped reduce jobs batch across whatever ReduceSums share a
source stack, whichever query emitted them. The per-query wiring lives
entirely outside the kernel in ``CompiledProgram.query_slots``; what the
kernel gains from linking is purely workload-shaped: each *shared*
source plane is staged into VMEM once per tile for all queries, and
CSE-deduped instructions simply never reach the op sequence.

Distributed execution (``core.distributed.shard_program_fn``) wraps the
whole program function — this kernel included — in ``shard_map``: the
kernel then sees only its shard's word slice (``W / n_shards``, still a
multiple of a power of two, so ``pick_block`` always finds a dividing
block), its popcount accumulators are psum-combined per grouped job and
its per-shard MIN/MAX candidates gathered + combined in the surrounding
SPMD program, and it writes its shard of each output mask. The valid
plane rides along as the last stacked row per shard, so padding words
beyond ``n_records`` are masked off locally wherever they live.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import pick_block as _pick_block, popcount as _popcount

U32 = jnp.uint32
BLOCK_W = 2048
LANES = 128
SUBLANES = 8


def _program_kernel(stacked_ref, masks_ref, pc_ref, mm_ref, *, instrs,
                    attr_rows, valid_row, mask_outputs, sum_jobs, mm_jobs,
                    frees, arith_batches):
    from repro.core.program import BitwiseEvaluator, _reduce_minmax_bits

    allp = stacked_ref[...]                      # (rows, block_w) in VMEM
    ev = BitwiseEvaluator(lambda a: allp[attr_rows[a][0]:attr_rows[a][1]],
                          allp[valid_row])

    # Per-(group, bit) popcount accumulators live in the revisited output
    # block across the whole grid; zero them on the first tile.
    @pl.when(pl.program_id(0) == 0)
    def _zero_accumulators():
        pc_ref[...] = jnp.zeros_like(pc_ref)

    jobs_at: Dict[int, List] = {}
    for job in sum_jobs:
        jobs_at.setdefault(job.exec_at, []).append(job)
    mm_at = {mj.exec_at: mj for mj in mm_jobs}
    batch_at = {b[0]: b for b in arith_batches}
    batched = {i for b in arith_batches for i in b}
    # This tile's MIN/MAX candidate row: column c of a lane-aligned row,
    # filled one scalar at a time by select (Mosaic has no scalar stores
    # into a vector block).
    mm_lane = jax.lax.broadcasted_iota(jnp.int32, (1, mm_ref.shape[1]), 1)
    mm_row = jnp.zeros((1, mm_ref.shape[1]), jnp.int32)

    for i, ins in enumerate(instrs):
        if ins.kind in ("ReduceSum", "Materialize"):
            # ReduceSum runs at its grouped job's exec_at; Materialize is
            # lowered as a second kernel over the attr planes (its mask
            # rides the mask_outputs block) — see kernels.materialize.
            pass
        elif ins.kind == "ReduceMinMax":
            mj = mm_at[i]
            bits, found = _reduce_minmax_bits(
                ev.planes(mj.attr)[:mj.width], ev.masks[mj.mask], mj.is_max)
            for c, v in enumerate(bits + [found.astype(jnp.int32)]):
                mm_row = jnp.where(mm_lane == mj.col_start + c, v, mm_row)
        elif i in batch_at:
            # Independent derived-arith run: per-member CSA trees + ONE
            # batched carry-propagate pass (core.program.plan_arith).
            ev.execute_arith_batch([instrs[j] for j in batch_at[i]])
        elif i in batched:
            pass                       # ran with its batch at batch_at
        else:
            ev.execute(ins)
        for job in jobs_at.get(i, ()):
            # ONE read of each aggregate plane for the whole mask stack.
            # Row ``col_start + b * g + k`` of the accumulator holds the
            # 128 lane partials of (bit b, mask k); the caller sums the
            # lanes once, after the last tile.
            p = ev.planes(job.attr)
            g = len(job.masks)
            for b in range(job.width):
                for k, m in enumerate(job.masks):
                    r = job.col_start + b * g + k
                    pc_ref[r:r + 1, :] += _lane_fold(
                        _popcount(ev.masks[m] & p[b]).astype(jnp.int32))
        for r in frees[i]:
            ev.free(r)
    mm_ref[...] = jnp.broadcast_to(mm_row, mm_ref.shape)
    for k, name in enumerate(mask_outputs):
        masks_ref[k, :] = ev.masks[name]
    if not mask_outputs:
        masks_ref[0, :] = jnp.zeros_like(masks_ref[0, :])


def _lane_fold(v: jax.Array) -> jax.Array:
    """(block_w,) -> (1, 128): sum of the tile's lane-aligned slices."""
    acc = v[:LANES]
    for k in range(1, v.shape[0] // LANES):
        acc = acc + v[k * LANES:(k + 1) * LANES]
    return acc[None, :]


def fused_program(stacked: jax.Array, *,
                  instrs: Sequence,
                  attr_rows: Mapping[str, Tuple[int, int]],
                  valid_row: int,
                  mask_outputs: Tuple[str, ...],
                  sum_jobs: Sequence,
                  mm_jobs: Sequence,
                  frees: Tuple[Tuple[str, ...], ...],
                  arith_batches: Tuple[Tuple[int, ...], ...] = (),
                  n_pc_cols: int,
                  n_mm_cols: int,
                  block_w: int = BLOCK_W,
                  interpret: bool = False):
    """Run a whole compiled relation program in one kernel launch.

    stacked: (rows, W) uint32 — every touched source bit-plane + the valid
    plane at ``valid_row``. ``sum_jobs``/``mm_jobs`` are the
    ``core.program.plan_reduces`` jobs (grouped popcounts + per-tile
    MIN/MAX); ``frees`` maps each instruction index to the registers that
    die right after it. Returns ``(masks, pc_totals, mm_tiles)``:

    * ``masks`` — (len(mask_outputs), W) packed uint32 result masks;
    * ``pc_totals`` — (1, n_pc_cols) int32 popcount totals, already
      accumulated over every tile, column ``job.col_start + b * n_groups
      + g`` holding (bit b, group g) of its job;
    * ``mm_tiles`` — (n_tiles, n_mm_cols) int32 per-tile MIN/MAX
      candidate bits + found flags, for the caller's cross-tile combine.
    """
    rows, w = stacked.shape
    block_w = _pick_block(w, block_w)
    n_tiles = w // block_w
    grid = (n_tiles,)
    # Both small outputs are laid out in whole (8, 128) tiles: the popcount
    # accumulator as one row of lane partials per column, the MIN/MAX
    # candidates as one 8-row block per tile (row 0 is read back).
    n_pc = _round_up(max(1, n_pc_cols), SUBLANES)
    n_mm = _round_up(max(1, n_mm_cols), LANES)
    n_mask_rows = max(1, len(mask_outputs))

    kernel = functools.partial(
        _program_kernel, instrs=tuple(instrs), attr_rows=dict(attr_rows),
        valid_row=valid_row, mask_outputs=tuple(mask_outputs),
        sum_jobs=tuple(sum_jobs), mm_jobs=tuple(mm_jobs),
        frees=tuple(frees), arith_batches=tuple(arith_batches))
    masks, pc_lanes, mm_blocks = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, block_w), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((n_mask_rows, block_w), lambda i: (0, i)),
                   pl.BlockSpec((n_pc, LANES), lambda i: (0, 0)),
                   pl.BlockSpec((SUBLANES, n_mm), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_mask_rows, w), U32),
                   jax.ShapeDtypeStruct((n_pc, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((n_tiles * SUBLANES, n_mm),
                                        jnp.int32)],
        interpret=interpret,
    )(stacked)
    pc_totals = jnp.sum(pc_lanes, axis=1)[None, :max(1, n_pc_cols)]
    mm_tiles = mm_blocks[::SUBLANES, :max(1, n_mm_cols)]
    return masks, pc_totals, mm_tiles


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m
