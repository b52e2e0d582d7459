"""Program-level fused execution: one compiled pass per relation program.

The eager :class:`~repro.core.engine.Engine` executes one ISA instruction
at a time — every predicate re-reads its bit-planes from memory and every
``ReduceSum`` round-trips through Python ints. The paper's whole point
(PIMDB §4, Algorithm 1) is the opposite: the *entire* compiled filter
program runs inside the array with a single result readout.

This module is the TPU analogue of that: :func:`compile_program` takes the
full ``isa.PimInstruction`` list a :class:`~repro.db.compiler.Compiler`
emits for one relation (predicate DAG + valid-AND + aggregates), performs
register liveness / plane-reuse analysis, and lowers it into a single
``jax.jit``-compiled function. Each query then makes **one** pass over the
touched planes per relation; masked per-bit popcounts for every aggregate
come back from the same dispatch, and only the final exact 2^b weighting
(arbitrary-precision) happens in host Python.

Backends:

* ``backend="jnp"``    — the whole program traced as one jnp graph.
* ``backend="pallas"`` — the predicate DAG + every reduce run inside one
  Pallas kernel (``repro.kernels.program``) streaming
  ``(n_bits, BLOCK_W)`` tiles: grouped popcounts accumulate into
  per-(group, bit) int32 VMEM accumulators across the grid, and MIN/MAX
  narrows per tile, emitting candidate bits a cross-tile combine reduces.

Both backends share one :func:`plan_reduces` step: every ``ReduceSum``
over the same source plane stack is coalesced into a single *grouped*
popcount job — one read of the aggregate planes serves all of a query's
group masks (TPC-H Q1's 6 groups drop from 6 plane-stack reads per pass
to 1; the plan records both counts for the bench trajectory). Grouped
jobs execute at the program position of their last member, so the plan
also extends register liveness across the deferral.

The eager engine is unchanged and remains the oracle for tests.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
from typing import (Callable, Dict, FrozenSet, List, Mapping, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import bitslice, isa
from . import engine as eng

U32 = jnp.uint32
_FULL = np.uint32(0xFFFFFFFF)

_REDUCE_KINDS = ("ReduceSum", "ReduceMinMax")
_DERIVED_KINDS = ("AddImm", "Add", "Subtract", "Multiply")


# --------------------------------------------------------------------------
# Static analysis: operand reads, register kinds, liveness
# --------------------------------------------------------------------------
def instruction_reads(ins: isa.PimInstruction) -> List[str]:
    """Register/attribute names an instruction reads, in operand order."""
    k = ins.kind
    if k in ("EqualImm", "NotEqualImm", "LessThanImm", "GreaterThanImm",
             "AddImm"):
        return [ins.attr]
    if k in ("Equal", "LessThan", "Add", "Subtract"):
        return [ins.attr_a, ins.attr_b]
    if k == "Multiply":
        return [ins.attr_a] + ([ins.attr_b] if ins.attr_b else [])
    if k in ("BitwiseAnd", "BitwiseOr"):
        return [ins.src_a, ins.src_b]
    if k == "BitwiseNot":
        return [ins.src]
    if k == "SetReset":
        return []
    if k in ("PlaneWrite", "ValidClear"):
        # DML write kinds: row/value payloads ride in the instruction
        # itself (Algorithm 1 style) — no register operands.
        return []
    if k in _REDUCE_KINDS:
        return [ins.attr, ins.mask]
    if k == "Materialize":
        return [*ins.attrs, ins.mask]
    if k == "ColumnTransform":
        return [ins.mask]
    raise ValueError(f"unknown instruction {k}")


@dataclasses.dataclass(frozen=True)
class ProgramAnalysis:
    """Liveness / plane-usage facts about one instruction program."""
    source_attrs: Tuple[str, ...]          # relation attributes read
    reg_kind: Mapping[str, str]            # register -> mask|derived|scalar
    widths: Mapping[str, int]              # register -> planes it occupies
    last_use: Mapping[str, int]            # register -> last reading instr
    peak_live_planes: int                  # max simultaneously-live planes
    total_reg_planes: int                  # planes if nothing were freed

    def width_of(self, name: str, relation: "eng.PimRelation") -> int:
        if name in self.widths:
            return self.widths[name]
        return relation.width_of(name)


def analyze_program(instrs: Sequence[isa.PimInstruction],
                    relation: eng.PimRelation,
                    keep: Sequence[str] = ()) -> ProgramAnalysis:
    """Classify registers, find source attributes, compute liveness.

    ``keep`` registers are pinned live through the end of the program
    (the outputs the caller will read).
    """
    reg_kind: Dict[str, str] = {"__valid__": "mask"}
    widths: Dict[str, int] = {"__valid__": 1}
    last_use: Dict[str, int] = {}
    source: List[str] = []
    for i, ins in enumerate(instrs):
        for r in instruction_reads(ins):
            if r in reg_kind:
                last_use[r] = i
            else:
                if r not in relation.planes:
                    from repro.analysis import ProgramVerificationError
                    raise ProgramVerificationError.single(
                        "analyze",
                        f"reads '{r}' which is neither a prior dest nor a "
                        "relation attribute", instr_index=i,
                        instr_kind=ins.kind, register=r)
                if r not in source:
                    source.append(r)
        k = ins.kind
        if k in ("PlaneWrite", "ValidClear"):
            # Write kinds target relation storage (an attribute's planes
            # or the valid plane), not a program register: no dest entry.
            continue
        if k in _REDUCE_KINDS:
            reg_kind[ins.dest] = "scalar"
            widths[ins.dest] = 0
        elif k == "Materialize":
            # Materialized values live in the readout path, not in planes.
            reg_kind[ins.dest] = "values"
            widths[ins.dest] = 0
        elif k in _DERIVED_KINDS:
            reg_kind[ins.dest] = "derived"
            widths[ins.dest] = ins.n_bits
        elif k == "BitwiseNot" and reg_kind.get(ins.src) != "mask":
            # Attribute NOT (the imm - attr path): multi-plane result.
            reg_kind[ins.dest] = "derived"
            widths[ins.dest] = ins.n_bits
        else:
            reg_kind[ins.dest] = "mask"
            widths[ins.dest] = 1
    for r in keep:
        last_use[r] = len(instrs)

    # Peak live planes: forward sweep, registers die after their last use.
    live: Dict[str, int] = {}
    peak = 0
    for i, ins in enumerate(instrs):
        if ins.kind in ("PlaneWrite", "ValidClear"):
            continue
        if reg_kind.get(ins.dest) != "scalar":
            live[ins.dest] = widths[ins.dest]
        peak = max(peak, sum(live.values()))
        for r in instruction_reads(ins):
            if r in live and last_use.get(r) == i:
                del live[r]
    total = sum(w for n, w in widths.items() if n != "__valid__")
    return ProgramAnalysis(tuple(source), reg_kind, widths, last_use,
                           peak, total)


# --------------------------------------------------------------------------
# Shared evaluator for the non-reduce ISA subset
# --------------------------------------------------------------------------
class BitwiseEvaluator:
    """Executes the bitwise/arithmetic ISA subset on jnp values.

    Works identically on full-width planes (the fused jnp trace) and on
    one VMEM tile inside the Pallas program kernel — the per-immediate op
    specialisation (Algorithm 1) happens at trace time either way.
    Reduces are the caller's job. Mirrors ``Engine.execute`` semantics
    bit-for-bit, including unrepresentable-immediate short-circuits.
    """

    def __init__(self, plane_source: Callable[[str], jnp.ndarray],
                 valid: jnp.ndarray):
        self._source = plane_source
        self.masks: Dict[str, jnp.ndarray] = {"__valid__": valid}
        self.derived: Dict[str, jnp.ndarray] = {}
        self._shape = valid.shape
        self.freed = 0

    def planes(self, name: str) -> jnp.ndarray:
        if name in self.derived:
            return self.derived[name]
        if name in self.masks:
            return self.masks[name][None]
        return self._source(name)

    def free(self, name: str) -> None:
        """Drop a dead register so XLA/Mosaic can reuse its planes."""
        if name == "__valid__":
            return
        if self.derived.pop(name, None) is not None:
            self.freed += 1
        elif self.masks.pop(name, None) is not None:
            self.freed += 1

    def execute(self, instr: isa.PimInstruction) -> None:
        kind = instr.kind
        if kind == "EqualImm":
            p = self.planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):
                self.masks[instr.dest] = jnp.zeros(self._shape, U32)
            else:
                self.masks[instr.dest] = eng.eq_imm_planes(p, instr.imm)
        elif kind == "NotEqualImm":
            p = self.planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):
                self.masks[instr.dest] = jnp.full(self._shape, _FULL, U32)
            else:
                self.masks[instr.dest] = ~eng.eq_imm_planes(p, instr.imm)
        elif kind == "LessThanImm":
            p = self.planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):
                self.masks[instr.dest] = jnp.full(self._shape, _FULL, U32)
            else:
                lt, eq = eng.cmp_imm_planes(p, instr.imm)
                self.masks[instr.dest] = (lt | eq) if instr.or_equal else lt
        elif kind == "GreaterThanImm":
            p = self.planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):
                self.masks[instr.dest] = jnp.zeros(self._shape, U32)
            else:
                lt, eq = eng.cmp_imm_planes(p, instr.imm)
                self.masks[instr.dest] = ~lt if instr.or_equal else ~(lt | eq)
        elif kind == "Equal":
            _, eq = eng.cmp_planes(self.planes(instr.attr_a),
                                   self.planes(instr.attr_b))
            self.masks[instr.dest] = eq
        elif kind == "LessThan":
            lt, eq = eng.cmp_planes(self.planes(instr.attr_a),
                                    self.planes(instr.attr_b))
            self.masks[instr.dest] = (lt | eq) if instr.or_equal else lt
        elif kind == "BitwiseAnd":
            self.masks[instr.dest] = (self.masks[instr.src_a]
                                      & self.masks[instr.src_b])
        elif kind == "BitwiseOr":
            self.masks[instr.dest] = (self.masks[instr.src_a]
                                      | self.masks[instr.src_b])
        elif kind == "BitwiseNot":
            if instr.src in self.masks:
                self.masks[instr.dest] = ~self.masks[instr.src]
            else:
                p = self.planes(instr.src)
                w = instr.n_bits
                if p.shape[0] < w:
                    pad = jnp.zeros((w - p.shape[0],) + p.shape[1:], U32)
                    p = jnp.concatenate([p, pad], axis=0)
                self.derived[instr.dest] = ~p[:w]
        elif kind == "SetReset":
            fill = _FULL if instr.value else np.uint32(0)
            self.masks[instr.dest] = jnp.full(self._shape, fill, U32)
        elif kind == "AddImm":
            self.derived[instr.dest] = eng.add_imm_planes(
                self.planes(instr.attr), instr.imm, instr.n_bits)
        elif kind == "Add":
            self.derived[instr.dest] = eng.add_planes(
                self.planes(instr.attr_a), self.planes(instr.attr_b),
                instr.n_bits)
        elif kind == "Subtract":
            self.derived[instr.dest] = eng.sub_planes(
                self.planes(instr.attr_a), self.planes(instr.attr_b),
                instr.n_bits)
        elif kind == "Multiply":
            if instr.imm is not None:
                self.derived[instr.dest] = eng.mul_imm_planes_csa(
                    self.planes(instr.attr_a), instr.imm, instr.n_bits)
            else:
                self.derived[instr.dest] = eng.mul_planes_csa(
                    self.planes(instr.attr_a), self.planes(instr.attr_b),
                    instr.n_bits)
        elif kind == "ColumnTransform":
            self.masks[instr.dest] = self.masks[instr.mask]
        else:
            raise ValueError(f"non-bitwise instruction {kind} "
                             "must be handled by the caller")

    # -- carry-save arithmetic batching ------------------------------------
    def _arith_terms(self, instr: isa.PimInstruction):
        """Decompose one derived-arith instruction into its carry-save
        addend list: ``(terms, carry_in, out_bits)``. Immediates become
        constant plane stacks (XLA folds them); subtract contributes the
        inverted operand with the ``+1`` as the final pass's carry-in."""
        kind = instr.kind
        w = instr.n_bits
        if kind == "AddImm":
            return ([self.planes(instr.attr),
                     eng.imm_planes(instr.imm, w, self._shape)], 0, w)
        if kind == "Add":
            return ([self.planes(instr.attr_a), self.planes(instr.attr_b)],
                    0, w)
        if kind == "Subtract":
            nb = ~eng.extend_planes(self.planes(instr.attr_b), w)
            return ([self.planes(instr.attr_a), nb], 1, w)
        if kind == "Multiply":
            pa = self.planes(instr.attr_a)
            if instr.imm is not None:
                pps = eng.mul_partial_products(pa, None, instr.imm, w)
            else:
                pps = eng.mul_partial_products(pa, self.planes(instr.attr_b),
                                               None, w)
            return (pps, 0, w)
        raise ValueError(f"not a derived-arith instruction: {kind}")

    def execute_arith_batch(self, batch: Sequence[isa.PimInstruction]) -> None:
        """Evaluate independent derived-arith instructions together: each
        member's addends CSA-reduce to a (sum, carry) pair, then ONE
        batched ripple pass carry-propagates all members at once — N
        independent Multiply/Add chains cost one final pass, not N."""
        finals = []                      # (instr, sum, carry, carry_in)
        for ins in batch:
            terms, cin, w = self._arith_terms(ins)
            if not terms:
                self.derived[ins.dest] = jnp.zeros((w,) + self._shape, U32)
            elif len(terms) == 1 and not cin:
                self.derived[ins.dest] = eng.extend_planes(terms[0], w)
            else:
                s, c = eng.csa_reduce(terms, w)
                finals.append((ins, s, c, cin))
        if not finals:
            return
        if len(finals) == 1:
            ins, s, c, cin = finals[0]
            self.derived[ins.dest] = eng.add_planes(s, c, ins.n_bits,
                                                    carry_in=cin)
            return
        wmax = max(ins.n_bits for ins, _, _, _ in finals)
        s_st = jnp.stack([eng.extend_planes(s, wmax) for _, s, _, _ in finals])
        c_st = jnp.stack([eng.extend_planes(c, wmax) for _, _, c, _ in finals])
        # Scalar-broadcast planes (not a captured constant vector): the
        # Pallas kernel traces this too, where non-scalar consts are
        # disallowed.
        carry = jnp.stack([jnp.full(self._shape, _FULL, U32) if f[3]
                           else jnp.zeros(self._shape, U32) for f in finals])
        outs = []
        for b in range(wmax):
            a, d = s_st[:, b], c_st[:, b]
            outs.append(a ^ d ^ carry)
            carry = (a & d) | (carry & (a ^ d))
        res = jnp.stack(outs, axis=1)            # (batch, wmax, W)
        for m, (ins, _, _, _) in enumerate(finals):
            self.derived[ins.dest] = res[m, :ins.n_bits]


def _reduce_minmax_bits(planes: jnp.ndarray, mask: jnp.ndarray,
                        is_max: bool
                        ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Traceable MSB-first narrowing. Returns (the result bits as a list
    of int32 scalars, LSB-first; found:bool) — the host assembles the
    exact value, and maps found=False (empty selection) to None. A list,
    so the Pallas kernel can write the bits lane by lane."""
    n_bits = planes.shape[0]
    cand = mask
    bits: List[jnp.ndarray] = [None] * n_bits  # type: ignore[list-item]
    for b in range(n_bits - 1, -1, -1):
        if is_max:
            t = cand & planes[b]
            has = jnp.any(t != 0)
            bits[b] = has.astype(jnp.int32)
            cand = jnp.where(has, t, cand & ~planes[b])
        else:
            t = cand & ~planes[b]
            has = jnp.any(t != 0)
            bits[b] = jnp.logical_not(has).astype(jnp.int32)
            cand = jnp.where(has, t, cand & planes[b])
    return bits, jnp.any(mask != 0)


# --------------------------------------------------------------------------
# Reduce planning: grouped popcounts + in-kernel MIN/MAX jobs
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SumJob:
    """All ReduceSums over one source plane stack, coalesced.

    The popcount executes once, at instruction index ``exec_at`` (the last
    member's position), against the whole stack of ``masks`` — one read of
    the ``width`` aggregate planes per pass instead of one per member.
    Columns ``[col_start, col_start + width * len(masks))`` of the
    popcount accumulator hold the per-(bit, group) partials, bit-major:
    column ``col_start + b * len(masks) + g`` is (bit b, mask g).
    """
    attr: str
    masks: Tuple[str, ...]           # unique mask registers, stack order
    width: int                       # planes of the shared operand
    exec_at: int                     # instruction index the job runs at
    col_start: int

    @property
    def n_cols(self) -> int:
        return self.width * len(self.masks)


@dataclasses.dataclass(frozen=True)
class MinMaxJob:
    """One ReduceMinMax, lowered into the kernel at its own position.

    Per tile the kernel narrows MSB-first and emits ``width`` candidate
    bits plus a found flag at columns ``[col_start, col_start + width]``
    of the per-tile MIN/MAX output; a cross-tile combine (the shape of
    ``core.distributed.combine_minmax_candidates``) reduces them.
    """
    dest: str
    attr: str
    mask: str
    width: int
    is_max: bool
    exec_at: int
    col_start: int


@dataclasses.dataclass(frozen=True)
class ReducePlan:
    """Grouped reduce jobs + liveness extended across job deferral."""
    sum_jobs: Tuple[SumJob, ...]
    mm_jobs: Tuple[MinMaxJob, ...]
    dest_slot: Mapping[str, Tuple[int, int]]  # sum dest -> (job, mask idx)
    last_use: Mapping[str, int]               # analysis.last_use, extended
    n_pc_cols: int                            # popcount accumulator columns
    n_mm_cols: int                            # per-tile MIN/MAX columns
    plane_reads: int                          # agg plane reads/pass, grouped
    plane_reads_ungrouped: int                # one read per ReduceSum/MinMax

    def job_keys(self) -> Tuple[str, ...]:
        return tuple(f"j{j}" for j in range(len(self.sum_jobs)))


def plan_reduces(instrs: Sequence[isa.PimInstruction],
                 analysis: ProgramAnalysis,
                 widths: Mapping[str, int]) -> ReducePlan:
    """Coalesce ReduceSums sharing a source plane stack into grouped jobs.

    Grouping defers a member's popcount to the last member's position,
    which is only sound while registers are single-assignment (the
    Compiler always emits fresh destinations). If a destination name is
    ever reassigned, coalescing is disabled and every reduce becomes a
    singleton job at its own position. Identical (attr, mask) pairs (Q1's
    ``avg`` re-reducing the ``sum`` operand, per-group counts) share one
    accumulator column instead of recounting.
    """
    seen_dests: set = set()
    ssa = True
    for ins in instrs:
        if ins.dest in seen_dests:
            ssa = False
        seen_dests.add(ins.dest)

    def op_width(ins) -> int:
        if analysis.reg_kind.get(ins.attr) == "mask":
            return 1
        return analysis.widths.get(ins.attr, widths.get(ins.attr, ins.n_bits))

    members: Dict[str, List[Tuple[int, str, str]]] = {}
    order: List[str] = []
    job_width: Dict[str, int] = {}
    mm_jobs: List[MinMaxJob] = []
    ungrouped = 0
    mm_col = 0
    for i, ins in enumerate(instrs):
        if ins.kind == "ReduceSum":
            w = op_width(ins)
            ungrouped += w
            key = ins.attr if ssa else f"{ins.attr}@{i}"
            if key not in members:
                members[key] = []
                order.append(key)
                job_width[key] = w
            members[key].append((i, ins.dest, ins.mask))
        elif ins.kind == "ReduceMinMax":
            w = op_width(ins)
            ungrouped += w
            mm_jobs.append(MinMaxJob(ins.dest, ins.attr, ins.mask, w,
                                     ins.is_max, i, mm_col))
            mm_col += w + 1                   # bits + found flag
    sum_jobs: List[SumJob] = []
    dest_slot: Dict[str, Tuple[int, int]] = {}
    last_use: Dict[str, int] = dict(analysis.last_use)
    col = 0
    for j, key in enumerate(order):
        masks: List[str] = []
        for i, dest, mask in members[key]:
            if mask not in masks:
                masks.append(mask)
            dest_slot[dest] = (j, masks.index(mask))
        exec_at = max(i for i, _, _ in members[key])
        attr = instrs[members[key][0][0]].attr
        job = SumJob(attr, tuple(masks), job_width[key], exec_at, col)
        sum_jobs.append(job)
        col += job.n_cols
        for r in (attr, *masks):             # operands live until the job
            if r in analysis.reg_kind:       # registers only: extending a
                last_use[r] = max(last_use.get(r, -1), exec_at)
            # ...source attribute would schedule a phantom free of the
            # relation's own planes (free is a no-op on sources, but the
            # schedule must stay register-exact for the verifier).
    plane_reads = sum(s.width for s in sum_jobs) + sum(m.width
                                                       for m in mm_jobs)
    return ReducePlan(tuple(sum_jobs), tuple(mm_jobs), dest_slot, last_use,
                      col, mm_col, plane_reads, ungrouped)


# --------------------------------------------------------------------------
# Arithmetic planning: carry-save lowering + plane-group batching
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ArithPlan:
    """How the derived-arith instructions lower to carry-save trees.

    ``batches`` are runs of *consecutive, mutually independent* derived
    instructions (no member reads another member's dest): all members of a
    batch CSA-reduce their addends independently, then share ONE batched
    final carry-propagate pass at the first member's position. Depth
    counters measure serialized plane-op chains (a carry-propagate ripple
    step is depth 1 per bit; a 3:2 compressor level is depth 1 regardless
    of width) — the compile-latency proxy the bench trend records.
    ``steps`` counts the lowering-internal op kinds for
    ``cost_model.classify_lowering``; these are lowering facts only and
    never contribute to Table 4 ISA cycles.
    """
    batches: Tuple[Tuple[int, ...], ...]   # instruction-index runs, len >= 2
    depth_csa: int                         # serialized depth, CSA + batching
    depth_ripple: int                      # same program, ripple lowering
    steps: Tuple[Tuple[str, int], ...]     # internal kind -> count

    @property
    def batched_indices(self) -> FrozenSet[int]:
        return frozenset(i for b in self.batches for i in b)


def _arith_addend_count(ins: isa.PimInstruction,
                        op_width: Callable[[str], int]) -> int:
    """Number of carry-save addends an instruction contributes."""
    if ins.kind == "Multiply":
        w = ins.n_bits
        if ins.imm is not None:
            return sum(1 for b in range(w) if (ins.imm >> b) & 1)
        return min(op_width(ins.attr_b), w)
    return 2                                     # a + b / a + imm / a + ~b


def plan_arith(instrs: Sequence[isa.PimInstruction],
               analysis: ProgramAnalysis,
               widths: Mapping[str, int]) -> ArithPlan:
    """Plan the carry-save lowering of every derived-arith instruction.

    A batch executes at its *first* member's position; a later derived
    instruction may join an open batch when every operand it reads was
    produced before that position (source attributes always qualify), so
    deferred ReduceSums or mask logic between two independent Multiplys do
    not break the batch. Early execution is sound under single-assignment
    (like ``plan_reduces``' deferral — batching is disabled otherwise):
    a member's result simply becomes live earlier, and its consumers all
    sit at or after its original position.
    """
    producer: Dict[str, int] = {}
    ssa = True
    for i, ins in enumerate(instrs):
        if ins.dest in producer:
            ssa = False
        producer[ins.dest] = i

    def op_width(name: str) -> int:
        if analysis.reg_kind.get(name) == "mask":
            return 1
        return analysis.widths.get(name, widths.get(name, 1))

    # -- open-batch scan ----------------------------------------------------
    batches: List[Tuple[int, ...]] = []
    if ssa:
        open_start: Optional[int] = None
        members: List[int] = []
        for i, ins in enumerate(instrs):
            if ins.kind not in _DERIVED_KINDS:
                continue
            joins = open_start is not None and all(
                producer.get(r, -1) < open_start
                for r in instruction_reads(ins))
            if joins:
                members.append(i)
            else:
                if len(members) > 1:
                    batches.append(tuple(members))
                open_start, members = i, [i]
        if len(members) > 1:
            batches.append(tuple(members))

    # -- depth + internal-step accounting ----------------------------------
    in_batch = {i for b in batches for i in b}
    depth_csa = 0
    depth_ripple = 0
    csa_compressions = 0
    carry_propagate_bits = 0
    copy_throughs = 0

    def member_stats(ins: isa.PimInstruction) -> Tuple[int, int]:
        """(csa tree levels, addend count) of one instruction."""
        k = _arith_addend_count(ins, op_width)
        return eng.csa_tree_levels(k), k

    for i, ins in enumerate(instrs):
        if ins.kind not in _DERIVED_KINDS:
            continue
        levels, k = member_stats(ins)
        w = ins.n_bits
        # Ripple lowering of the same instruction (post copy-through fix):
        # one carry chain per extra addend; subtract's +1 rides carry-in.
        depth_ripple += max(0, k - 1) * w
        csa_compressions += max(0, k - 2)
        if k <= 1:
            copy_throughs += 1
            continue
        if i not in in_batch:
            depth_csa += levels + w
            carry_propagate_bits += w
    for b in batches:
        stats = [member_stats(instrs[i]) for i in b]
        live = [(lv, instrs[i].n_bits) for (lv, k), i in zip(stats, b)
                if k > 1]
        if live:
            depth_csa += max(lv for lv, _ in live) + max(w for _, w in live)
            carry_propagate_bits += max(w for _, w in live)
    steps = (("csa_compress", csa_compressions),
             ("carry_propagate", carry_propagate_bits),
             ("copy_through", copy_throughs))
    return ArithPlan(tuple(batches), depth_csa, depth_ripple, steps)


def frees_by_instr(n_instrs: int, last_use: Mapping[str, int],
                   keep: FrozenSet[str]) -> Tuple[Tuple[str, ...], ...]:
    """frees[i] = registers whose (plan-extended) last use is instruction
    ``i`` — dropped right after it executes, inside the kernel too."""
    frees: List[List[str]] = [[] for _ in range(n_instrs)]
    for r, i in last_use.items():
        if 0 <= i < n_instrs and r not in keep and r != "__valid__":
            frees[i].append(r)
    return tuple(tuple(sorted(f)) for f in frees)


# --------------------------------------------------------------------------
# Cross-query linking: many programs over one relation -> one SSA program
# --------------------------------------------------------------------------
# Operand field names per instruction kind (the register-valued fields a
# linker must rename); every other dataclass field is static and becomes
# part of the value-numbering key unchanged.
_OPERAND_FIELDS: Dict[str, Tuple[str, ...]] = {
    "EqualImm": ("attr",), "NotEqualImm": ("attr",),
    "LessThanImm": ("attr",), "GreaterThanImm": ("attr",),
    "AddImm": ("attr",),
    "Equal": ("attr_a", "attr_b"), "LessThan": ("attr_a", "attr_b"),
    "Add": ("attr_a", "attr_b"), "Subtract": ("attr_a", "attr_b"),
    "Multiply": ("attr_a", "attr_b"),
    "BitwiseAnd": ("src_a", "src_b"), "BitwiseOr": ("src_a", "src_b"),
    "BitwiseNot": ("src",),
    "SetReset": (),
    "ReduceSum": ("attr", "mask"), "ReduceMinMax": ("attr", "mask"),
    "Materialize": ("mask",),            # plus the attrs tuple, special-cased
    "ColumnTransform": ("mask",),
}
# Kinds whose operand order is semantically irrelevant — their value key
# sorts the operand pair so ``And(a, b)`` dedups against ``And(b, a)``.
# Multiply is NOT here: its value is symmetric but its Table-4 cycle
# count (24nm - 19n + 2m - 1) is not, so only exact-form matches dedup.
# LessThan/Subtract are order-sensitive in value and excluded too.
_COMMUTATIVE_KINDS = frozenset(
    {"BitwiseAnd", "BitwiseOr", "Equal", "Add"})


def _linked_key(ins: isa.PimInstruction, rename: Mapping[str, str]) -> tuple:
    """Value-numbering key of one instruction under a register renaming:
    (kind, linked operand names, static fields). Two instructions with
    equal keys compute the same value in the linked program."""
    def rn(v: str) -> str:
        return rename.get(v, v)

    kind = ins.kind
    op_fields = _OPERAND_FIELDS[kind]
    ops: tuple = tuple(rn(getattr(ins, f)) for f in op_fields)
    if kind == "Materialize":
        ops = (tuple(rn(a) for a in ins.attrs),) + ops
    elif kind in _COMMUTATIVE_KINDS:
        ops = tuple(sorted(ops))
    skip = set(op_fields) | {"dest", "attrs"}
    static = tuple((f.name, getattr(ins, f.name))
                   for f in dataclasses.fields(ins) if f.name not in skip)
    return (kind, ops, static)


def _relink_instr(ins: isa.PimInstruction, rename: Mapping[str, str],
                  dest: str) -> isa.PimInstruction:
    """Rebuild one instruction with renamed operands and a new dest."""
    def rn(v: str) -> str:
        return rename.get(v, v)

    kw: Dict[str, object] = {f: rn(getattr(ins, f))
                             for f in _OPERAND_FIELDS[ins.kind]}
    if ins.kind == "Materialize":
        kw["attrs"] = tuple(rn(a) for a in ins.attrs)
    return dataclasses.replace(ins, dest=dest, **kw)


@dataclasses.dataclass(frozen=True)
class QuerySlot:
    """Output wiring of ONE source program inside a linked program.

    ``reg_map`` maps every register the source program defined to the
    register that computes the same value in the linked program (shared
    subexpressions of several queries map to one linked register);
    ``mask_outputs`` are the source program's requested mask outputs,
    already translated. ``ProgramResult.query`` uses a slot to demux
    masks/scalars/materialized rows back to the originating query.
    """
    reg_map: Mapping[str, str]
    mask_outputs: Tuple[str, ...]

    def reg(self, name: str) -> str:
        return self.reg_map.get(name, name)


@dataclasses.dataclass(frozen=True)
class LinkedProgram:
    """Result of :func:`link_programs`: one SSA program + per-query slots."""
    instrs: Tuple[isa.PimInstruction, ...]
    mask_outputs: Tuple[str, ...]        # union of all slots', deduped
    slots: Tuple[QuerySlot, ...]
    n_instrs_unlinked: int               # sum of member program lengths
    n_deduped: int                       # instructions removed by CSE

    @property
    def cache_key(self) -> str:
        """Short stable digest of the linked instruction stream + outputs.

        Canonicalization + deterministic linking make a recurring batch
        produce byte-identical instruction tuples, so this key is equal
        for equal-meaning batches: the serving layer uses it to label
        dispatches, and it varies exactly when the executable-cache
        signature (:func:`program_signature`) would.
        """
        import hashlib
        return hashlib.sha256(
            repr((self.instrs, self.mask_outputs)).encode()).hexdigest()[:16]


def link_programs(programs: Sequence[Tuple[Sequence[isa.PimInstruction],
                                           Sequence[str]]],
                  relation: Optional[eng.PimRelation] = None
                  ) -> LinkedProgram:
    """Merge several compiled instruction streams over ONE relation into
    a single SSA program fit for one fused dispatch.

    ``programs`` is a sequence of ``(instrs, mask_outputs)`` pairs, one
    per query, in batch order. Instructions are value-numbered as they
    are appended: an instruction whose (kind, linked operands, static
    fields) key was already emitted — by this query or an earlier one —
    is dropped, and its dest aliases the existing register. Predicate
    canonicalization (``db.compiler.canonicalize``) makes structurally
    equal subtrees arrive here in identical instruction form, so the
    shared-subexpression dedup is exact, not heuristic. Colliding dest
    names (un-namespaced compilers both emitting ``t0``) are uniquified
    with a ``q<i>.`` prefix; pass ``relation`` so renames also avoid its
    attribute names. The output stays single-assignment, which keeps
    ``plan_reduces`` grouping and ``plan_arith`` batching enabled — one
    query's aggregates stack as extra groups in another's popcount jobs,
    and independent per-query arith chains join one CSA batch.
    """
    reserved = {"__valid__"}
    if relation is not None:
        reserved.update(relation.planes)
    value_table: Dict[tuple, str] = {}
    linked: List[isa.PimInstruction] = []
    used: set = set()
    slots: List[QuerySlot] = []
    total = deduped = 0
    for qi, (instrs, mouts) in enumerate(programs):
        rename: Dict[str, str] = {}
        for ins in instrs:
            total += 1
            key = _linked_key(ins, rename)
            hit = value_table.get(key)
            if hit is not None:
                rename[ins.dest] = hit
                deduped += 1
                continue
            dest = ins.dest
            if dest in used or dest in reserved:
                dest = f"q{qi}.{ins.dest}"
                while dest in used or dest in reserved:
                    dest = "_" + dest
            linked.append(_relink_instr(ins, rename, dest))
            used.add(dest)
            rename[ins.dest] = dest
            value_table[key] = dest
        slots.append(QuerySlot(reg_map=dict(rename),
                               mask_outputs=tuple(rename.get(m, m)
                                                  for m in mouts)))
    mask_outputs = tuple(dict.fromkeys(
        m for s in slots for m in s.mask_outputs))
    return LinkedProgram(tuple(linked), mask_outputs, tuple(slots),
                         total, deduped)


# --------------------------------------------------------------------------
# compile_program / run_program
# --------------------------------------------------------------------------
class LruFnCache:
    """Bounded LRU of jitted executables keyed by the full static program
    signature, so recompiling the same query against the same layout reuses
    the XLA build (PimDatabase constructs a fresh Compiler per run).

    Bounded because the key includes the full instruction tuple: a
    long-lived serving process answering ad-hoc queries would otherwise
    accumulate compiled executables without limit. Evicting an entry drops
    the jitted callable (and, transitively, XLA's hold on the executable);
    re-requesting an evicted signature simply recompiles.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("cache capacity must be >= 1")
        self._data: "collections.OrderedDict[tuple, Callable]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._data)

    def get(self, key: tuple) -> Optional[Callable]:
        with self._lock:
            fn = self._data.get(key)
            if fn is None:
                self.misses += 1
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return fn

    def put(self, key: tuple, fn: Callable) -> None:
        with self._lock:
            self._data[key] = fn
            self._data.move_to_end(key)
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)
                self.evictions += 1

    def set_capacity(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError("cache capacity must be >= 1")
        with self._lock:
            self.capacity = capacity
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._data.clear()


_FN_CACHE = LruFnCache(
    capacity=int(os.environ.get("REPRO_PROGRAM_CACHE_CAPACITY", "128")))


def set_program_cache_capacity(capacity: int) -> None:
    """Resize the compiled-executable LRU (evicts oldest entries now)."""
    _FN_CACHE.set_capacity(capacity)


def program_cache_stats() -> Dict[str, int]:
    """Hit/miss/eviction counters of the compiled-executable LRU — the
    serving layer surfaces these so a trace that should be recurring
    (identical canonical batches) is visibly hitting warm executables."""
    return {"hits": _FN_CACHE.hits, "misses": _FN_CACHE.misses,
            "evictions": _FN_CACHE.evictions, "size": len(_FN_CACHE),
            "capacity": _FN_CACHE.capacity}


def program_signature(instrs: Tuple[isa.PimInstruction, ...],
                      mask_outputs: Tuple[str, ...], backend: str,
                      interpret: bool, relation: eng.PimRelation,
                      widths: Mapping[str, int],
                      mesh: Optional[Mesh] = None,
                      shard_axes: Optional[Tuple[str, ...]] = None) -> tuple:
    """The full static signature a compiled executable is cached under.

    Everything that can change the traced computation is in here —
    instruction stream, requested outputs, backend/interpret mode, the
    relation's layout (name + padded word count + source widths), and the
    mesh/sharding — and nothing else: demux metadata (``query_slots``)
    and the relation's *content* (including its ``version``) are excluded
    on purpose, so recompiling a recurring batch against refreshed data
    still reuses the warm executable.
    """
    return (instrs, mask_outputs, backend, interpret, relation.name,
            relation.layout.n_words, tuple(sorted(widths.items())),
            mesh, shard_axes)


@dataclasses.dataclass
class CompiledProgram:
    """A relation program lowered to one jit-compiled dispatch.

    With ``mesh`` set the dispatch is the shard_map-wrapped SPMD
    executable: planes sharded along the word axis, per-shard popcount
    partials psum-combined, MIN/MAX candidates gathered + combined —
    still ONE logical dispatch per relation program.
    """
    instrs: Tuple[isa.PimInstruction, ...]
    mask_outputs: Tuple[str, ...]
    scalar_kinds: Dict[str, tuple]         # dest -> ("sum",)|("minmax",)
    analysis: ProgramAnalysis
    plan: ReducePlan
    arith: ArithPlan
    backend: str
    n_words: int
    _fn: Callable                          # (planes dict, valid) -> raw out
    mesh: Optional[Mesh] = None
    shard_axes: Optional[Tuple[str, ...]] = None
    # Materialize dest -> the attribute tuple it decodes (readout order).
    mat_attrs: Mapping[str, Tuple[str, ...]] = \
        dataclasses.field(default_factory=dict)
    # Per-query output wiring when this is a linked multi-query program
    # (empty for a plain single-query compile).
    query_slots: Tuple[QuerySlot, ...] = ()
    # Source attribute -> bit-planes it contributes to the streamed stack.
    source_plane_counts: Mapping[str, int] = \
        dataclasses.field(default_factory=dict)
    # The executable-cache signature (see :func:`program_signature`).
    signature: Optional[tuple] = None
    # Whether the Pallas kernels run in interpret mode (off the TPU).
    interpret: bool = False

    @property
    def n_dispatches(self) -> int:
        """Device dispatches per execution — the fusion headline."""
        return 1

    @property
    def n_queries(self) -> int:
        return max(1, len(self.query_slots))

    @property
    def agg_plane_reads(self) -> int:
        """Aggregate-plane tile reads per pass under the grouped plan."""
        return self.plan.plane_reads

    @property
    def source_plane_reads(self) -> int:
        """Source bit-planes streamed per dispatch — each touched attribute
        plane is read once no matter how many linked queries consume it
        (the cross-query amortization headline)."""
        return sum(self.source_plane_counts.values())

    @property
    def total_plane_reads(self) -> int:
        """Source planes streamed + aggregate-plane re-reads per dispatch."""
        return self.source_plane_reads + self.plan.plane_reads

    @property
    def agg_plane_reads_ungrouped(self) -> int:
        """Same count with one read per ReduceSum/MinMax (the pre-grouping
        execution) — the grouped-aggregation headline is the ratio."""
        return self.plan.plane_reads_ungrouped

    @property
    def n_reduce_jobs(self) -> int:
        return len(self.plan.sum_jobs) + len(self.plan.mm_jobs)

    @property
    def arith_depth_csa(self) -> int:
        """Serialized derived-plane op depth under the carry-save lowering
        (3:2 tree levels + one shared carry-propagate per arith batch)."""
        return self.arith.depth_csa

    @property
    def arith_depth_ripple(self) -> int:
        """Same program's depth under the ripple-carry lowering (one full
        carry chain per extra addend) — the pre-CSA execution."""
        return self.arith.depth_ripple

    @property
    def n_arith_batches(self) -> int:
        return len(self.arith.batches)

    @property
    def n_shards(self) -> int:
        if self.mesh is None:
            return 1
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        out = 1
        for a in (self.shard_axes or ()):
            out *= sizes[a]
        return out

    @property
    def peak_live_planes(self) -> int:
        return self.analysis.peak_live_planes

    @property
    def total_reg_planes(self) -> int:
        return self.analysis.total_reg_planes

    def paper_cycles(self) -> int:
        return sum(i.cycles() for i in self.instrs)


class ProgramResult:
    """Outputs of one fused dispatch; exact host-side finalisation."""

    def __init__(self, cp: CompiledProgram, raw: Dict[str, dict],
                 n_records: int):
        self._cp = cp
        self._raw = raw
        self._n = n_records

    def mask_packed(self, name: str) -> np.ndarray:
        return np.asarray(self._raw["masks"][name])

    def mask(self, name: str, n_records: Optional[int] = None) -> np.ndarray:
        n = self._n if n_records is None else n_records
        return bitslice.unpack_mask(self.mask_packed(name), n)

    def scalar(self, name: str) -> Optional[int]:
        kind = self._cp.scalar_kinds[name][0]
        if kind == "sum":
            j, k = self._cp.plan.dest_slot[name]
            pcs = np.asarray(self._raw["job_pc"][f"j{j}"])[k]
            return sum(int(pcs[b]) << b for b in range(pcs.shape[0]))
        if kind == "minmax":
            if not bool(np.asarray(self._raw["mm_found"][name])):
                return None
            bits = np.asarray(self._raw["mm_bits"][name])
            return sum(int(bits[b]) << b for b in range(bits.shape[0]))
        raise KeyError(name)

    def materialized_count(self, name: str) -> int:
        """Selected-record count of one Materialize output (all shards)."""
        return int(np.asarray(self._raw["mat_cnt"][name]).sum())

    def materialized(self, name: str) -> Dict[str, np.ndarray]:
        """Decoded column values of one Materialize output.

        Returns ``{attr: (count,) int array}`` in record order. The value
        buffer is the one output ``run_program`` leaves on device: only
        the ``count``-row prefixes are copied to the host, so readback
        traffic is O(selected records), not O(relation) — the
        readout-reduction the subsystem exists for. Under a mesh the
        buffer is word-axis-sharded (shard s owns columns ``[s*cap,
        (s+1)*cap)`` with its own count); each device's piece is read
        on its own, which works for any mesh axis type, and the
        per-shard prefixes are stitched here — the mask never leaves the
        devices unsharded.
        """
        vals = self._raw["mat_vals"][name]       # device-resident
        cnts = np.asarray(self._raw["mat_cnt"][name]).ravel()
        cap = vals.shape[1] // cnts.shape[0]
        prefixes: Dict[int, np.ndarray] = {}
        for piece in vals.addressable_shards:    # one shard's columns
            s = (piece.index[1].start or 0) // cap
            if s not in prefixes:                # replicas hold copies
                prefixes[s] = _read_prefix(piece.data, int(cnts[s]))
        dense = np.concatenate([prefixes[s] for s in range(cnts.shape[0])],
                               axis=1)
        attrs = self._cp.mat_attrs[name]
        return {a: dense[i] for i, a in enumerate(attrs)}

    def query(self, q: int) -> "QueryView":
        """Demux view for source query ``q`` of a linked program: the
        same mask/scalar/materialized accessors, addressed by the
        query's OWN register names (translated through its slot)."""
        return QueryView(self, self._cp.query_slots[q])


def _read_prefix(buf: jax.Array, count: int) -> np.ndarray:
    """Host copy of the first ``count`` columns of a single-device
    buffer. The device slice is rounded up to a power of two (within the
    buffer) so distinct counts share a few slice executables; the
    readback stays under twice the selected rows."""
    if count == 0:
        return np.zeros((buf.shape[0], 0), np.int32)
    width = min(buf.shape[1], 1 << (count - 1).bit_length())
    return np.asarray(buf[:, :width])[:, :count]


class QueryView:
    """Per-query window onto a linked-program :class:`ProgramResult`."""

    def __init__(self, res: ProgramResult, slot: QuerySlot):
        self._res = res
        self._slot = slot

    @property
    def mask_outputs(self) -> Tuple[str, ...]:
        return self._slot.mask_outputs

    def reg(self, name: str) -> str:
        return self._slot.reg(name)

    def mask_packed(self, name: str) -> np.ndarray:
        return self._res.mask_packed(self.reg(name))

    def mask(self, name: str, n_records: Optional[int] = None) -> np.ndarray:
        return self._res.mask(self.reg(name), n_records)

    def scalar(self, name: str) -> Optional[int]:
        return self._res.scalar(self.reg(name))

    def materialized_count(self, name: str) -> int:
        return self._res.materialized_count(self.reg(name))

    def materialized(self, name: str) -> Dict[str, np.ndarray]:
        return self._res.materialized(self.reg(name))


def compile_program(relation: eng.PimRelation,
                    program: Sequence[isa.PimInstruction],
                    mask_outputs: Sequence[str] = (),
                    backend: str = "jnp",
                    interpret: Optional[bool] = None,
                    mesh: Optional[Mesh] = None,
                    shard_axes: Optional[Sequence[str]] = None,
                    query_slots: Sequence[QuerySlot] = ()
                    ) -> CompiledProgram:
    """Lower a whole relation program into a single jit-compiled function.

    ``mask_outputs`` names the mask registers the host will read; every
    reduce destination automatically becomes a scalar output. Liveness
    analysis drops dead registers during tracing so XLA sees the true
    (smaller) live-plane working set.

    ``query_slots`` (from ``link_programs``) is demux metadata for linked
    multi-query programs; it does not affect the executable, so it is not
    part of the cache signature — recurring batches hit the ``LruFnCache``
    on the canonical linked instruction stream alone.

    With ``mesh`` the compiled function is wrapped in ``shard_map`` over
    ``shard_axes`` (default: every mesh axis): bit-planes shard along the
    word axis, result masks stay sharded, popcount partials combine via
    psum and MIN/MAX via a cross-shard candidate combine — see
    ``core.distributed.shard_program_fn``. Execution stays one logical
    dispatch per relation program.
    """
    instrs = tuple(program)
    mask_outputs = tuple(mask_outputs)
    if interpret is None:
        from repro.kernels.common import interpret_off_tpu
        interpret = interpret_off_tpu()

    scalar_kinds: Dict[str, tuple] = {}
    mat_attrs: Dict[str, Tuple[str, ...]] = {}
    mat_masks: List[str] = []
    for ins in instrs:
        if ins.kind == "ReduceSum":
            scalar_kinds[ins.dest] = ("sum",)
        elif ins.kind == "ReduceMinMax":
            scalar_kinds[ins.dest] = ("minmax", ins.is_max)
        elif ins.kind == "Materialize":
            mat_attrs[ins.dest] = tuple(ins.attrs)
            if ins.mask not in mat_masks:
                mat_masks.append(ins.mask)
    # Materialize masks are read out of the filter kernel (the pallas
    # lowering feeds them to the materialize kernel), so pin them live.
    keep = mask_outputs + tuple(m for m in mat_masks if m not in mask_outputs)
    analysis = analyze_program(instrs, relation, keep=keep)
    widths = {a: relation.width_of(a) for a in analysis.source_attrs}
    plan = plan_reduces(instrs, analysis, widths)
    arith = plan_arith(instrs, analysis, widths)

    if mesh is not None:
        from . import distributed as dist  # lazy: avoids import cycle
        shard_axes = dist.mesh_shard_axes(mesh, shard_axes)

    sig = program_signature(instrs, mask_outputs, backend, interpret,
                            relation, widths, mesh, shard_axes)
    fn = _FN_CACHE.get(sig)
    if fn is None:
        # Static verification rides the cache miss: every program is
        # checked once, before the (much more expensive) XLA build, and
        # warm-path compiles re-dispatch the cached fn with zero added
        # work. Raises ProgramVerificationError on any error finding.
        from repro.analysis import passes as _vp  # lazy: analysis imports us
        _vp.verify_compile(instrs, relation, analysis, plan, arith,
                           frozenset(keep), backend)
        if backend == "pallas":
            fn = _build_pallas_fn(instrs, mask_outputs, analysis, widths,
                                  interpret, plan, arith)
        else:
            fn = _build_jnp_fn(instrs, mask_outputs, analysis, plan, arith)
        if mesh is not None:
            fn = dist.shard_program_fn(
                fn, mesh, shard_axes,
                source_attrs=analysis.source_attrs,
                mask_outputs=mask_outputs,
                pc_job_keys=plan.job_keys(),
                mm_items=tuple((d, k[1]) for d, k in scalar_kinds.items()
                               if k[0] == "minmax"),
                mat_items=tuple(mat_attrs))
        fn = jax.jit(fn)
        _FN_CACHE.put(sig, fn)

    return CompiledProgram(instrs, mask_outputs, scalar_kinds, analysis,
                           plan, arith, backend, relation.layout.n_words, fn,
                           mesh=mesh, shard_axes=shard_axes,
                           mat_attrs=mat_attrs,
                           query_slots=tuple(query_slots),
                           source_plane_counts=dict(widths),
                           signature=sig, interpret=interpret)


def run_program(cp: CompiledProgram, relation: eng.PimRelation) -> ProgramResult:
    """Execute a compiled program: ONE device dispatch for the whole
    relation program, then exact host-side weighting of the popcounts.

    Materialize value buffers stay on device — their capacity is the
    padded record count, and ``ProgramResult.materialized`` copies out
    only each shard's ``count``-row prefix."""
    planes = {a: relation.planes[a] for a in cp.analysis.source_attrs}
    raw = dict(cp._fn(planes, relation.valid))
    mat_vals = raw.pop("mat_vals")
    host = jax.device_get(raw)
    host["mat_vals"] = mat_vals
    return ProgramResult(cp, host, relation.n_records)


# --------------------------------------------------------------------------
# Backend lowerings
# --------------------------------------------------------------------------
def _build_jnp_fn(instrs, mask_outputs, analysis: ProgramAnalysis,
                  plan: ReducePlan, arith: ArithPlan):
    from repro.kernels import materialize as kmat  # jnp lowering lives there

    keep = frozenset(mask_outputs)
    frees = frees_by_instr(len(instrs), plan.last_use, keep)
    jobs_at: Dict[int, List[Tuple[int, SumJob]]] = {}
    for j, job in enumerate(plan.sum_jobs):
        jobs_at.setdefault(job.exec_at, []).append((j, job))
    batch_at = {b[0]: b for b in arith.batches}
    batched = arith.batched_indices

    def _run(planes: Dict[str, jnp.ndarray], valid: jnp.ndarray):
        ev = BitwiseEvaluator(lambda a: planes[a], valid)
        job_pc: Dict[str, jnp.ndarray] = {}
        mm_bits: Dict[str, jnp.ndarray] = {}
        mm_found: Dict[str, jnp.ndarray] = {}
        mat_vals: Dict[str, jnp.ndarray] = {}
        mat_cnt: Dict[str, jnp.ndarray] = {}
        for i, ins in enumerate(instrs):
            if ins.kind == "ReduceSum":
                pass                   # runs at its grouped job's exec_at
            elif ins.kind == "ReduceMinMax":
                bits, found = _reduce_minmax_bits(
                    ev.planes(ins.attr), ev.masks[ins.mask], ins.is_max)
                mm_bits[ins.dest] = jnp.stack(bits)
                mm_found[ins.dest] = found
            elif ins.kind == "Materialize":
                mat_vals[ins.dest], mat_cnt[ins.dest] = \
                    kmat.materialize_planes(
                        [ev.planes(a) for a in ins.attrs],
                        ev.masks[ins.mask])
            elif i in batch_at:
                ev.execute_arith_batch([instrs[j] for j in batch_at[i]])
            elif i in batched:
                pass                   # ran with its batch at batch_at
            else:
                ev.execute(ins)
            for j, job in jobs_at.get(i, ()):
                p = ev.planes(job.attr)[:job.width]
                mstack = jnp.stack([ev.masks[m] for m in job.masks])
                job_pc[f"j{j}"] = eng.reduce_sum_bits_grouped(p, mstack)
            for r in frees[i]:
                ev.free(r)
        return {"masks": {m: ev.masks[m] for m in mask_outputs},
                "job_pc": job_pc, "mm_bits": mm_bits, "mm_found": mm_found,
                "mat_vals": mat_vals, "mat_cnt": mat_cnt}

    return _run


def _build_pallas_fn(instrs, mask_outputs, analysis: ProgramAnalysis,
                     widths: Dict[str, int], interpret: bool,
                     plan: ReducePlan, arith: ArithPlan):
    from repro.kernels import materialize as kmat
    from repro.kernels import program as kprog  # lazy: optional path
    from .distributed import combine_minmax_candidates

    mask_outputs_t = tuple(mask_outputs)
    mat_instrs = tuple(i for i in instrs if i.kind == "Materialize")
    # The materialize kernel consumes filter masks, so the program kernel
    # must emit them even when the caller asked for no mask readout.
    kernel_masks = mask_outputs_t + tuple(dict.fromkeys(
        m.mask for m in mat_instrs
        if m.mask not in mask_outputs_t and m.mask != "__valid__"))
    frees = frees_by_instr(len(instrs), plan.last_use,
                           frozenset(kernel_masks))

    # Only attrs the filter/aggregate program actually reads ride the
    # program kernel's tile stream; Materialize-only attrs would be
    # staged through it untouched (their one pass is materialize_pallas).
    kernel_reads = {r for ins in instrs if ins.kind != "Materialize"
                    for r in instruction_reads(ins)}
    kernel_attrs = tuple(a for a in analysis.source_attrs
                         if a in kernel_reads)

    def _run(planes: Dict[str, jnp.ndarray], valid: jnp.ndarray):
        attr_rows: Dict[str, Tuple[int, int]] = {}
        rows = []
        r0 = 0
        for a in kernel_attrs:
            p = planes[a]
            attr_rows[a] = (r0, r0 + p.shape[0])
            rows.append(p)
            r0 += p.shape[0]
        rows.append(valid[None])
        stacked = jnp.concatenate(rows, axis=0)
        masks_arr, pc_tot, mm_tiles = kprog.fused_program(
            stacked, instrs=instrs, attr_rows=attr_rows, valid_row=r0,
            mask_outputs=kernel_masks, sum_jobs=plan.sum_jobs,
            mm_jobs=plan.mm_jobs, frees=frees,
            arith_batches=arith.batches,
            n_pc_cols=plan.n_pc_cols, n_mm_cols=plan.n_mm_cols,
            interpret=interpret)

        # Second kernel launch, same jit dispatch: stream the materialized
        # attributes' planes once more, compacting against the filter mask.
        mat_vals: Dict[str, jnp.ndarray] = {}
        mat_cnt: Dict[str, jnp.ndarray] = {}
        for mi in mat_instrs:
            mask = (valid if mi.mask == "__valid__"
                    else masks_arr[kernel_masks.index(mi.mask)])
            mat_vals[mi.dest], mat_cnt[mi.dest] = kmat.materialize_pallas(
                [planes[a] for a in mi.attrs], mask, interpret=interpret)

        # Per-(bit, group) accumulator columns -> (n_groups, width) per job.
        job_pc = {f"j{j}": pc_tot[0, job.col_start:job.col_start + job.n_cols]
                  .reshape(job.width, len(job.masks)).T
                  for j, job in enumerate(plan.sum_jobs)}

        # Cross-tile MIN/MAX combine of the kernel's per-tile candidates —
        # the same MSB-first narrowing the distributed path runs per shard.
        mm_bits: Dict[str, jnp.ndarray] = {}
        mm_found: Dict[str, jnp.ndarray] = {}
        for mj in plan.mm_jobs:
            bits_t = mm_tiles[:, mj.col_start:mj.col_start + mj.width]
            found_t = mm_tiles[:, mj.col_start + mj.width] != 0
            bits, found = combine_minmax_candidates(bits_t, found_t,
                                                    mj.is_max)
            mm_bits[mj.dest] = bits
            mm_found[mj.dest] = found

        out_masks = {m: masks_arr[kernel_masks.index(m)]
                     for m in mask_outputs_t}
        return {"masks": out_masks, "job_pc": job_pc,
                "mm_bits": mm_bits, "mm_found": mm_found,
                "mat_vals": mat_vals, "mat_cnt": mat_cnt}

    return _run
