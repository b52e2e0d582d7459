"""Bulk-bitwise execution engine (the PIM-module analogue).

A :class:`PimRelation` holds a relation bit-sliced into uint32 planes
(`bitslice.py`). The engine executes `isa.py` instructions the way a PIM
controller would — bit-serially over planes, with immediates specialising
the op sequence at trace time (paper Algorithm 1) — but each "crossbar
row op" is a full-width bulk bitwise op over packed uint32 lanes.

Two execution paths produce identical results:

* ``backend="jnp"``  — pure jnp ops (always available, oracle for tests).
* ``backend="pallas"`` — Pallas kernels from ``repro.kernels`` for the
  hot loops (bit-serial predicate, fused filter+aggregate).

Arithmetic comes in two semantically identical lowerings: the ripple-carry
shift-add forms (``add_planes``/``mul_planes``/... — what this eager
engine executes, and the oracle the fused paths are tested against) and
the carry-save forms (``csa_compress3``/``csa_reduce``/``*_csa`` — a
log-depth 3:2 compressor tree over ALL addends followed by ONE final
carry-propagate pass), which the fused program executor uses to keep the
unrolled XLA/Mosaic graphs shallow.

Every executed instruction is appended to ``self.trace`` so the cost model
can charge paper-faithful cycles/energy/endurance afterwards.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from . import bitslice, isa

U32 = jnp.uint32
_FULL = np.uint32(0xFFFFFFFF)


# --------------------------------------------------------------------------
# Word-level primitives
# --------------------------------------------------------------------------
def popcount_u32(v: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount per uint32 word (sum returned as int64-safe uint32)."""
    v = v.astype(U32)
    v = v - ((v >> 1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    return (v * np.uint32(0x01010101)) >> 24


def popcount_total(v: jnp.ndarray) -> jnp.ndarray:
    """Total set bits as int32 — exact while the shard holds < 2^31 records
    (the per-shard layout guarantees far less); cross-shard/global exact
    combining happens in Python ints or via per-bit partials."""
    return jnp.sum(popcount_u32(v).astype(jnp.int32))


# --------------------------------------------------------------------------
# Bit-serial comparators over planes (MSB-first; one uint32 word = 32 rows)
# --------------------------------------------------------------------------
def eq_imm_planes(planes: jnp.ndarray, imm: int) -> jnp.ndarray:
    """planes: (n_bits, W) uint32 -> (W,) uint32 mask of records == imm.

    Immediate bits steer the op (AND v_b vs AND ~v_b) — Algorithm 1.
    """
    n_bits = planes.shape[0]
    acc = jnp.full(planes.shape[1:], _FULL, U32)
    for b in range(n_bits):
        if (imm >> b) & 1:
            acc = acc & planes[b]
        else:
            acc = acc & ~planes[b]
    return acc


def cmp_imm_planes(planes: jnp.ndarray, imm: int):
    """Returns (lt, eq) packed masks for records vs an immediate."""
    n_bits = planes.shape[0]
    lt = jnp.zeros(planes.shape[1:], U32)
    eq = jnp.full(planes.shape[1:], _FULL, U32)
    for b in range(n_bits - 1, -1, -1):   # MSB-first
        v = planes[b]
        if (imm >> b) & 1:
            lt = lt | (eq & ~v)
            eq = eq & v
        else:
            eq = eq & ~v
    return lt, eq


def cmp_planes(pa: jnp.ndarray, pb: jnp.ndarray):
    """(lt, eq) masks for attribute-vs-attribute comparison (a ? b)."""
    n = max(pa.shape[0], pb.shape[0])
    w = pa.shape[1:]
    zero = jnp.zeros(w, U32)
    lt = jnp.zeros(w, U32)
    eq = jnp.full(w, _FULL, U32)
    for b in range(n - 1, -1, -1):
        a = pa[b] if b < pa.shape[0] else zero
        c = pb[b] if b < pb.shape[0] else zero
        lt = lt | (eq & ~a & c)
        eq = eq & ~(a ^ c)
    return lt, eq


def add_planes(pa: jnp.ndarray, pb: jnp.ndarray, out_bits: int,
               carry_in: int = 0) -> jnp.ndarray:
    """Ripple-carry bit-serial addition over planes -> (out_bits, W).

    ``carry_in`` seeds the carry chain (0 or 1): two's-complement subtract
    folds its ``+1`` here instead of paying a second ripple pass.
    """
    w = pa.shape[1:]
    zero = jnp.zeros(w, U32)
    carry = jnp.full(w, _FULL, U32) if carry_in else zero
    outs = []
    for b in range(out_bits):
        a = pa[b] if b < pa.shape[0] else zero
        c = pb[b] if b < pb.shape[0] else zero
        s = a ^ c ^ carry
        carry = (a & c) | (carry & (a ^ c))
        outs.append(s)
    return jnp.stack(outs)


def add_imm_planes(pa: jnp.ndarray, imm: int, out_bits: int) -> jnp.ndarray:
    """Immediate-specialised adder (carry chain simplifies per imm bit)."""
    w = pa.shape[1:]
    zero = jnp.zeros(w, U32)
    carry = zero
    outs = []
    for b in range(out_bits):
        a = pa[b] if b < pa.shape[0] else zero
        if (imm >> b) & 1:
            s = ~(a ^ carry)
            carry = a | carry
        else:
            s = a ^ carry
            carry = a & carry
        outs.append(s)
    return jnp.stack(outs)


def extend_planes(p: jnp.ndarray, out_bits: int) -> jnp.ndarray:
    """Zero-extend (or truncate) a plane stack to exactly ``out_bits``."""
    if p.shape[0] == out_bits:
        return p
    if p.shape[0] > out_bits:
        return p[:out_bits]
    pad = jnp.zeros((out_bits - p.shape[0],) + tuple(p.shape[1:]), U32)
    return jnp.concatenate([p, pad], axis=0)


def shift_planes(pa: jnp.ndarray, b: int, out_bits: int) -> jnp.ndarray:
    """(pa << b) truncated to ``out_bits`` planes (a multiply partial
    product before gating)."""
    w = tuple(pa.shape[1:])
    n_zero = min(b, out_bits)
    n_keep = max(0, min(pa.shape[0], out_bits - b))
    # Never build a zero-sized stack: Mosaic has no zero-sized vector.
    parts = ([jnp.zeros((n_zero,) + w, U32)] if n_zero else []) + \
        ([pa[:n_keep]] if n_keep else [])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def imm_planes(imm: int, n_bits: int, shape) -> jnp.ndarray:
    """An immediate as a constant plane stack (all-ones / all-zeros per
    bit). Only used inside batched CSA reductions — XLA folds the
    constants, so the immediate still never occupies real planes."""
    rows = [jnp.full(shape, _FULL, U32) if (imm >> b) & 1
            else jnp.zeros(shape, U32) for b in range(n_bits)]
    return jnp.stack(rows)


def mul_partial_products(pa: jnp.ndarray, pb: Optional[jnp.ndarray],
                         imm: Optional[int], out_bits: int
                         ) -> List[jnp.ndarray]:
    """The shift-add partial products of a multiply, ungated-by-accumulate:
    immediate multiplies contribute one shifted copy of ``pa`` per set imm
    bit; attribute multiplies gate ``pa << b`` with plane ``pb[b]``."""
    pps: List[jnp.ndarray] = []
    if imm is not None:
        b = 0
        while (imm >> b) and b < out_bits:
            if (imm >> b) & 1:
                pps.append(shift_planes(pa, b, out_bits))
            b += 1
    else:
        for b in range(min(pb.shape[0], out_bits)):
            pps.append(shift_planes(pa, b, out_bits) & pb[b][None])
    return pps


# --------------------------------------------------------------------------
# Carry-save (3:2 compressor) arithmetic — Wallace-style reduction
# --------------------------------------------------------------------------
def csa_compress3(a: jnp.ndarray, b: jnp.ndarray,
                  c: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One 3:2 compressor level over equal-shape plane stacks.

    Returns ``(sum, carry)`` with the carry stack already shifted up one
    bit plane (the carry out of bit b feeds bit b+1; the top carry drops,
    i.e. arithmetic is mod 2^n like every planes op here). Constant depth
    regardless of width — this is what makes the multiply tree shallow.
    """
    s = a ^ b ^ c
    maj = (a & b) | (c & (a ^ b))
    carry = jnp.concatenate([jnp.zeros_like(maj[:1]), maj[:-1]], axis=0)
    return s, carry


def csa_tree_levels(k: int) -> int:
    """3:2 compressor levels needed to reduce ``k`` addends to 2.

    Mirrors ``csa_reduce``'s loop exactly (full triples compress 3 -> 2,
    the 0-2 leftover terms pass through) so the tested depth counter
    tracks the real lowering; change the two together.
    """
    levels = 0
    while k > 2:
        k = 2 * (k // 3) + k % 3
        levels += 1
    return levels


def csa_reduce(terms: Sequence[jnp.ndarray], out_bits: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reduce any number of addend plane stacks to a (sum, carry) pair via
    a log-depth 3:2 compressor tree. The caller finishes with ONE
    carry-propagate pass (``add_planes``), however many addends went in —
    vs one ripple pass *per addend* in the shift-add formulation."""
    work = [extend_planes(t, out_bits) for t in terms]
    if not work:
        raise ValueError("csa_reduce needs at least one term")
    while len(work) > 2:
        nxt: List[jnp.ndarray] = []
        tail = len(work) % 3
        for i in range(0, len(work) - tail, 3):
            s, c = csa_compress3(work[i], work[i + 1], work[i + 2])
            nxt.append(s)
            nxt.append(c)
        nxt.extend(work[len(work) - tail:])
        work = nxt
    if len(work) == 1:
        work.append(jnp.zeros_like(work[0]))
    return work[0], work[1]


def add_planes_csa(terms: Sequence[jnp.ndarray], out_bits: int,
                   carry_in: int = 0) -> jnp.ndarray:
    """Sum any number of plane stacks: CSA tree + one final ripple pass."""
    if not terms:
        raise ValueError("add_planes_csa needs at least one term")
    if len(terms) == 1 and not carry_in:
        return extend_planes(terms[0], out_bits)
    s, c = csa_reduce(terms, out_bits)
    return add_planes(s, c, out_bits, carry_in=carry_in)


def _ripple_accumulate(pps: Sequence[jnp.ndarray], out_bits: int,
                       shape) -> jnp.ndarray:
    """Shift-add accumulation: one full ripple pass per extra partial
    product. The first seeds the accumulator directly (copy-through)
    instead of paying an adder pass against zeros."""
    acc: Optional[jnp.ndarray] = None
    for pp in pps:
        acc = (extend_planes(pp, out_bits) if acc is None
               else add_planes(acc, pp, out_bits))
    if acc is None:
        return jnp.zeros((out_bits,) + tuple(shape), U32)
    return acc


def mul_imm_planes(pa: jnp.ndarray, imm: int, out_bits: int) -> jnp.ndarray:
    """Shift-add multiply by an immediate (only set bits cost adds).

    Ripple-carry oracle over the SAME ``mul_partial_products`` enumeration
    the CSA path reduces — only the accumulation strategy differs, so
    oracle-vs-CSA parity tests compare exactly that.
    """
    return _ripple_accumulate(mul_partial_products(pa, None, imm, out_bits),
                              out_bits, pa.shape[1:])


def mul_planes(pa: jnp.ndarray, pb: jnp.ndarray, out_bits: int) -> jnp.ndarray:
    """Bit-serial shift-add multiply: partial product b = (pa << b) AND
    pb[b]. Ripple-carry oracle; see ``mul_imm_planes``."""
    return _ripple_accumulate(mul_partial_products(pa, pb, None, out_bits),
                              out_bits, pa.shape[1:])


def mul_imm_planes_csa(pa: jnp.ndarray, imm: int, out_bits: int) -> jnp.ndarray:
    """Immediate multiply, carry-save: ALL partial products reduced in a
    log-depth 3:2 tree, then one carry-propagate pass (vs one ripple pass
    per set immediate bit in the oracle)."""
    pps = mul_partial_products(pa, None, imm, out_bits)
    if not pps:
        return jnp.zeros((out_bits,) + tuple(pa.shape[1:]), U32)
    return add_planes_csa(pps, out_bits)


def mul_planes_csa(pa: jnp.ndarray, pb: jnp.ndarray,
                   out_bits: int) -> jnp.ndarray:
    """Attribute multiply, carry-save (see ``mul_imm_planes_csa``)."""
    pps = mul_partial_products(pa, pb, None, out_bits)
    if not pps:
        return jnp.zeros((out_bits,) + tuple(pa.shape[1:]), U32)
    return add_planes_csa(pps, out_bits)


def sub_planes(pa: jnp.ndarray, pb: jnp.ndarray, out_bits: int) -> jnp.ndarray:
    """a - b (two's complement), assuming a >= b for unsigned semantics.

    The ``+1`` of the complement rides the adder's carry-in — one ripple
    pass total, not an add followed by a full increment pass.
    """
    w = pa.shape[1:]
    zero = jnp.zeros(w, U32)
    nb = jnp.stack([~(pb[b] if b < pb.shape[0] else zero) for b in range(out_bits)])
    return add_planes(pa, nb, out_bits, carry_in=1)


# --------------------------------------------------------------------------
# Aggregations (paper Fig. 7 reduce; masked per §4.2)
# --------------------------------------------------------------------------
def reduce_count(mask: jnp.ndarray) -> jnp.ndarray:
    return popcount_total(mask)


def reduce_sum_bits(planes: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Per-bit masked popcounts (int32, in-graph safe): pc[b] =
    popcount(plane_b & mask). Weighting by 2^b is done by the caller —
    exactly in Python ints (eager engine) or in wider dtype downstream."""
    return jnp.stack([popcount_total(planes[b] & mask)
                      for b in range(planes.shape[0])])


def reduce_sum_bits_grouped(planes: jnp.ndarray,
                            masks: jnp.ndarray) -> jnp.ndarray:
    """Per-(group, bit) masked popcounts for a *stack* of group masks:
    out[g, b] = popcount(plane_b & mask_g). One read of each aggregate
    plane serves every group (the paper's grouped aggregation inside the
    array; arXiv:2307.00658 §4), where per-group ``reduce_sum_bits`` calls
    would re-read the plane stack once per group.

    planes: (n_bits, W) uint32; masks: (n_groups, W) uint32 ->
    (n_groups, n_bits) int32. Weighting by 2^b stays with the caller.
    """
    return jnp.sum(
        popcount_u32(masks[:, None, :] & planes[None, :, :]).astype(jnp.int32),
        axis=-1)


def reduce_sum(planes: jnp.ndarray, mask: jnp.ndarray) -> int:
    """SUM = sum_b 2^b * popcount(plane_b & mask) — bit-serial reduce.

    Eager/exact: the engine executes instruction-at-a-time like a PIM
    controller, so the final weighting runs in arbitrary-precision Python
    ints (the 'host combine' step of Fig. 7).
    """
    pcs = np.asarray(reduce_sum_bits(planes, mask))
    return sum(int(pcs[b]) << b for b in range(pcs.shape[0]))


def reduce_min(planes: jnp.ndarray, mask: jnp.ndarray):
    """MSB-first candidate narrowing (eager). Returns (value:int, found)."""
    n_bits = planes.shape[0]
    cand = mask
    value = 0
    for b in range(n_bits - 1, -1, -1):
        t = cand & ~planes[b]
        if bool(jnp.any(t != 0)):
            cand = t
        else:
            value |= 1 << b
            cand = cand & planes[b]
    return value, bool(jnp.any(mask != 0))


def reduce_max(planes: jnp.ndarray, mask: jnp.ndarray):
    n_bits = planes.shape[0]
    cand = mask
    value = 0
    for b in range(n_bits - 1, -1, -1):
        t = cand & planes[b]
        if bool(jnp.any(t != 0)):
            value |= 1 << b
            cand = t
        else:
            cand = cand & ~planes[b]
    return value, bool(jnp.any(mask != 0))


# --------------------------------------------------------------------------
# DML write primitives (repro.dml): row-targeted plane programming.
# The controller receives (rows, values) in the PIM request (Algorithm 1
# style — values steer the write phases, they are never staged as a
# bit-plane) and programs the listed crossbar rows. Here that becomes a
# word-level masked merge: host-built touch/value bitvectors, one bulk
# ``(plane & ~touch) | vals`` per plane — sharding- and jit-friendly.
# --------------------------------------------------------------------------
def write_touch_mask(rows: np.ndarray, n_words: int) -> np.ndarray:
    """(W,) uint32 bitvector with the listed record slots set."""
    rows = np.asarray(rows, np.int64)
    touch = np.zeros(n_words, np.uint32)
    if rows.size == 0:
        return touch
    word = rows // bitslice.WORD_BITS
    shift = (rows % bitslice.WORD_BITS).astype(np.uint32)
    np.bitwise_or.at(touch, word, np.uint32(1) << shift)
    return touch


def plane_write_masks(rows, values, n_bits: int,
                      n_words: int) -> Tuple[np.ndarray, np.ndarray]:
    """(touch (W,), vals (n_bits, W)) uint32 masks of one PlaneWrite.

    Rows must be distinct within one instruction (the DML layer dedupes
    keeping the last write); repeated rows would OR their value bits.
    """
    rows = np.asarray(rows, np.int64)
    touch = write_touch_mask(rows, n_words)
    vals = np.zeros((n_bits, n_words), np.uint32)
    if rows.size == 0:
        return touch, vals
    v = np.asarray(values, np.uint64)
    word = rows // bitslice.WORD_BITS
    shift = (rows % bitslice.WORD_BITS).astype(np.uint32)
    for b in range(n_bits):
        bits = ((v >> np.uint64(b)) & np.uint64(1)).astype(np.uint32)
        np.bitwise_or.at(vals[b], word, bits << shift)
    return touch, vals


def apply_plane_write(planes: jnp.ndarray, touch: np.ndarray,
                      vals: np.ndarray) -> jnp.ndarray:
    """Masked merge of new row values into an (n_bits, W) plane stack."""
    t = jnp.asarray(touch)
    return (planes & ~t[None, :]) | jnp.asarray(vals)


# Device-fault injection hook (repro.faults): when installed, every DATA
# plane write is routed through it — dead rows drop their touch/value
# bits (the write never programs the row, modeling endurance-exhausted
# cells), and stuck-at cells force their value back after the merge.
# The valid plane is exempt by model choice: it is the one plane the
# controller can always program (an SLC-style healthier region), so
# quarantining a faulty row via ValidClear always succeeds.
_WRITE_FAULT_HOOK = None


def install_write_fault_hook(hook):
    """Install (or, with ``None``, remove) the process-wide write-fault
    hook.  Returns the previously installed hook so callers can restore
    it; the hook must provide ``filter_plane_write(rel, attr, touch,
    vals) -> (touch, vals)`` and ``force_stuck(rel, attr, planes) ->
    planes``."""
    global _WRITE_FAULT_HOOK
    prev = _WRITE_FAULT_HOOK
    _WRITE_FAULT_HOOK = hook
    return prev


# --------------------------------------------------------------------------
# Relation store + executor
# --------------------------------------------------------------------------
@dataclasses.dataclass
class PimRelation:
    """A relation resident in the PIM module (bit-sliced copy, §4.1)."""
    name: str
    layout: bitslice.RelationLayout
    planes: Dict[str, jnp.ndarray]       # attr -> (n_bits, W) uint32
    valid: jnp.ndarray                   # (W,) uint32 valid-record mask
    n_records: int
    # Monotonic content version. Any mutation of the resident copy
    # (INSERT/DELETE/UPDATE, reload) must produce a relation with a higher
    # version; serving-layer result caches key on it, so cached query
    # results are invalidated by construction, never by heuristic.
    version: int = 0

    @classmethod
    def from_columns(cls, name: str, columns: Mapping[str, np.ndarray],
                     encodings: Mapping[str, str] | None = None,
                     widths: Mapping[str, int] | None = None) -> "PimRelation":
        layout = bitslice.build_layout(columns, encodings, widths)
        W = layout.n_words
        planes = {
            a: jnp.asarray(bitslice.pack_bits(np.asarray(col),
                                              layout.attributes[a].n_bits, W))
            for a, col in columns.items()
        }
        valid = jnp.asarray(bitslice.pack_mask(
            np.ones(layout.n_records, bool), W))
        return cls(name, layout, planes, valid, layout.n_records)

    def width_of(self, attr: str) -> int:
        return self.layout.attributes[attr].n_bits

    def bytes_resident(self) -> int:
        """Device-resident bytes: every attribute plane plus the valid
        plane, spanning the FULL reserved capacity (``layout.n_words``
        words per plane) — append segments cost memory whether or not
        their slots hold records yet. Layout-derived rather than summing
        array sizes, so the figure stays honest for any capacity state."""
        return self.layout.row_bits * self.layout.n_words * 4

    def bytes_reserved(self) -> int:
        """The reserved-but-unused share of ``bytes_resident``: plane
        bytes of capacity words past the last word any record occupies —
        the append-segment headroom (tile padding + grown segments) that
        INSERTs fill before the layout ever has to change."""
        used = -(-self.layout.n_records // bitslice.WORD_BITS)
        return self.layout.row_bits * max(0, self.layout.n_words - used) * 4

    def bumped(self) -> "PimRelation":
        """A copy with the content version advanced — the handle mutation
        paths (and tests simulating them) publish so version-keyed caches
        stop serving results computed against the old contents."""
        return dataclasses.replace(self, version=self.version + 1)

    def shard(self, mesh, shard_axes=None) -> "PimRelation":
        """Return a copy with every bit-plane (and the valid plane) placed
        word-axis-sharded over ``shard_axes`` of ``mesh`` — the paper's
        pages-across-modules placement. The word count is always a multiple
        of ``TILE_WORDS`` (1024), so any power-of-two device count divides
        it evenly."""
        from . import distributed as dist   # lazy: avoids import cycle
        ax = dist.mesh_shard_axes(mesh, shard_axes)
        planes = {a: dist.shard_relation_planes(p, mesh, ax)
                  for a, p in self.planes.items()}
        valid = dist.shard_relation_planes(self.valid, mesh, ax)
        return dataclasses.replace(self, planes=planes, valid=valid)


class Engine:
    """Executes PIM instruction sequences on a PimRelation.

    Masks and derived attributes live in a register file (dict) the way the
    paper's computation area holds intermediates inside each crossbar. The
    instruction trace is kept for the cost model.
    """

    def __init__(self, relation: PimRelation, backend: str = "jnp"):
        self.rel = relation
        self.backend = backend
        self.masks: Dict[str, jnp.ndarray] = {"__valid__": relation.valid}
        self.derived: Dict[str, jnp.ndarray] = {}
        self.found: Dict[str, bool] = {}     # ReduceMinMax empty-selection flags
        self.materialized: Dict[str, Dict[str, np.ndarray]] = {}
        self.trace: List[isa.PimInstruction] = []
        if backend == "pallas":
            from repro.kernels import ops as kops   # lazy; optional path
            self._kops = kops
        else:
            self._kops = None

    # -- operand helpers ---------------------------------------------------
    def _planes(self, attr: str) -> jnp.ndarray:
        if attr in self.derived:
            return self.derived[attr]
        if attr in self.masks:          # a mask viewed as a 1-bit attribute
            return self.masks[attr][None, :]
        return self.rel.planes[attr]

    def _width(self, attr: str) -> int:
        if attr in self.derived:
            return self.derived[attr].shape[0]
        if attr in self.masks:
            return 1
        return self.rel.width_of(attr)

    def mask(self, name: str) -> jnp.ndarray:
        return self.masks[name]

    # -- execution ---------------------------------------------------------
    def execute(self, instr: isa.PimInstruction) -> None:
        self.trace.append(instr)
        kind = instr.kind
        if kind == "EqualImm":
            p = self._planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):   # unrepresentable: never equal
                m = jnp.zeros(p.shape[1:], U32)
            elif self._kops is not None:
                m = self._kops.predicate_eq_imm(p, instr.imm)
            else:
                m = eq_imm_planes(p, instr.imm)
            self.masks[instr.dest] = m
        elif kind == "NotEqualImm":
            p = self._planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):
                self.masks[instr.dest] = jnp.full(p.shape[1:], _FULL, U32)
            else:
                self.masks[instr.dest] = ~eq_imm_planes(p, instr.imm)
        elif kind == "LessThanImm":
            p = self._planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):   # every value < imm
                self.masks[instr.dest] = jnp.full(p.shape[1:], _FULL, U32)
            else:
                if self._kops is not None:
                    lt, eq = self._kops.predicate_cmp_imm(p, instr.imm)
                else:
                    lt, eq = cmp_imm_planes(p, instr.imm)
                self.masks[instr.dest] = (lt | eq) if instr.or_equal else lt
        elif kind == "GreaterThanImm":
            p = self._planes(instr.attr)
            if instr.imm >= (1 << p.shape[0]):   # no value > imm
                self.masks[instr.dest] = jnp.zeros(p.shape[1:], U32)
            else:
                if self._kops is not None:
                    lt, eq = self._kops.predicate_cmp_imm(p, instr.imm)
                else:
                    lt, eq = cmp_imm_planes(p, instr.imm)
                self.masks[instr.dest] = ~lt if instr.or_equal else ~(lt | eq)
        elif kind == "Equal":
            lt, eq = cmp_planes(self._planes(instr.attr_a), self._planes(instr.attr_b))
            self.masks[instr.dest] = eq
        elif kind == "LessThan":
            lt, eq = cmp_planes(self._planes(instr.attr_a), self._planes(instr.attr_b))
            self.masks[instr.dest] = (lt | eq) if instr.or_equal else lt
        elif kind == "BitwiseAnd":
            self.masks[instr.dest] = self.masks[instr.src_a] & self.masks[instr.src_b]
        elif kind == "BitwiseOr":
            self.masks[instr.dest] = self.masks[instr.src_a] | self.masks[instr.src_b]
        elif kind == "BitwiseNot":
            if instr.src in self.masks:
                self.masks[instr.dest] = ~self.masks[instr.src]
            else:
                # Attribute NOT: zero-extend to n_bits, invert every plane
                # (the first step of imm - attr via two's complement).
                p = self._planes(instr.src)
                w = instr.n_bits
                if p.shape[0] < w:
                    pad = jnp.zeros((w - p.shape[0],) + p.shape[1:], U32)
                    p = jnp.concatenate([p, pad], axis=0)
                self.derived[instr.dest] = ~p[:w]
        elif kind == "SetReset":
            fill = _FULL if instr.value else np.uint32(0)
            self.masks[instr.dest] = jnp.full((self.rel.layout.n_words,), fill, U32)
        elif kind == "AddImm":
            self.derived[instr.dest] = add_imm_planes(
                self._planes(instr.attr), instr.imm, instr.n_bits)
        elif kind == "Add":
            self.derived[instr.dest] = add_planes(
                self._planes(instr.attr_a), self._planes(instr.attr_b), instr.n_bits)
        elif kind == "Subtract":
            self.derived[instr.dest] = sub_planes(
                self._planes(instr.attr_a), self._planes(instr.attr_b), instr.n_bits)
        elif kind == "Multiply":
            if instr.imm is not None:
                self.derived[instr.dest] = mul_imm_planes(
                    self._planes(instr.attr_a), instr.imm, instr.n_bits)
            else:
                self.derived[instr.dest] = mul_planes(
                    self._planes(instr.attr_a), self._planes(instr.attr_b), instr.n_bits)
        elif kind == "ReduceSum":
            p = self._planes(instr.attr)
            m = self.masks[instr.mask]
            if self._kops is not None:
                self.derived[instr.dest] = self._kops.masked_sum(p, m)
            else:
                self.derived[instr.dest] = reduce_sum(p, m)
        elif kind == "ReduceMinMax":
            fn = reduce_max if instr.is_max else reduce_min
            v, found = fn(self._planes(instr.attr), self.masks[instr.mask])
            self.derived[instr.dest] = v
            self.found[instr.dest] = found
        elif kind == "Materialize":
            # Eager oracle of the materialization kernel: host-side
            # unpack + gather (np.asarray gathers sharded arrays too).
            sel = bitslice.unpack_mask(np.asarray(self.masks[instr.mask]),
                                       self.rel.n_records)
            self.materialized[instr.dest] = {
                a: bitslice.unpack_bits(np.asarray(self._planes(a)),
                                        self.rel.n_records)[sel]
                .astype(np.int64)
                for a in instr.attrs}
        elif kind == "ColumnTransform":
            # In the bit-plane layout the mask is already packed row-wise:
            # the transform is the readout itself. Kept as a traced no-op so
            # the cost model charges the paper's 2050 cycles.
            self.masks[instr.dest] = self.masks[instr.mask]
        elif kind == "PlaneWrite":
            W = self.rel.layout.n_words
            if instr.dest == "__valid__":
                touch, vals = plane_write_masks(instr.rows, instr.values,
                                                1, W)
                valid = (self.rel.valid & ~jnp.asarray(touch)) \
                    | jnp.asarray(vals[0])
                self.rel = dataclasses.replace(self.rel, valid=valid)
                self.masks["__valid__"] = valid
            else:
                p = self.rel.planes[instr.dest]
                touch, vals = plane_write_masks(instr.rows, instr.values,
                                                p.shape[0], W)
                hook = _WRITE_FAULT_HOOK
                if hook is not None:
                    touch, vals = hook.filter_plane_write(
                        self.rel.name, instr.dest, touch, vals)
                planes = dict(self.rel.planes)
                planes[instr.dest] = apply_plane_write(p, touch, vals)
                if hook is not None:
                    planes[instr.dest] = hook.force_stuck(
                        self.rel.name, instr.dest, planes[instr.dest])
                self.rel = dataclasses.replace(self.rel, planes=planes)
        elif kind == "ValidClear":
            touch = write_touch_mask(np.asarray(instr.rows),
                                     self.rel.layout.n_words)
            valid = self.rel.valid & ~jnp.asarray(touch)
            self.rel = dataclasses.replace(self.rel, valid=valid)
            self.masks["__valid__"] = valid
        else:
            raise ValueError(f"unknown instruction {kind}")

    def run(self, program: List[isa.PimInstruction]) -> None:
        for ins in program:
            self.execute(ins)

    # -- readout (the "host reads" the paper charges) -----------------------
    def read_mask(self, name: str) -> np.ndarray:
        packed = np.asarray(self.masks[name])
        return bitslice.unpack_mask(packed, self.rel.n_records)

    def read_scalar(self, name: str):
        return np.asarray(self.derived[name])

    def read_reduce(self, name: str) -> Optional[int]:
        """Reduce result as a Python int; None for MIN/MAX over an empty
        selection (the `found` flag of ReduceMinMax, dropped pre-fix)."""
        if not self.found.get(name, True):
            return None
        return int(np.asarray(self.derived[name]))

    def read_materialized(self, name: str) -> Dict[str, np.ndarray]:
        """Materialized column values ({attr: (count,) int64}, record
        order) of one executed Materialize instruction."""
        return self.materialized[name]

    def count(self, mask: str):
        return int(reduce_count(self.masks[mask] & self.rel.valid))
