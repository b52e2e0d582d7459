"""Jit'd public wrappers for the Pallas kernels.

Off the TPU the kernels run in interpret mode so the kernel bodies are
validated end-to-end; on a TPU they compile natively. The choice is made
at trace time, from the backend JAX runs on then.
"""
from __future__ import annotations

from functools import partial

import jax

from . import bitpack as _bitpack
from . import bitwise_filter as _filter
from . import filter_aggregate as _fagg
from .common import interpret_off_tpu as _interpret


@partial(jax.jit, static_argnums=(1,))
def predicate_eq_imm(planes, imm: int):
    return _filter.eq_imm(planes, imm, interpret=_interpret())


@partial(jax.jit, static_argnums=(1,))
def predicate_cmp_imm(planes, imm: int):
    return _filter.cmp_imm(planes, imm, interpret=_interpret())


@partial(jax.jit, static_argnums=(1, 2))
def predicate_range(planes, lo: int, hi: int):
    return _filter.range_mask(planes, lo, hi, interpret=_interpret())


@partial(jax.jit, static_argnums=(3, 4))
def fused_filter_sum(filter_planes, agg_planes, valid, lo: int, hi: int):
    return _fagg.filter_sum(filter_planes, agg_planes, valid, lo, hi,
                            interpret=_interpret())


@jax.jit
def pack_mask(bits):
    return _bitpack.bitpack(bits, interpret=_interpret())


@jax.jit
def unpack_mask(words):
    return _bitpack.bitunpack(words, interpret=_interpret())


def masked_sum(planes, mask):
    """Engine hook: masked bit-serial SUM via the fused kernel machinery."""
    from repro.core import engine as eng
    return eng.reduce_sum(planes, mask)
