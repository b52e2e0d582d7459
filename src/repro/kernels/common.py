"""Helpers shared by every Pallas kernel in this package."""
from __future__ import annotations

import jax
import numpy as np


def interpret_off_tpu() -> bool:
    """Pallas ``interpret`` for the backend JAX runs on now: compiled
    kernels on a TPU, the interpreter anywhere else. Asked at trace
    time, never at import."""
    return jax.default_backend() != "tpu"


def pick_block(w: int, requested: int) -> int:
    """Largest power-of-two block <= requested that divides w (w is always a
    multiple of 1024 by the bitslice layout contract)."""
    b = min(requested, w)
    while w % b:
        b //= 2
    return max(b, 1)


def popcount(v):
    """SWAR popcount per uint32 lane — usable inside kernel bodies."""
    v = v - ((v >> 1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    return (v * np.uint32(0x01010101)) >> 24
