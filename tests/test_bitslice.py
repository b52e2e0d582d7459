"""Bit-plane layout: pack/unpack roundtrips (property-based)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import bitslice


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 2000), st.integers(1, 40), st.integers(0, 2**32))
def test_pack_unpack_roundtrip(n, bits, seed):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 1 << bits, n, dtype=np.uint64)
    planes = bitslice.pack_bits(vals, bits)
    back = bitslice.unpack_bits(planes, n)
    assert (back == vals).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 70_000), st.integers(1, 27), st.integers(0, 2**32))
def test_pack_unpack_roundtrip_padded_words(n, bits, seed):
    """pack_bits/unpack_bits round-trip with explicit (tile-padded)
    n_words and non-tile-multiple n — the layout contract the
    materialization kernel inverts (pad bits must read back as absent,
    not as phantom records)."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 1 << bits, n, dtype=np.uint64)
    W = bitslice.pad_words(n)
    planes = bitslice.pack_bits(vals, bits, W)
    assert planes.shape == (bits, W)
    assert (bitslice.unpack_bits(planes, n) == vals).all()
    # masked gather oracle (what kernels.materialize must reproduce)
    sel = rng.random(n) < 0.5
    mask = bitslice.pack_mask(sel, W)
    got = bitslice.unpack_bits(planes, n)[bitslice.unpack_mask(mask, n)]
    assert (got == vals[sel]).all()


def _gather_unpack_mask(words, n):
    """The per-record gather that ``unpack_mask`` replaced: the oracle."""
    words = np.asarray(words, dtype=np.uint32)
    idx = np.arange(n, dtype=np.int64)
    bits = (words[idx // 32] >> (idx % 32).astype(np.uint32)) & np.uint32(1)
    return bits.astype(bool)


def _check_unpack_mask(words, n):
    got = bitslice.unpack_mask(words, n)
    assert got.dtype == np.bool_ and got.shape == (n,)
    assert got.flags.writeable
    np.testing.assert_array_equal(got, _gather_unpack_mask(words, n))
    return got


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 5000), st.integers(0, 2**32))
def test_mask_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    m = rng.random(n) < 0.3
    packed = bitslice.pack_mask(m)
    assert (bitslice.unpack_mask(packed, n) == m).all()
    _check_unpack_mask(packed, n)
    # Random stray 1-bits past n in the tile-padded words must not read back.
    stray = rng.random(packed.shape[0] * 32) < 0.5
    stray[:n] = False
    dirty = packed | bitslice.pack_mask(stray, packed.shape[0])
    assert (_check_unpack_mask(dirty, n) == m).all()


@pytest.mark.parametrize("n", [0, 1, 31, 33, 63, 1000, bitslice.TILE_RECORDS - 5,
                               bitslice.TILE_RECORDS, bitslice.TILE_RECORDS + 7])
def test_unpack_mask_matches_gather(n):
    """Lengths off the word and tile grid, and n == 0, against the gather."""
    rng = np.random.default_rng(n)
    words = rng.integers(0, 2**32, bitslice.pad_words(n), dtype=np.uint32)
    _check_unpack_mask(words, n)


def test_unpack_mask_ignores_stray_bits_beyond_n():
    """Words at the tile-padded capacity, every bit past n set to 1."""
    n = 70_001
    rng = np.random.default_rng(7)
    m = rng.random(n) < 0.02
    words = bitslice.pack_mask(m, bitslice.pad_words(n))
    tail = np.ones(words.shape[0] * 32, dtype=bool)
    tail[:n] = False
    words |= bitslice.pack_mask(tail, words.shape[0])
    assert words[-1] == np.uint32(0xFFFFFFFF)
    got = _check_unpack_mask(words, n)
    np.testing.assert_array_equal(got, m)


@pytest.mark.parametrize("layout", ["big_endian", "strided"])
def test_unpack_mask_byte_order_and_strides(layout):
    rng = np.random.default_rng(11)
    n = 5_000
    words = rng.integers(0, 2**32, bitslice.pad_words(n), dtype=np.uint32)
    if layout == "big_endian":
        given_words = words.astype(">u4")
        assert given_words.dtype.byteorder == ">"
    else:
        wide = np.zeros((words.shape[0], 3), dtype=np.uint32)
        wide[:, 1] = words
        given_words = wide[:, 1]
        assert not given_words.flags.c_contiguous
    got = bitslice.unpack_mask(given_words, n)
    np.testing.assert_array_equal(got, _gather_unpack_mask(words, n))


def test_unpack_mask_sf1_lineitem():
    """An SF 1 lineitem-sized mask: 6,001,215 records, 2% selected."""
    n = 6_001_215
    rng = np.random.default_rng(2024)
    m = rng.random(n) < 0.02
    words = bitslice.pack_mask(m)
    got = _check_unpack_mask(words, n)
    np.testing.assert_array_equal(got, m)


@pytest.mark.parametrize("n", [4 * 32 + 1, 5 * 32, -1])
def test_unpack_mask_rejects_counts_out_of_range(n):
    """Past the words' bits np.unpackbits would pad with zeros, and a
    negative count would trim from the end: both raise instead."""
    words = np.zeros(4, dtype=np.uint32)
    with pytest.raises(ValueError):
        bitslice.unpack_mask(words, n)


def test_padding_is_tile_aligned():
    assert bitslice.pad_words(1) == bitslice.TILE_WORDS
    assert bitslice.pad_words(bitslice.TILE_RECORDS) == bitslice.TILE_WORDS
    assert bitslice.pad_words(bitslice.TILE_RECORDS + 1) == 2 * bitslice.TILE_WORDS


def test_layout_coordinates_and_utilization():
    cols = {"a": np.arange(100), "b": np.arange(100) * 7}
    layout = bitslice.build_layout(cols)
    c = layout.coordinates(33, "a", 2)
    assert c["tile"] == 0 and c["lane"] == 33 % 32
    assert 0 < layout.memory_utilization() < 1
    with pytest.raises(IndexError):
        layout.coordinates(0, "a", 99)


def test_negative_values_rejected():
    with pytest.raises(ValueError):
        bitslice.pack_bits(np.asarray([-1, 2]), 4)
