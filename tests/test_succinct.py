"""Succinct structures (Section 5.2): rank, coders, hybrid blocks."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.succinct import (BitReader, BitVector, BitWriter,
                                 HybridEncodedArray, delta_length,
                                 encoded_bits_per_entry, gamma_length,
                                 golomb_length, read_delta, read_gamma,
                                 write_delta, write_gamma)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 3000))
def test_bitvector_rank(seed, n):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, n).astype(np.uint8)
    bv = BitVector(bits)
    cum = np.concatenate([[0], np.cumsum(bits)])
    idx = rng.integers(0, n + 1, 32)
    for j in idx:
        assert bv.rank1(int(j)) == cum[j]
    assert np.array_equal(bv.rank1_bulk(idx), cum[idx])
    some = rng.integers(0, n, 16)
    assert np.array_equal(bv.get_bulk(some), bits[some])


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 10 ** 6), min_size=1, max_size=60))
def test_gamma_delta_roundtrip(values):
    bw = BitWriter()
    for v in values:
        write_gamma(bw, v)
    br = BitReader(bw.to_words(), bw.nbits)
    pos = 0
    for v in values:
        got, pos = read_gamma(br, pos)
        assert got == v
    assert pos == sum(gamma_length(v) for v in values)

    bw = BitWriter()
    for v in values:
        write_delta(bw, v)
    br = BitReader(bw.to_words(), bw.nbits)
    pos = 0
    for v in values:
        got, pos = read_delta(br, pos)
        assert got == v
    assert pos == sum(delta_length(v) for v in values)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 500), min_size=1, max_size=300),
       st.sampled_from([4, 8, 16, 32]))
def test_hybrid_array_access(values, block):
    arr = HybridEncodedArray(values, block=block)
    assert arr.decode_all().tolist() == values
    rng = np.random.default_rng(0)
    for j in rng.integers(0, len(values), 20):
        assert arr.access(int(j)) == values[int(j)]


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(1, 64), min_size=16, max_size=400))
def test_hybrid_never_worse_than_components(values):
    """The hybrid scheme's payload is min(fixed, gamma) per block, so its
    average bits/entry is <= both (the Table-2 claim)."""
    h = encoded_bits_per_entry(values, "hybrid")
    f = encoded_bits_per_entry(values, "fixed")
    g = encoded_bits_per_entry(values, "gamma")
    assert h <= f + 1e-9
    assert h <= g + 1e-9


def test_golomb_lengths_sane():
    assert golomb_length(1, 1) == 1
    assert golomb_length(1, 4) == 3  # q=0 stop bit + 2-bit remainder
    for m in (1, 2, 3, 4, 5, 8, 10):
        for x in range(1, 40):
            assert golomb_length(x, m) >= 1


def test_space_bound_section_5_4():
    """|S_X| <= |Psi| * (floor(log b_max) + 1) bits (paper's bound)."""
    rng = np.random.default_rng(2)
    values = rng.integers(1, 40, 700).tolist()
    arr = HybridEncodedArray(values, block=16)
    bmax = max(values)
    bound = len(values) * (int(np.floor(np.log2(bmax))) + 1)
    assert arr.size_bits().s_bits <= bound
