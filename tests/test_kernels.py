"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.bitunpack.ops import pack_hybrid, unpack_hybrid
from repro.kernels.bitunpack.ref import unpack_hybrid_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.qgram_filter.ops import (fused_filter_bounds,
                                            fused_filter_bounds_batched,
                                            make_aux, make_scalars,
                                            shape_bucket)
from repro.kernels.qgram_filter.ref import (fused_batched_bounds_ref,
                                            fused_filter_bounds_ref)
from repro.kernels.rank_popcount.kernel import block_popcounts
from repro.kernels.rank_popcount.ops import build_rank_dictionary, rank1_query
from repro.kernels.rank_popcount.ref import block_popcounts_ref, rank1_query_ref
from repro.core.succinct import BitVector


# --------------------------------------------------------------------------
# qgram_filter
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,U,NV,NE,VM", [
    (7, 33, 5, 3, 9), (64, 256, 62, 3, 40), (130, 700, 16, 2, 16),
])
def test_qgram_filter_kernel_vs_ref(B, U, NV, NE, VM):
    rng = np.random.default_rng(B * U)
    fd = rng.integers(0, 4, (B, U)).astype(np.int32)
    qfd = rng.integers(0, 4, U).astype(np.int32)
    vh = rng.integers(0, 5, (B, NV)).astype(np.int32)
    qvh = rng.integers(0, 5, NV).astype(np.int32)
    eh = rng.integers(0, 5, (B, NE)).astype(np.int32)
    qeh = rng.integers(0, 5, NE).astype(np.int32)
    ds = -np.sort(-rng.integers(0, 6, (B, VM)), axis=1).astype(np.int32)
    qs = -np.sort(-rng.integers(0, 6, VM)).astype(np.int32)
    aux = np.asarray(make_aux(
        jnp.asarray(rng.integers(1, 30, B).astype(np.int32)),
        jnp.asarray(rng.integers(0, 40, B).astype(np.int32)),
        jnp.asarray(rng.integers(-3, 4, B).astype(np.int32)),
        jnp.asarray(rng.integers(-3, 4, B).astype(np.int32)),
        jnp.asarray(rng.integers(0, 3, B).astype(np.int32))))
    sc = make_scalars(10, 12, 3, 25, 27, 4)
    b1, m1 = fused_filter_bounds(sc, fd, qfd, vh, qvh, eh, qeh, ds, qs, aux,
                                 interpret=True)
    b2, m2 = fused_filter_bounds_ref(sc, jnp.asarray(fd), jnp.asarray(qfd),
                                     jnp.asarray(vh), jnp.asarray(qvh),
                                     jnp.asarray(eh), jnp.asarray(qeh),
                                     jnp.asarray(ds), jnp.asarray(qs),
                                     jnp.asarray(aux))
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    assert np.array_equal(np.asarray(m1), np.asarray(m2))


def _batched_case(rng, Q, B, U, NV=7, NE=3, VM=11):
    """Random operands for the query-batched kernel + its per-query ref."""
    fd = rng.integers(0, 4, (B, U)).astype(np.int32)
    vh = rng.integers(0, 5, (B, NV)).astype(np.int32)
    eh = rng.integers(0, 5, (B, NE)).astype(np.int32)
    ds = -np.sort(-rng.integers(0, 6, (B, VM)), axis=1).astype(np.int32)
    aux = np.concatenate([rng.integers(1, 30, (B, 2)),
                          rng.integers(-3, 4, (B, 2))], 1).astype(np.int32)
    cdt = rng.integers(0, 3, (Q, B)).astype(np.int32)
    sc = np.concatenate(
        [rng.integers(1, 30, (Q, 2)), rng.integers(1, 4, (Q, 1)),
         np.full((Q, 2), 25), np.full((Q, 1), 4)], 1).astype(np.int32)
    qfd = rng.integers(0, 4, (Q, U)).astype(np.int32)
    qvh = rng.integers(0, 5, (Q, NV)).astype(np.int32)
    qeh = rng.integers(0, 5, (Q, NE)).astype(np.int32)
    qsig = -np.sort(-rng.integers(0, 6, (Q, VM)), axis=1).astype(np.int32)
    return sc, fd, qfd, vh, qvh, eh, qeh, ds, qsig, aux, cdt


def _batched_ref(case):
    """(Q, B) oracle via ref.fused_batched_bounds_ref (itself a loop of
    the already-ref-tested single-query ref)."""
    b, m = fused_batched_bounds_ref(*[jnp.asarray(x) for x in case])
    return np.asarray(b), np.asarray(m)


@pytest.mark.parametrize("Q,B,U", [
    (1, 7, 33),        # everything ragged and tiny
    (5, 130, 260),     # Q/B/U all off the tile multiples
    (8, 64, 128),      # exactly tile-aligned
    (13, 97, 515),     # ragged against every default tile
])
def test_qgram_filter_batched_vs_ref_ragged(Q, B, U):
    rng = np.random.default_rng(Q * 1000 + B)
    case = _batched_case(rng, Q, B, U)
    want_b, want_m = _batched_ref(case)
    got_b, got_m = fused_filter_bounds_batched(
        *[jnp.asarray(x) for x in case], interpret=True)
    assert np.array_equal(np.asarray(got_b), want_b)
    assert np.array_equal(np.asarray(got_m), want_m)


def test_qgram_filter_batched_tile_sweep():
    """The (qb, bb, bu) choice must never change a single bound/mask bit
    — that is what makes the autotuner safe to run blind."""
    rng = np.random.default_rng(42)
    case = _batched_case(rng, 6, 70, 300)
    want_b, want_m = _batched_ref(case)
    args = [jnp.asarray(x) for x in case]
    for qb in (2, 4, 8, 16):
        for bb, bu in [(16, 128), (32, 256), (64, 512), (128, 128)]:
            got_b, got_m = fused_filter_bounds_batched(
                *args, qb=qb, bb=bb, bu=bu, interpret=True)
            assert np.array_equal(np.asarray(got_b), want_b), (qb, bb, bu)
            assert np.array_equal(np.asarray(got_m), want_m), (qb, bb, bu)


def test_qgram_filter_batched_no_cdt_means_zeros():
    rng = np.random.default_rng(3)
    case = _batched_case(rng, 4, 33, 140)
    zero = list(case)
    zero[-1] = np.zeros_like(case[-1])
    want_b, want_m = _batched_ref(tuple(zero))
    got_b, got_m = fused_filter_bounds_batched(
        *[jnp.asarray(x) for x in case[:-1]], None, interpret=True)
    assert np.array_equal(np.asarray(got_b), want_b)
    assert np.array_equal(np.asarray(got_m), want_m)


def test_shape_bucket_ladder():
    # powers of two times base up to cap, then cap multiples — and always
    # divisible by min(block, bucket) for power-of-two blocks
    assert [shape_bucket(n, 8, 512) for n in (1, 8, 9, 65, 512, 513)] == \
        [8, 8, 16, 128, 512, 1024]
    for n in (3, 17, 100, 700, 2000):
        for blk in (8, 16, 64, 128, 512):
            bucket = shape_bucket(n, 8, 512)
            assert bucket >= n and bucket % min(blk, bucket) == 0


def test_qgram_filter_block_size_invariance():
    rng = np.random.default_rng(0)
    B, U = 96, 512
    args = (make_scalars(8, 9, 2, 20, 22, 4),
            rng.integers(0, 3, (B, U)).astype(np.int32),
            rng.integers(0, 3, U).astype(np.int32),
            rng.integers(0, 4, (B, 8)).astype(np.int32),
            rng.integers(0, 4, 8).astype(np.int32),
            rng.integers(0, 4, (B, 3)).astype(np.int32),
            rng.integers(0, 4, 3).astype(np.int32),
            -np.sort(-rng.integers(0, 5, (B, 12)), axis=1).astype(np.int32),
            -np.sort(-rng.integers(0, 5, 12)).astype(np.int32),
            np.concatenate([rng.integers(1, 20, (B, 2)),
                            rng.integers(-2, 3, (B, 2)),
                            np.zeros((B, 1), int)], 1).astype(np.int32))
    outs = [fused_filter_bounds(*args, bb=bb, bu=bu, interpret=True)
            for bb, bu in [(16, 64), (32, 128), (96, 512)]]
    for b, m in outs[1:]:
        assert np.array_equal(np.asarray(outs[0][0]), np.asarray(b))
        assert np.array_equal(np.asarray(outs[0][1]), np.asarray(m))


# --------------------------------------------------------------------------
# assign_lb (stage-1.5 assignment lower bound, DESIGN.md §16)
# --------------------------------------------------------------------------

def _assign_lb_case(rng, Q, N, vmq_raw, vm_raw):
    """Ragged branch-feature blocks padded with the production helpers:
    query side via pad_query_block, db side with the slab-gather fills
    (label -1 / degree 0 / zero hists, nv pad 0)."""
    from repro.kernels.assign_lb.ops import (N_BASE, N_CAP, VM_BASE, VM_CAP,
                                             pad_query_block)

    def feats(counts, vm):
        v = np.full((len(counts), vm), -1, np.int32)
        d = np.zeros((len(counts), vm), np.int32)
        eh = np.zeros((len(counts), vm, 3), np.int32)
        for r, c in enumerate(counts):
            v[r, :c] = rng.integers(0, 5, c)
            eh[r, :c] = rng.integers(0, 3, (c, 3))
            d[r, :c] = eh[r, :c].sum(1)
        return v, d, eh

    qn = rng.integers(1, vmq_raw + 1, Q).astype(np.int32)
    dn = rng.integers(1, vm_raw + 1, N).astype(np.int32)
    qv, qd, qeh = feats(qn, vmq_raw)
    dv, dd, deh = feats(dn, vm_raw)
    qv, qd, qeh, qn = pad_query_block(qv, qd, qeh, qn)
    npad = shape_bucket(N, N_BASE, N_CAP)
    vmp = shape_bucket(vm_raw, VM_BASE, VM_CAP)
    pr = npad - N
    dv = np.pad(dv, [(0, pr), (0, vmp - vm_raw)], constant_values=-1)
    dd = np.pad(dd, [(0, pr), (0, vmp - vm_raw)])
    deh = np.pad(deh, [(0, pr), (0, vmp - vm_raw), (0, 0)])
    dn = np.pad(dn, (0, pr))
    return qv, qd, qeh, qn, dv, dd, deh, dn


@pytest.mark.parametrize("Q,N,VMq,VM", [
    (1, 7, 5, 9),       # everything ragged and tiny
    (5, 130, 11, 17),   # every axis off its bucket
    (8, 64, 8, 16),     # exactly bucket-aligned
    (13, 97, 30, 40),   # ragged against the default tiles
])
def test_assign_lb_kernel_vs_ref_ragged(Q, N, VMq, VM):
    from repro.kernels.assign_lb.ops import (assign_lb_bounds_batched,
                                             assign_lb_np)
    from repro.kernels.assign_lb.ref import batched_assign_lb_ref
    rng = np.random.default_rng(Q * 1000 + N)
    case = _assign_lb_case(rng, Q, N, VMq, VM)
    want = assign_lb_np(*case)
    ref = np.asarray(batched_assign_lb_ref(*[jnp.asarray(x) for x in case]))
    got = np.asarray(assign_lb_bounds_batched(
        *case, qb=min(8, case[0].shape[0]), bb=min(128, case[4].shape[0]),
        interpret=True))
    assert np.array_equal(ref, want)
    assert np.array_equal(got, want)


def test_assign_lb_tile_sweep():
    """The (qb, bb) tile choice must never change a single bound — what
    makes the assign_lb autotuner safe to run blind."""
    from repro.kernels.assign_lb.ops import (assign_lb_bounds_batched,
                                             assign_lb_np)
    rng = np.random.default_rng(7)
    case = _assign_lb_case(rng, 6, 70, 10, 14)      # pads to (8, 128)
    want = assign_lb_np(*case)
    for qb in (2, 4, 8):
        for bb in (16, 32, 64, 128):
            got = np.asarray(assign_lb_bounds_batched(
                *case, qb=qb, bb=bb, interpret=True))
            assert np.array_equal(got, want), (qb, bb)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_assign_lb_le_exact_ged(seed):
    """Provability on random graph pairs: Hausdorff <= Hungarian <= the
    exact GED (so stage-1.5 pruning can never drop a true match)."""
    from repro.core.verify import GEDSearch
    from repro.graphs.generators import random_graph
    from repro.kernels.assign_lb.ops import (assign_lb_np,
                                             graph_branch_features,
                                             hungarian_lb_pair)
    rng = np.random.default_rng(seed)
    n1, n2 = (int(rng.integers(2, 7)) for _ in range(2))
    g = random_graph(rng, n1, int(rng.integers(n1 - 1, 2 * n1)), 4, 2)
    h = random_graph(rng, n2, int(rng.integers(n2 - 1, 2 * n2)), 4, 2)
    ged = GEDSearch(g, h, 60).run()     # tau far above any possible GED
    qf = graph_branch_features(g, 2)
    hf = graph_branch_features(h, 2)
    haus = int(assign_lb_np(
        qf[0][None], qf[1][None], qf[2][None], np.array([g.n]),
        hf[0][None], hf[1][None], hf[2][None], np.array([h.n]))[0, 0])
    hung = hungarian_lb_pair(*qf, *hf)
    assert haus <= ged
    if hung is not None:                # scipy-gated
        assert haus <= hung <= ged


# --------------------------------------------------------------------------
# bitunpack
# --------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 600),
       st.sampled_from([2, 14, 250, 60000, 2 ** 30]))
def test_bitunpack_roundtrip(seed, n, hi):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, hi, n).astype(np.int64)
    words, sb, widths, nv = pack_hybrid(vals)
    out = np.asarray(unpack_hybrid(sb, widths, words, nv, interpret=True))
    assert np.array_equal(out, vals)
    ref = np.asarray(unpack_hybrid_ref(jnp.asarray(sb), jnp.asarray(widths),
                                       jnp.asarray(words)))
    assert np.array_equal(ref.reshape(-1)[:nv], vals)


def test_bitunpack_mixed_widths():
    # force different widths across blocks
    vals = np.concatenate([np.ones(128, np.int64),
                           np.full(128, 200, np.int64),
                           np.full(128, 70000, np.int64),
                           np.arange(1, 129, dtype=np.int64)])
    words, sb, widths, nv = pack_hybrid(vals)
    assert len(set(widths.tolist())) >= 3
    out = np.asarray(unpack_hybrid(sb, widths, words, nv, interpret=True))
    assert np.array_equal(out, vals)


# --------------------------------------------------------------------------
# rank_popcount
# --------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 30000))
def test_rank_kernel_matches_refs(seed, n):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, n).astype(np.uint8)
    words, cum = build_rank_dictionary(bits, interpret=True)
    assert np.array_equal(np.asarray(block_popcounts(words, interpret=True)),
                          np.asarray(block_popcounts_ref(words)))
    idx = rng.integers(0, n + 1, 48).astype(np.int32)
    r_k = np.asarray(rank1_query(words, cum, jnp.asarray(idx)))
    r_r = np.asarray(rank1_query_ref(words, jnp.asarray(idx)))
    bv = BitVector(bits)
    r_h = np.array([bv.rank1(int(i)) for i in idx])
    assert np.array_equal(r_k, r_r)
    assert np.array_equal(r_k, r_h)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    dict(B=2, Hq=4, Hkv=2, Sq=64, Skv=64, D=16, causal=True, window=0,
         off=0, bq=16, bk=16),
    dict(B=1, Hq=8, Hkv=8, Sq=32, Skv=32, D=8, causal=True, window=8,
         off=0, bq=8, bk=8),
    dict(B=1, Hq=4, Hkv=1, Sq=16, Skv=128, D=16, causal=True, window=0,
         off=112, bq=16, bk=32),
    dict(B=2, Hq=2, Hkv=2, Sq=48, Skv=48, D=32, causal=False, window=0,
         off=0, bq=16, bk=16),
    dict(B=1, Hq=2, Hkv=1, Sq=40, Skv=40, D=16, causal=True, window=12,
         off=0, bq=8, bk=8),
])
def test_flash_attention_vs_ref(case, dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(case["B"], case["Hq"], case["Sq"],
                                     case["D"])), dtype)
    k = jnp.asarray(rng.normal(size=(case["B"], case["Hkv"], case["Skv"],
                                     case["D"])), dtype)
    v = jnp.asarray(rng.normal(size=(case["B"], case["Hkv"], case["Skv"],
                                     case["D"])), dtype)
    out = flash_attention(q, k, v, causal=case["causal"],
                          window=case["window"], kv_offset=case["off"],
                          bq=case["bq"], bk=case["bk"], impl="interpret")
    ref = attention_ref(q, k, v, causal=case["causal"],
                        window=case["window"], kv_offset=case["off"])
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=1e-2)


def test_flash_attention_xla_impl_matches_ref():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 4, 32, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 32, 16)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, impl="xla")
    b = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
