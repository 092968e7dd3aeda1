"""Ahead-of-time compiles of the served path's Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology.  Each test compiles one kernel at
the widths the AIDS deployment (configs/msq_aids.py: 62 vertex labels, 3
edge labels, |V| <= 64, a degree q-gram vocabulary of ~1.2k ids) serves
with, and asserts a Mosaic kernel (``tpu_custom_call``) is in the result —
so a kernel the chip's compiler refuses fails here, at no chip time.
Interpret mode cannot catch these: block shapes that break the (8, 128)
tiling, strided minor-dim gathers, or tiles that overflow VMEM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import json
import os

import pytest

# AIDS-sized serving shapes: padded query block, region-bucket rows, the
# F_D width of each slab layout (dense vocabulary bucket, hot prefix,
# packed decode width), db vertex width, labels
Q, B, VM, NV, NE = 8, 4096, 64, 62, 3
U_BY_LAYOUT = {"dense": 1536, "hot": 512, "packed": 1280}
TUNE_DIR = os.path.join(os.path.dirname(__file__), "..", "artifacts", "tune")


def _table_keys(name):
    with open(os.path.join(TUNE_DIR, name), encoding="utf-8") as f:
        return sorted(json.load(f)["entries"])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_tpu(topo):
    """``compile_tpu(fn, *shapes)`` -> the compiled text, for one v5e chip.
    The persistent compile cache is off meanwhile: an entry written for a
    described chip cannot be read back without one."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes, mosaic=True):
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip),
            list(shapes), is_leaf=lambda s: isinstance(s, tuple) and all(
                isinstance(d, int) for d in s))
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert ("tpu_custom_call" in text) == mosaic
        return text

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _filter_shapes(q, b, u):
    return [(q, 6), (b, u), (q, u), (b, NV), (q, NV), (b, NE), (q, NE),
            (b, VM), (q, VM), (b, 5), (q, b)]


@pytest.mark.parametrize("layout", sorted(U_BY_LAYOUT))
def test_qgram_filter_compiles_at_aids_widths(compile_tpu, layout):
    from repro.kernels.qgram_filter.autotune import TileTable
    from repro.kernels.qgram_filter.ops import fused_filter_bounds_batched
    U = U_BY_LAYOUT[layout]
    qb, bb, bu = TileTable().lookup(Q, B, U)
    compile_tpu(lambda *a: fused_filter_bounds_batched(
        *a, qb=qb, bb=bb, bu=bu, interpret=False), *_filter_shapes(Q, B, U))


@pytest.mark.parametrize("key", _table_keys("qgram_filter.json"))
def test_qgram_filter_tuned_tiles_compile(compile_tpu, key):
    """Every tile the persisted table hands out is one the compiler
    accepts (``TileTable.lookup`` clamps to legal tiles)."""
    from repro.kernels.qgram_filter.autotune import load_tile_table
    from repro.kernels.qgram_filter.ops import fused_filter_bounds_batched
    q, b, u = (int(x) for x in key.split("x"))
    qb, bb, bu = load_tile_table(None).lookup(q, b, u)
    compile_tpu(lambda *a: fused_filter_bounds_batched(
        *a, qb=qb, bb=bb, bu=bu, interpret=False), *_filter_shapes(q, b, u))


@pytest.mark.parametrize("key", _table_keys("assign_lb.json") + ["8x512x64x64"])
def test_assign_lb_compiles(compile_tpu, key):
    """The last case is the AIDS serving shape under the default tiles."""
    from repro.kernels.assign_lb.autotune import load_tile_table
    from repro.kernels.assign_lb.ops import assign_lb_bounds_batched
    q, n, vmq, vm = (int(x) for x in key.split("x"))
    qb, bb = load_tile_table(None).lookup(q, n, vmq, vm)
    compile_tpu(lambda *a: assign_lb_bounds_batched(
        *a, qb=qb, bb=bb, interpret=False),
        (q, vmq), (q, vmq), (q, vmq, NE), (q,), (n, vm), (n, vm),
        (n, vm, NE), (n,))


def test_bitunpack_compiles_at_aids_widths(compile_tpu):
    """The packed slab's per-launch decode: B rows of ceil(U / 128)
    blocks, words at the widest (32-bit) payload plus the guard."""
    from repro.kernels.bitunpack.kernel import bitunpack_call
    n_blocks = B * -(-U_BY_LAYOUT["packed"] // 128)
    compile_tpu(lambda sb, w, words: bitunpack_call(
        sb, w, words, n_blocks=n_blocks), (n_blocks,), (n_blocks,),
        (n_blocks * 128 + 128,))


def test_pallas_kernels_carry_stable_names(compile_tpu):
    """The served kernels keep their names on the chip, so a device trace
    finds them: ``msq_qgram_filter_<layout>`` and ``msq_assign_lb``."""
    from repro.kernels.assign_lb.ops import assign_lb_bounds_batched
    from repro.kernels.qgram_filter.ops import fused_filter_bounds_batched
    U = U_BY_LAYOUT["dense"]
    hot = compile_tpu(lambda *a: fused_filter_bounds_batched(
        *a, interpret=False), *_filter_shapes(Q, B, U))
    dense = compile_tpu(lambda *a: fused_filter_bounds_batched(
        *a, interpret=False), *_filter_shapes(Q, B, U)[:-1])
    lb = compile_tpu(lambda *a: assign_lb_bounds_batched(
        *a, interpret=False), (Q, VM), (Q, VM), (Q, VM, NE), (Q,), (B, VM),
        (B, VM), (B, VM, NE), (B,))
    assert "msq_qgram_filter_hot" in hot
    assert "msq_qgram_filter_dense" in dense
    assert "msq_assign_lb" in lb


# (graphs, F_D width, vertex width, vertex / edge labels, padded bucket
# rows, sparse query width) of the two served deployments
SERVED = {"aids": (42687, 1851, 64, 62, 3, 5632, 64),
          "s100k": (100000, 342, 16, 5, 2, 65536, 32)}


@pytest.mark.parametrize("name", sorted(SERVED))
def test_resident_filter_pass_compiles_at_served_sizes(compile_tpu, name):
    """The jax backend's filter pass over the whole resident slab (plus
    its sentinel row), gathering one padded bucket by row index."""
    from repro.core.arrays import DBArrays, QueryArrays
    from repro.core.engine import _bounds_multi_jit
    b, u, vm, nv, ne, n, k = SERVED[name]
    r = b + 1
    db = DBArrays((r,), (r,), (r, vm), (r, nv), (r, ne), (r, u), (r,), (r,))
    qb = QueryArrays((Q,), (Q,), (Q, vm), (Q, nv), (Q, ne), (Q, u), (Q,))
    text = compile_tpu(_bounds_multi_jit("dense"), db, (n,), qb, (Q, k),
                       (Q, k), mosaic=False)
    assert "msq_qgram_filter_dense" in text
