"""Succinct block-decode Pallas kernel (the TPU hybrid encoding).

TPU adaptation of the paper's per-block hybrid coding (DESIGN.md §3): each
block of 128 entries is stored at the narrowest power-of-two bit width in
{2, 4, 8, 16, 32} that fits its maximum value (the per-block *scheme choice*
of the paper, with vectorisable fixed-width lanes instead of bit-serial
Elias gamma).  Because 128 * w / 32 is an integer for every width, block
payloads are word-aligned: SB[k] is a word offset and no entry straddles a
word.

Kernel layout:
  * the packed word stream stays in HBM as ``(rows, 128)`` words; a block's
    <=128-word window starting at SB[k] always lies within two consecutive
    rows, which the kernel DMAs into VMEM (row-granular copies at a dynamic
    row index — no unaligned slice of a 1-D ref);
  * each grid step decodes ``GROUP`` = 8 blocks, so the output block is one
    (8, 128) int32 tile; the per-block (word offset, width) pairs arrive as
    an SMEM (8, 2) block;
  * decoding is a per-lane gather within those two rows: entry e of a
    width-w block reads word ``SB % 128 + e // (32 / w)`` at bit
    ``32 - w - (e % (32 / w)) * w`` — shifts and masks on the VPU, with
    the width-dependent divisions written as shifts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ENTRIES = 128
WIDTHS = (2, 4, 8, 16, 32)
MAX_WORDS = BLOCK_ENTRIES * 32 // 32  # width=32 worst case: 128 words
GROUP = 8                             # blocks decoded per grid step
LANES = 128


def _kernel(meta_ref,      # SMEM (GROUP, 2) int32: word offset, bit width
            words_hbm,     # ANY (rows, 128) int32 — packed stream
            out_ref,       # VMEM (GROUP, 128) int32 — decoded blocks
            lo_buf,        # VMEM (GROUP, 128) int32: row holding SB[k]
            hi_buf,        # VMEM (GROUP, 128) int32: the row after it
            sem):          # DMA semaphores (2, GROUP)
    copies = []
    for g in range(GROUP):
        row = meta_ref[g, 0] // LANES
        for half, buf in enumerate((lo_buf, hi_buf)):
            cp = pltpu.make_async_copy(words_hbm.at[pl.ds(row + half, 1)],
                                       buf.at[pl.ds(g, 1)], sem.at[half, g])
            cp.start()
            copies.append(cp)
    for cp in copies:
        cp.wait()

    off = jnp.stack([meta_ref[g, 0] % LANES for g in range(GROUP)])[:, None]
    width = jnp.stack([meta_ref[g, 1] for g in range(GROUP)])[:, None]
    # log2(width) for width in WIDTHS; entries per word = 32 >> lw
    lw = sum((width >= w).astype(jnp.int32) for w in WIDTHS)
    e = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANES), 1)
    word_idx = off + jax.lax.shift_right_logical(e, 5 - lw)
    slot = e & (jax.lax.shift_right_logical(jnp.int32(32), lw) - 1)
    lane = word_idx & (LANES - 1)
    word = jnp.where(word_idx < LANES,
                     jnp.take_along_axis(lo_buf[...], lane, axis=1),
                     jnp.take_along_axis(hi_buf[...], lane, axis=1))
    shift = 32 - width - jax.lax.shift_left(slot, lw)
    mask = jnp.where(width == 32, jnp.int32(-1),
                     jax.lax.shift_left(jnp.int32(1), width) - 1)
    out_ref[...] = jax.lax.shift_right_logical(word, shift) & mask


@functools.partial(jax.jit, static_argnames=("n_blocks", "interpret"))
def bitunpack_call(sb, widths, words, *, n_blocks: int,
                   interpret: bool = False) -> jax.Array:
    """Decode all blocks: returns (n_blocks, 128) int32.

    ``words`` must be padded with >= MAX_WORDS trailing words so the last
    window never reads out of bounds; here it is further padded to whole
    128-word rows plus one spare row for the two-row window.
    """
    g_pad = -(-n_blocks // GROUP) * GROUP
    meta = jnp.stack([sb, widths], axis=1).astype(jnp.int32)
    # pad blocks decode word 0 at width 2 and are sliced off below
    meta = jnp.pad(meta, ((0, g_pad - n_blocks), (0, 0)),
                   constant_values=WIDTHS[0])
    meta = meta.at[n_blocks:, 0].set(0)
    n_rows = -(-words.shape[0] // LANES) + 1
    rows = jnp.pad(words.astype(jnp.int32),
                   (0, n_rows * LANES - words.shape[0])).reshape(n_rows, LANES)
    out = pl.pallas_call(
        _kernel,
        grid=(g_pad // GROUP,),
        in_specs=[
            pl.BlockSpec((GROUP, 2), lambda k: (k, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((GROUP, BLOCK_ENTRIES), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((g_pad, BLOCK_ENTRIES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((GROUP, LANES), jnp.int32),
                        pltpu.VMEM((GROUP, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((2, GROUP))],
        interpret=interpret,
    )(meta, rows)
    return out[:n_blocks]
