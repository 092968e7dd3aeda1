"""Block-size autotuner for the query-batched fused filter kernel
(DESIGN.md §13).

The kernel's ``(qb, bb, bu)`` tile sizes trade VMEM residency against
grid overhead, and the right point depends on the serving shapes — how
many queries share a block, how many graphs a region bucket holds, how
wide the degree vocabulary is.  The ROADMAP's open item ("tune the
qgram_filter block sizes for the padded multi-query shapes") is this
module: sweep candidate tiles over the *real bucket shapes* of a built
index, keep the fastest per canonical shape bucket, persist the table to
``artifacts/tune/qgram_filter.json``, and let serving load it with the
built-in defaults as fallback (``MSQConfig.tile_table()`` /
``BatchedFilterEval``).

Off-TPU the sweep runs the kernel in interpret mode — the same code path
CI exercises — so the machinery is tested everywhere; the timings that
matter are the ones taken on a real TPU (``timed_on`` records which kind
a table holds).  Candidate tiles are powers of two so every tile evenly
divides every shape-bucket ladder value (``ops.shape_bucket``).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEFAULT_TILES: Tuple[int, int, int] = (8, 128, 512)
DEFAULT_PATH = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "..",
    "artifacts", "tune", "qgram_filter.json"))

# powers of two only: shape_bucket guarantees any of these tiles an even
# grid after min(tile, bucket)
QB_CANDIDATES = (8, 16)
BB_CANDIDATES = (128, 256)
BU_CANDIDATES = (128, 256, 512)
# the finalize step keeps (qb, bb, VM)-sized intermediates in VMEM; on a
# v5e, qb * bb above this overflows it (16 x 256 is refused)
MAX_QB_BB = 2048


def canonical_shape(Q: int, B: int, U: int) -> Tuple[int, int, int]:
    """The shape-bucket key a (Q, B, U) launch resolves to — independent
    of the tile choice, so the tuner and the serving path agree."""
    from repro.kernels.qgram_filter import ops
    return (ops.shape_bucket(Q, ops.Q_BASE, ops.Q_CAP),
            ops.shape_bucket(B, ops.B_BASE, ops.B_CAP),
            ops.shape_bucket(U, ops.U_BASE, ops.U_CAP))


def _key(shape: Tuple[int, int, int]) -> str:
    return "x".join(str(int(s)) for s in shape)


def legal_tile(tile: int, n: int, align: int) -> int:
    """A block size the TPU lowering accepts on an axis padded to ``n``:
    at least ``align`` (8 on the sublane axis, 128 on the lane axis) or
    else the whole axis.  Bucket sizes are powers of two or multiples of
    the cap, so the result still tiles ``n`` evenly."""
    return min(max(int(tile), align), int(n))


def legal_tiles(tiles: Sequence[int], shape: Sequence[int]
                ) -> Tuple[int, int, int]:
    """Clamp (qb, bb, bu) to what the TPU compiler accepts on a canonical
    (Q, B, U) shape: (8, 128)-aligned blocks (or whole axes), and
    qb * bb within ``MAX_QB_BB`` (bb shrinks first).  Idempotent, so a
    table of legal tiles reads back unchanged."""
    Q, B, U = shape
    qb = legal_tile(tiles[0], Q, 8)
    bb = legal_tile(tiles[1], B, 128)
    while qb * bb > MAX_QB_BB and bb > 128:
        bb //= 2
    while qb * bb > MAX_QB_BB and qb > 8:
        qb //= 2
    return qb, bb, legal_tile(tiles[2], U, 128)


class TileTable:
    """Shape-bucket -> (qb, bb, bu) lookup with a default fallback."""

    def __init__(self, entries: Optional[Dict[str, Sequence[int]]] = None,
                 default: Tuple[int, int, int] = DEFAULT_TILES,
                 timed_on: str = ""):
        self.entries: Dict[str, Tuple[int, int, int]] = {
            k: tuple(int(x) for x in v) for k, v in (entries or {}).items()}
        self.default = tuple(int(x) for x in default)
        self.timed_on = timed_on

    def lookup(self, Q: int, B: int, U: int) -> Tuple[int, int, int]:
        shape = canonical_shape(Q, B, U)
        return legal_tiles(self.entries.get(_key(shape), self.default),
                           shape)

    def __len__(self) -> int:
        return len(self.entries)


@functools.lru_cache(maxsize=8)
def load_tile_table(path: Optional[str] = None) -> TileTable:
    """Load the persisted table; a missing/unreadable file is the default
    table (tuning is an optimisation, never a requirement)."""
    path = DEFAULT_PATH if path is None else path
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        entries = {k: v["tiles"] for k, v in doc.get("entries", {}).items()}
        return TileTable(entries, timed_on=doc.get("timed_on", ""))
    except (OSError, ValueError, KeyError, TypeError):
        return TileTable()


def default_table() -> TileTable:
    return load_tile_table(None)


def _synth_operands(rng, Q, B, U, NV, NE, VM):
    """Random tile-aligned operands of one canonical shape."""
    import jax.numpy as jnp
    sc = np.concatenate([rng.integers(1, 30, (Q, 2)),
                         rng.integers(1, 4, (Q, 1)),
                         np.full((Q, 2), 25), np.full((Q, 1), 4)],
                        axis=1).astype(np.int32)
    aux = np.concatenate([rng.integers(1, 30, (B, 2)),
                          rng.integers(-3, 4, (B, 2))], 1).astype(np.int32)
    arr = lambda *s: jnp.asarray(rng.integers(0, 4, s).astype(np.int32))
    return (jnp.asarray(sc), arr(B, U), arr(Q, U), arr(B, NV), arr(Q, NV),
            arr(B, NE), arr(Q, NE), arr(B, VM), arr(Q, VM),
            jnp.asarray(aux), jnp.asarray(np.zeros((Q, B), np.int32)))


def _time_tiles(args, qb, bb, bu, interpret: bool, repeats: int) -> float:
    from repro.kernels.qgram_filter.kernel import fused_batched_call
    run = lambda: fused_batched_call(*args, qb=qb, bb=bb, bu=bu,
                                     interpret=interpret)[0]
    run().block_until_ready()                      # compile / warm
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(shapes: Iterable[Tuple[int, int, int]], *,
          nv: int = 62, ne: int = 3, vm: int = 64,
          candidates: Optional[Iterable[Tuple[int, int, int]]] = None,
          repeats: int = 3, interpret: Optional[bool] = None,
          max_interpret_b: int = 1024, seed: int = 0,
          verbose: bool = False) -> Dict[str, Dict]:
    """Time every candidate tile on every canonical shape; return
    {shape key: {"tiles": best, "us": best time, "swept": n}}.

    Interpret mode (CPU) clamps B to ``max_interpret_b`` — the Python
    grid loop makes huge shapes pointless to time there, and the table
    those runs produce is exercise/fallback material, not a tuning claim.
    """
    from repro.kernels.qgram_filter.ops import on_tpu
    if interpret is None:
        interpret = not on_tpu()
    if candidates is None:
        candidates = [(qb, bb, bu) for qb in QB_CANDIDATES
                      for bb in BB_CANDIDATES for bu in BU_CANDIDATES]
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict] = {}
    for shape in shapes:
        Q, B, U = canonical_shape(*shape)
        # the key is ALWAYS the unclamped canonical shape — serving looks
        # buckets up by their true size, so a clamp-keyed entry would
        # never be found; the clamp only shrinks what gets timed
        key = _key((Q, B, U))
        if key in out:
            continue
        B_t = min(B, max_interpret_b) if interpret else B
        args = _synth_operands(rng, Q, B_t, U, nv, ne, vm)
        best, best_t = DEFAULT_TILES, np.inf
        seen = set()
        for qb, bb, bu in candidates:
            eff = legal_tiles((qb, bb, bu), (Q, B_t, U))
            if eff in seen:
                continue
            seen.add(eff)
            t = _time_tiles(args, *eff, interpret=interpret,
                            repeats=repeats)
            if verbose:
                print(f"  {key} tiles={eff}: {t * 1e6:.0f}us")
            if t < best_t:
                best, best_t = eff, t
        out[key] = {"tiles": list(best), "us": best_t * 1e6,
                    "swept": len(seen)}
        if B_t != B:
            out[key]["timed_b"] = B_t
        if verbose:
            print(f"{key} -> {best} ({best_t * 1e6:.0f}us)")
    return out


def slab_shapes(slab, qs: Sequence[int] = (8, 64),
                max_shapes: int = 4) -> List[Tuple[int, int, int]]:
    """The real bucket shapes a built FilterSlab serves: the full slab
    plus the largest distinct per-region bucket sizes, at each expected
    query-block size.  U is the layout's on-device F_D width (hot prefix
    for 'hot', the 128-block-padded decode width for 'packed')."""
    if slab.layout == "hot":
        U = slab.hot_d
    elif slab.layout == "packed":
        U = slab.packed.sb.shape[1] * 128
    else:
        U = slab.U
    sizes = {int(slab.B)}
    _, counts = np.unique(
        np.stack([slab.region_i, slab.region_j]), axis=1, return_counts=True)
    for c in sorted(counts.tolist(), reverse=True)[:max_shapes]:
        sizes.add(int(c))
    return [(int(q), b, U) for q in qs for b in sorted(sizes)]


def autotune_slab(slab, *, qs: Sequence[int] = (8, 64),
                  save_path: Optional[str] = DEFAULT_PATH,
                  **kw) -> "TileTable":
    """Index-build-time entry point: sweep the slab's real bucket shapes,
    merge into (and persist to) the on-disk table, return the merged
    TileTable.  ``save_path=None`` skips persistence."""
    results = sweep(slab_shapes(slab, qs=qs),
                    nv=slab.vhist.shape[1], ne=slab.ehist.shape[1],
                    vm=slab.degseq.shape[1], **kw)
    return save_table(results, save_path)


def save_table(results: Dict[str, Dict],
               path: Optional[str] = DEFAULT_PATH) -> TileTable:
    """Merge sweep results into the persisted table and return it.

    Merge rule: a CPU-interpret sweep (exercise/fallback material) must
    never clobber an entry timed on a real TPU — only same-or-better
    provenance replaces (``timed_on`` is kept per entry; the table-level
    field reports 'tpu' iff any entry is TPU-timed)."""
    import jax
    backend = jax.default_backend()
    doc = {"version": 1, "timed_on": backend, "entries": {}}
    if path is not None and os.path.exists(path):
        try:
            with open(path, encoding="utf-8") as f:
                old = json.load(f)
            doc["entries"] = old.get("entries", {})
            for k, v in doc["entries"].items():   # rows predating the
                v.setdefault("timed_on", old.get("timed_on", ""))  # field
        except (OSError, ValueError):
            pass
    for k, v in results.items():
        have = doc["entries"].get(k)
        if (have is not None and have.get("timed_on") == "tpu"
                and backend != "tpu"):
            continue                  # never downgrade TPU timings
        doc["entries"][k] = {**v, "timed_on": backend}
    if any(v.get("timed_on") == "tpu" for v in doc["entries"].values()):
        doc["timed_on"] = "tpu"
    if path is not None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        load_tile_table.cache_clear()      # readers see the new table
    return TileTable({k: v["tiles"] for k, v in doc["entries"].items()},
                     timed_on=doc["timed_on"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=2000,
                    help="graphs in the synthetic AIDS-like DB")
    ap.add_argument("--layout", default="dense",
                    choices=["dense", "hot", "packed"])
    ap.add_argument("--hot-d", type=int, default=128)
    ap.add_argument("--q", type=int, nargs="+", default=[8, 64],
                    help="query-block sizes to tune for")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=DEFAULT_PATH)
    args = ap.parse_args()

    from repro.core.qgrams import EncodedDB
    from repro.core.region import default_partition
    from repro.core.slab import FilterSlab
    from repro.graphs.generators import aids_like_db

    db = aids_like_db(args.n, seed=0)
    enc = EncodedDB.build(db, None)
    nv, ne = db.sizes()
    partition = default_partition(nv, ne, l=4)
    slab = FilterSlab.build(db, enc, partition, layout=args.layout,
                            hot_d=args.hot_d if args.layout == "hot"
                            else None)
    table = autotune_slab(slab, qs=tuple(args.q), save_path=args.out,
                          repeats=args.repeats, verbose=True)
    print(f"{len(table)} shape buckets tuned "
          f"(timed on {table.timed_on}) -> {args.out}")


if __name__ == "__main__":
    main()
