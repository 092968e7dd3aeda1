"""Tile autotuner for the assignment-LB kernel (DESIGN.md §16) —
the (qb, bb) analogue of ``kernels.qgram_filter.autotune``.

The LB kernel has no reduction axis to tile (the whole min-reduce fits
one (qb, bb) tile), so the sweep is over query-block and candidate-block
sizes only.  Tables persist to ``artifacts/tune/assign_lb.json`` with
the same provenance rules: ``timed_on`` recorded per entry, a
CPU-interpret sweep never clobbers a TPU-timed one, and a missing table
falls back to the built-in defaults.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from repro.kernels.qgram_filter.autotune import legal_tile

DEFAULT_TILES: Tuple[int, int] = (8, 128)
DEFAULT_PATH = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "..",
    "artifacts", "tune", "assign_lb.json"))

QB_CANDIDATES = (8, 16)
BB_CANDIDATES = (128, 256)


def canonical_shape(Q: int, N: int, VMq: int, VM: int
                    ) -> Tuple[int, int, int, int]:
    """The shape-bucket key a (Q, N, VMq, VM) launch resolves to."""
    from repro.kernels.assign_lb import ops
    from repro.kernels.qgram_filter.ops import shape_bucket
    return (shape_bucket(Q, ops.Q_BASE, ops.Q_CAP),
            shape_bucket(N, ops.N_BASE, ops.N_CAP),
            shape_bucket(VMq, ops.VM_BASE, ops.VM_CAP), int(VM))


def _key(shape: Sequence[int]) -> str:
    return "x".join(str(int(s)) for s in shape)


class TileTable:
    """Shape-bucket -> (qb, bb) lookup with a default fallback."""

    def __init__(self, entries: Optional[Dict[str, Sequence[int]]] = None,
                 default: Tuple[int, int] = DEFAULT_TILES,
                 timed_on: str = ""):
        self.entries: Dict[str, Tuple[int, int]] = {
            k: tuple(int(x) for x in v) for k, v in (entries or {}).items()}
        self.default = tuple(int(x) for x in default)
        self.timed_on = timed_on

    def lookup(self, Q: int, N: int, VMq: int, VM: int) -> Tuple[int, int]:
        qb, bb = self.entries.get(_key(canonical_shape(Q, N, VMq, VM)),
                                  self.default)
        # the padded launch shapes always divide by a clamped tile, and
        # the TPU lowering wants (8, 128)-aligned (or whole-axis) blocks
        return legal_tile(qb, Q, 8), legal_tile(bb, N, 128)

    def __len__(self) -> int:
        return len(self.entries)


@functools.lru_cache(maxsize=8)
def load_tile_table(path: Optional[str] = None) -> TileTable:
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


def _synth_operands(rng, Q, N, VMq, VM, NE):
    import jax.numpy as jnp
    arr = lambda *s: jnp.asarray(rng.integers(0, 4, s).astype(np.int32))
    qn = rng.integers(1, VMq + 1, Q).astype(np.int32)
    dn = rng.integers(1, VM + 1, N).astype(np.int32)
    return (arr(Q, VMq), arr(Q, VMq), arr(Q, VMq, NE), jnp.asarray(qn),
            arr(N, VM), arr(N, VM), arr(N, VM, NE), jnp.asarray(dn))


def _time_tiles(args, qb, bb, interpret: bool, repeats: int) -> float:
    from repro.kernels.assign_lb.kernel import assign_lb_call
    run = lambda: assign_lb_call(*args, qb=qb, bb=bb, interpret=interpret)
    run().block_until_ready()                      # compile / warm
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(shapes: Iterable[Tuple[int, int, int, int]], *, ne: int = 3,
          candidates: Optional[Iterable[Tuple[int, int]]] = None,
          repeats: int = 3, interpret: Optional[bool] = None,
          max_interpret_n: int = 512, seed: int = 0,
          verbose: bool = False) -> Dict[str, Dict]:
    """Time every candidate tile on every canonical (Q, N, VMq, VM)
    shape; return {shape key: {"tiles": best, "us": ..., "swept": n}}."""
    from repro.kernels.qgram_filter.ops import on_tpu
    if interpret is None:
        interpret = not on_tpu()
    if candidates is None:
        candidates = [(qb, bb) for qb in QB_CANDIDATES
                      for bb in BB_CANDIDATES]
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict] = {}
    for shape in shapes:
        Q, N, VMq, VM = canonical_shape(*shape)
        key = _key((Q, N, VMq, VM))
        if key in out:
            continue
        N_t = min(N, max_interpret_n) if interpret else N
        args = _synth_operands(rng, Q, N_t, VMq, VM, ne)
        best, best_t = DEFAULT_TILES, np.inf
        seen = set()
        for qb, bb in candidates:
            eff = (legal_tile(qb, Q, 8), legal_tile(bb, N_t, 128))
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
        if N_t != N:
            out[key]["timed_n"] = N_t
        if verbose:
            print(f"{key} -> {best} ({best_t * 1e6:.0f}us)")
    return out


def save_table(results: Dict[str, Dict],
               path: Optional[str] = DEFAULT_PATH) -> TileTable:
    """Merge sweep results into the persisted table (same provenance
    rules as the filter-kernel table: TPU entries are never downgraded
    by a CPU-interpret sweep)."""
    import jax
    backend = jax.default_backend()
    doc = {"version": 1, "timed_on": backend, "entries": {}}
    if path is not None and os.path.exists(path):
        try:
            with open(path, encoding="utf-8") as f:
                old = json.load(f)
            doc["entries"] = old.get("entries", {})
            for k, v in doc["entries"].items():
                v.setdefault("timed_on", old.get("timed_on", ""))
        except (OSError, ValueError):
            pass
    for k, v in results.items():
        have = doc["entries"].get(k)
        if (have is not None and have.get("timed_on") == "tpu"
                and backend != "tpu"):
            continue
        doc["entries"][k] = {**v, "timed_on": backend}
    if any(v.get("timed_on") == "tpu" for v in doc["entries"].values()):
        doc["timed_on"] = "tpu"
    if path is not None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        load_tile_table.cache_clear()
    return TileTable({k: v["tiles"] for k, v in doc["entries"].items()},
                     timed_on=doc["timed_on"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--q", type=int, nargs="+", default=[8],
                    help="query-block sizes to tune for")
    ap.add_argument("--n", type=int, nargs="+", default=[128, 512],
                    help="candidate-union sizes to tune for")
    ap.add_argument("--vmq", type=int, default=32)
    ap.add_argument("--vm", type=int, default=32)
    ap.add_argument("--ne", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=DEFAULT_PATH)
    args = ap.parse_args()
    shapes = [(q, n, args.vmq, args.vm) for q in args.q for n in args.n]
    table = save_table(sweep(shapes, ne=args.ne, repeats=args.repeats,
                             verbose=True), args.out)
    print(f"{len(table)} shape buckets tuned "
          f"(timed on {table.timed_on}) -> {args.out}")


if __name__ == "__main__":
    main()
