"""Share of the filter's device-slab lookups that found the bucket's
slab resident, in percent: the ``slab_cache.jax_db`` hits over hits plus
misses."""


def read(run):
    hits = run.counters.get("slab_cache.jax_db.hits", 0)
    misses = run.counters.get("slab_cache.jax_db.misses", 0)
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
