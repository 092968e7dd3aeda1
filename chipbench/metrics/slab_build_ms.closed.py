"""Mean time per query spent building per-bucket slabs on a device-cache
miss: the ``slab_gather`` (host gather) and ``slab_upload`` (device copy)
spans of every field, per query sent.  A window with no miss reads 0;
a program that counts no slab-cache lookups reads nothing."""


def read(run):
    if not run.spans or not run.n_queries or not any(
            k.startswith("slab_cache.") for k in run.counters):
        return None
    return run.span_sum("slab_gather", "slab_upload") / run.n_queries * 1e3
