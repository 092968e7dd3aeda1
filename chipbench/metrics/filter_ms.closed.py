"""Mean q-gram filter time per query: the ``filter_bucket`` spans, which
hold the device pass and its host sync."""


def read(run):
    if not run.spans or not run.n_queries:
        return None
    return run.span_sum("filter_bucket") / run.n_queries * 1e3
