"""Mean time per query spent encoding query graphs and grouping them by
region bucket (``encode`` and ``bucket`` spans)."""


def read(run):
    if not run.spans or not run.n_queries:
        return None
    return run.span_sum("encode", "bucket") / run.n_queries * 1e3
