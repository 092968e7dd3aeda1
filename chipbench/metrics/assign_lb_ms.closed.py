"""Mean assignment-LB (stage 1.5) time per query (``assign_lb`` spans)."""


def read(run):
    if not run.spans or not run.n_queries:
        return None
    return run.span_sum("assign_lb") / run.n_queries * 1e3
