"""A* verification time per query: the sum of the ``verify`` slice spans
over the window's queries, per query issued."""


def read(run):
    if not run.spans or not run.n_queries:
        return None
    return run.span_sum("verify") / run.n_queries * 1e3
