"""Mean time a query waited in the admission inbox and batch former
(``queue`` spans), per query issued."""


def read(run):
    if not run.spans or not run.n_queries:
        return None
    return run.span_sum("queue") / run.n_queries * 1e3
