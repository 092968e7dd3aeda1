"""A* node expansions per query issued, from the ``verify`` spans."""


def read(run):
    if not run.spans or not run.n_queries:
        return None
    return sum(int(s.args.get("expansions", 0)) for s in run.spans
               if s.name == "verify") / run.n_queries
