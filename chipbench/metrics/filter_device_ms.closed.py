"""Mean time per query in the filter's device section: the
``filter_device`` spans (query upload, dispatch, device pass and copy
back), per query sent."""


def read(run):
    spans = [s for s in run.spans if s.name == "filter_device"]
    if not spans or not run.n_queries:
        return None
    return sum(s.t1 - s.t0 for s in spans) / run.n_queries * 1e3
