"""Thread CPU time per query in the q-gram filter: the ``cpu_ms`` of the
``filter_bucket`` spans, per query sent.  Against ``filter_ms.closed``
(their wall time) the rest is time the filter thread waited."""


def read(run):
    cpu = [s.args["cpu_ms"] for s in run.spans
           if s.name == "filter_bucket" and "cpu_ms" in s.args]
    if not cpu or not run.n_queries:
        return None
    return sum(cpu) / run.n_queries
