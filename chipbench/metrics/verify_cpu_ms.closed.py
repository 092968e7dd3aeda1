"""Thread CPU time per query in A* verification: the ``cpu_ms`` of the
``verify`` slice spans, per query sent.  Against ``verify_ms.closed``
(their wall time) the rest is time the verifier threads waited."""


def read(run):
    cpu = [s.args["cpu_ms"] for s in run.spans
           if s.name == "verify" and "cpu_ms" in s.args]
    if not cpu or not run.n_queries:
        return None
    return sum(cpu) / run.n_queries
