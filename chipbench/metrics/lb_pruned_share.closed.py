"""Share of the candidate pairs entering the assignment LB that it pruned
before A*, in percent: the ``lb_pruned`` counter over the pairs the
``assign_lb`` spans report."""


def read(run):
    pairs = sum(int(s.args.get("n_pairs", 0)) for s in run.spans
                if s.name == "assign_lb")
    if not pairs:
        return None
    return 100.0 * run.counters.get("lb_pruned", 0) / pairs
