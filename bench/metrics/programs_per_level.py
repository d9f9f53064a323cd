"""Device program executions per graph node step
(``programs_per_level.<cells>``): the programs that ran on the device in
the traced window, over the BFS levels and PageRank power steps the
service launched in it (the service's ``graph_steps`` counter)."""
from bench import phases


def read(run):
    steps = run.stats.get("graph_steps", 0)
    if run.trace is None or not steps:
        return None
    lo, hi = run.trace_window
    return phases.programs(run.trace, lo, hi) / steps
