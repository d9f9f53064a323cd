"""Device program executions per NPB FT time step
(``programs_per_step.<cells>``): the programs that ran on the device in
the traced window, over the steps the service served in it (its
``ft_steps`` counter).  A program without the counter gives nothing."""
from bench import phases


def read(run):
    steps = run.stats.get("ft_steps")
    if run.trace is None or not steps:
        return None
    lo, hi = run.trace_window
    return phases.programs(run.trace, lo, hi) / steps
