"""Padded adjacency slots a graph node step sweeps per state column
(``slots_per_level.<cells>``): the service's ``graph_slots`` counter over
its ``graph_steps`` in the window.  A program without the counter gives
nothing."""


def read(run):
    slots, steps = run.stats.get("graph_slots"), run.stats.get("graph_steps")
    if slots is None or not steps:
        return None
    return slots / steps
