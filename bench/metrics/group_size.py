"""Requests served per batched launch (the service's own counters) over
the window (``group_size.<cells>``, one per end-to-end metric it moves)."""
from bench import readers


def read(run):
    return readers.group_size(run)
