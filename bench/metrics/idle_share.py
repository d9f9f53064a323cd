"""Percent of the traced window in which no program ran on the device
(``idle_share.<cells>``, one per end-to-end metric it moves)."""
from bench import readers


def read(run):
    return readers.idle_share(run)
