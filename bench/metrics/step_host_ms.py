"""Median over service steps of the step's wall time minus the device's
busy time inside it: the host's round trip per step, in ms."""
from bench import readers


def read(run):
    return readers.step_host_ms(run)
