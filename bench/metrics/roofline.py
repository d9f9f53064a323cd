"""A kernel's share of its HBM roofline (``<kernel>_roofline``, one per
kernel that moves an end-to-end metric): the algorithmic bytes of the
completed work (from the operand's shape, ``bench/counters.py``) at the
chip's peak bandwidth, over the device's busy time in the traced window."""
from bench import readers


def read(run):
    return readers.roofline(run)
