"""Set-up: process start to the window's first due request (generation,
registration, compilation or cache loads, warm-up)."""


def read(run):
    return run.setup_s
