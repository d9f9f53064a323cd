"""Requests completed over the whole window."""


def read(run):
    done = len(run.done)
    return done / run.window_s if done and run.window_s > 0 else None
