"""95th percentile of latency over every request due in the window, from
its due time to ``poll`` returning its result; a request that failed or
never returned counts as infinitely late."""
import math

import numpy as np


def read(run):
    if not run.records:
        return None
    lat = [(r.done - r.due) * 1e3 if r.ok else math.inf for r in run.records]
    return float(np.percentile(lat, 95, method="higher"))
