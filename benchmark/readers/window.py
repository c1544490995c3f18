"""Reader ``window``: the benchmark's own stamps of the measured window."""

from __future__ import annotations

import numpy as np


def read(state, *, series, percentile):
    values = state["run"]["window"].get(series)
    if values is None or not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), percentile))
