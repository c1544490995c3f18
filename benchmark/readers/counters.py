"""Reader ``counters``: the job's metric registry as reported after the run.

``of`` and ``over`` name entries ``<operator>.<subtask>.<metric>``.  A histogram
contributes its total (mean x count) unless ``stat`` picks one of its fields; a
list under ``over`` is summed.  ``scale`` multiplies the result.
"""

from __future__ import annotations


def _value(counters, key, stat=None):
    entry = counters.get(key)
    if entry is None:
        return None
    if isinstance(entry, dict):
        if stat is not None:
            return entry.get(stat)
        if "total_s" in entry:
            return entry["total_s"]
        if "mean" in entry:
            return entry["mean"] * entry["count"]
        return entry.get("count")
    return entry


def read(state, *, of, over=None, stat=None, scale=1.0):
    counters = state["run"]["counters"]
    top = _value(counters, of, stat)
    if top is None or top != top:
        return None
    if over is None:
        return scale * top
    parts = [_value(counters, k) for k in ([over] if isinstance(over, str) else over)]
    if any(p is None for p in parts) or not sum(parts):
        return None
    return scale * top / sum(parts)
