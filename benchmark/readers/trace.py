"""Reader ``trace``: numbers of the device trace of the traced span."""

from __future__ import annotations

import statistics

from benchmark import flops


def read(state, *, what, module=None):
    """``module``: a pattern for the name of the step's program, which the two
    step readings need."""
    trace = state["ctx"].traced
    if trace is None:
        return None
    if what == "idle_share":
        return 100.0 * trace.idle_share()
    if what == "module_ms_p50":
        return 1e3 * statistics.median(trace.module_runs(module))
    if what == "step_mfu":
        # Whole runs of the step's program in the traced span, each over one
        # batch, against what the chips could have done in that span.
        runs = trace.module_runs(module)
        work = flops.step_flops(state["config"]) * state["run"]["window"]["batch_records"] * len(runs)
        peak = state["peaks"]["bf16_flops_per_s"] * state["cell"]["chips"]
        return 100.0 * work / (trace.window_s * peak)
    if what == "exposed_collective_share":
        return 100.0 * trace.exposed_collective_s() / trace.window_s
    raise ValueError(f"reader trace: unknown what={what!r}")
