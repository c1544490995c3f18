"""Reader ``moe``: the grouped products of a routed-expert layer in the device
trace, read with the work that the configuration's reference counts from its
shapes (``reference/<name>.py: expert_kernel_cost``, ``sizes``).
``readers/moe.md`` says what it relies on in the program.

``what``:

- ``kernel_roofline``: the least time the chip could take over the grouped
  products of every routed layer of every run of the step's program
  (``module``) in the traced span (a run cut by the span's edge for the part
  of it that was seen; a layer's products: the larger of operations over peak
  FLOP/s and bytes over peak bytes/s, from their shapes), over the device time
  of the ops that match ``pattern``, in %.  However many ops the compiler
  makes of a product, they are counted by their time and the work by the
  layers run.

A ``pattern`` is searched as reader ``lm`` searches it, in an op's name and
what it produces, and one that matches no op the device ran raises.
"""

from __future__ import annotations

import statistics

from benchmark.jobs import _zoo
from benchmark.readers.lm import _ops_s


def read(state, *, what, module, pattern):
    trace = state["ctx"].traced
    if trace is None:
        return None
    if what != "kernel_roofline":
        raise ValueError(f"reader moe: unknown what={what!r}")
    ref = _zoo.reference_of(state["config"])
    model, window, peaks = state["config"]["model"], state["run"]["window"], state["peaks"]
    seconds, _ = _ops_s(trace, pattern)
    runs = trace.module_runs(module)
    layers_run = ref.sizes(model)["expert_layers"] * sum(runs) / statistics.median(runs)
    flops, moved = ref.expert_kernel_cost(model, int(window["record_tokens"]), int(window["batch_records"]))
    least = max(flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * layers_run * least / seconds
