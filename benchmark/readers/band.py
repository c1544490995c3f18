"""Reader ``band``: the device trace of a cell whose attention kernel is called
on the band of a sliding window in some layers and on the causal triangle in
others, read with the work that the configuration's reference counts for each
call by its own window (``reference/<name>.py: attention_kernel_cost(model,
tokens, batch, window)``).  ``readers/band.md`` says what it relies on in the
program.

Reader ``lm`` prices every call of its pattern alike, by the triangle; a band
call at 32,768 positions and a window of 4,096 costs a quarter of that (3.09
against 13.19 TFLOP at Trinity-Large's heads), so each kind of call is read
here against its own cost.

``what``:

- ``kernel_roofline``: the least time the chip could take over the calls of
  the kernel whose ops match ``pattern`` (the larger of operations over peak
  FLOP/s and bytes over peak bytes/s, from the call's shapes and window), over
  the device time those calls took, in %.  ``layer_type`` names the layers
  whose calls these are: ``sliding_attention`` passes the configuration's
  ``sliding_window``, ``full_attention`` none.

A ``pattern`` is searched as reader ``lm`` searches it, in an op's name and
what it produces, and one that matches no op the device ran raises.
"""

from __future__ import annotations

from benchmark.jobs import _zoo
from benchmark.readers.lm import _ops_s


def read(state, *, what, pattern, layer_type):
    trace = state["ctx"].traced
    if trace is None:
        return None
    if what != "kernel_roofline":
        raise ValueError(f"reader band: unknown what={what!r}")
    if layer_type not in ("sliding_attention", "full_attention"):
        raise ValueError(f"reader band: unknown layer_type={layer_type!r}")
    ref = _zoo.reference_of(state["config"])
    model, window, peaks = state["config"]["model"], state["run"]["window"], state["peaks"]
    seconds, calls = _ops_s(trace, pattern)
    flops, moved = ref.attention_kernel_cost(model, int(window["record_tokens"]), int(window["batch_records"]),
                                             window=model["sliding_window"] if layer_type == "sliding_attention"
                                             else None)
    least = max(flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * len(calls) * least / seconds
