"""Reader ``lm``: the device trace of a language-model stream cell, read with
the work that the configuration's reference counts from its shapes
(``reference/<name>.py``: ``forward_flops``, ``attention_kernel_cost``).
``readers/lm.md`` says what it relies on in the program.

``what``:

- ``step_mfu``: runs of the step's program (``module``) in the traced span,
  each over one window of ``batch_records`` records of ``record_tokens``
  positions (one cut by the span's edge for the part of it that was seen),
  against what the chip could have done in that span.  First holds the program's own count to that: the counter
  ``<track>.tokens`` (real tokens, never padding) must be ``batch_records x
  record_tokens`` a batch of ``<track>.batches``, or the share would count
  padding as work: it raises where they differ;
- ``kernel_roofline``: the least time the chip could take over the calls of
  the kernel whose ops match ``pattern`` (the larger of operations over peak
  FLOP/s and bytes over peak bytes/s, from the kernel's shapes), over the
  device time those calls took, in %;
- ``op_share``: device time of the ops that match ``pattern`` over that of
  the runs of ``module``, in %.

The trace names an op by its HLO text, ``%name = <result shapes> kind(<operands>)``.
A ``pattern`` is searched in the part before the op's kind, its name and what it
produces, so that an op that merely reads a tensor of the scan is not the scan.
A ``pattern`` that matches no op the device ran raises, as a ``module`` that
matches no program does: a kernel renamed or a scan reshaped must not change
silently what is read.
"""

from __future__ import annotations

import re
import statistics

from benchmark import trace_reduce
from benchmark.jobs import _zoo
from benchmark.readers import counters


#: Where an op's kind starts in its HLO text: `` fusion(``, `` custom-call(``, `` while(``.
KIND = re.compile(r" [a-z][\w\-]*\(")


def produced(name: str) -> str:
    """``%fusion.2 = f32[2,32,2,16,128,128]{...} fusion(f32[...] %p, ...)`` ->
    ``%fusion.2 = f32[2,32,2,16,128,128]{...}``."""
    return KIND.split(name, 1)[0]


def _ops_s(trace, pattern: str):
    """(seconds, events) of the ops on the first device that match ``pattern``;
    nested matches (a loop and the ops of its body) count once."""
    device = min(trace.device_events)
    found = [(s, e) for name, s, e in trace.device_events[device] if re.search(pattern, produced(name))]
    if not found:
        seen = sorted({trace_reduce.short_op(name) for name, _, _ in trace.device_events[device]})
        raise LookupError(f"no op on the device matches {pattern!r}; it ran {seen[:40]} ...")
    return trace_reduce.union_ns(found) / 1e9, found


def read(state, *, what, module=None, pattern=None, track="model.0"):
    trace = state["ctx"].traced
    if trace is None:
        return None
    ref = _zoo.reference_of(state["config"])
    model, window = state["config"]["model"], state["run"]["window"]
    batch, tokens = int(window["batch_records"]), int(window["record_tokens"])
    peaks = state["peaks"]
    if what == "step_mfu":
        counted = counters.read(state, of=f"{track}.tokens", over=f"{track}.batches")
        if counted is None:
            return None  # a program that counts no tokens: nothing to hold the share to
        if counted != batch * tokens:
            raise ValueError(f"{track}.tokens / {track}.batches is {counted}, the cell's window is "
                             f"{batch} x {tokens}: padding would be counted as work")
        # A run cut by the span's edge counts for the part of it that was seen
        # (its share of a whole run's median time), so that the share cannot
        # pass its ceiling once the device never idles.
        runs = trace.module_runs(module)
        whole = sum(runs) / statistics.median(runs)
        work = ref.forward_flops(model, tokens) * batch * whole
        return 100.0 * work / (trace.window_s * peaks["bf16_flops_per_s"] * state["cell"]["chips"])
    if what == "kernel_roofline":
        seconds, calls = _ops_s(trace, pattern)
        flops, moved = ref.attention_kernel_cost(model, tokens, batch)
        least = max(flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
        return 100.0 * len(calls) * least / seconds
    if what == "op_share":
        seconds, _ = _ops_s(trace, pattern)
        return 100.0 * seconds / sum(trace.module_runs(module))
    raise ValueError(f"reader lm: unknown what={what!r}")
