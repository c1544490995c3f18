"""Reader ``pulse``: the program's own record of its stalls.

What it relies on in the program (``benchmark/readers/pulse.md``): the flight
ring through ``flink_tensorflow_tpu.tracing.flight.recorder_of(job)``, with the
job named after the cell, and on it the track ``process`` that the program's
pulse writes: the instant ``pulse.late`` and the span ``gc``.  A program
without a pulse (the parent of the PR that brought it) has nothing to give:
``read`` then returns None and the line leaves the metric out.

``what``:

- ``late_ms_max``: the longest ``late_s`` of a ``pulse.late`` on ``track``
  inside the measured window, in ms; 0.0 with none.  Over the whole window and
  not the traced span, as ``overslept_ms_max`` of the ``spans`` reader is.

Once a run it prints one line to stderr, as the span join does: how many
pulses came late and the seconds booked to each ``cause``; for the longest
one its args, and the span of each hot-path thread of ``model.0`` /
``train.0`` that covers it, with what that thread was charged in it; and for
the longest ``park.overslept`` whether a ``gc`` span covers it.
"""

from __future__ import annotations

import sys

from benchmark.readers import spans

#: The hot path's spans by the thread that reads their two stamps, innermost
#: first (``emit`` and ``collect_wait`` run inside ``fire`` or ``fill``).
THREADS = {
    "subtask": ("emit", "collect_wait", "fire", "fill", "drain_wait", "dispatch",
                "h2d_enqueue", "assemble", "open"),
    "lane": ("enqueue",),
    "fetch": ("unbatch", "in_flight"),
}
#: The operators' tracks: the model operator's and the gang train operator's.
TRACKS = ("model.0", "train.0")


def _events(state):
    """The ring's events, or None where the program has no pulse or kept no ring."""
    try:
        from flink_tensorflow_tpu.tracing.flight import Pulse  # noqa: F401
    except ImportError:
        return None
    return spans._events(state)


def _overlap(a0, a1, b0, b1):
    return max(min(a1, b1) - max(a0, b0), 0.0)


def covering(events, lo, hi):
    """``{thread: (name, seconds of the span, its args)}``: of each thread of
    the operators' tracks the span that covers most of ``[lo, hi]``, the
    innermost where two cover as much."""
    out = {}
    for thread, names in THREADS.items():
        best = None
        for track, name, ph, t0, dur, args in events:
            if ph != "X" or track not in TRACKS or name not in names:
                continue
            key = (round(_overlap(t0, t0 + dur, lo, hi), 6), -names.index(name))
            if key[0] > 0 and (best is None or key > best[0]):
                best = (key, (name, dur, args or {}))
        if best is not None:
            out[thread] = best[1]
    return out


def _charge(args):
    """The account's deltas in a span's args, whichever names they go by."""
    return {k: round(v, 4) for k, v in args.items()
            if k in ("cpu_s", "runq_s", "fetch_cpu_s", "fetch_runq_s")}


def describe(events, track, lo, hi) -> str:
    """The line: late pulses inside ``[lo, hi)`` by cause, the longest with
    what covers it, and the longest late park against the collections."""
    late = [(t, args) for ev_track, name, _, t, _, args in events
            if ev_track == track and name == "pulse.late" and lo <= t < hi]
    booked = {}
    for _, args in late:
        booked[args["cause"]] = booked.get(args["cause"], 0.0) + args["late_s"]
    text = f"pulse: {len(late)} late in the window" + "".join(
        f", {cause} {seconds:.3f} s" for cause, seconds in sorted(booked.items()))
    if late:
        t, args = max(late, key=lambda row: row[1]["late_s"])
        text += f"; longest at {t - lo:.1f} s: " + ", ".join(
            f"{k} {v:.4f}" if isinstance(v, float) else f"{k} {v}" for k, v in args.items())
        for thread, (name, dur, span_args) in covering(events, t - args["late_s"], t).items():
            text += f"; {thread}: {name} {dur:.3f} s {_charge(span_args)}"
    gcs = [(t0, t0 + dur) for ev_track, name, ph, t0, dur, _ in events
           if ev_track == track and name == "gc" and ph == "X"]
    parks = [(t, args) for _, name, _, t, _, args in events
             if name == "park.overslept" and lo <= t < hi]
    text += f"; {sum(lo <= a < hi for a, _ in gcs)} full collections in the window"
    if parks:
        t, args = max(parks, key=lambda row: row[1]["slept_s"] - row[1]["asked_s"])
        inside = sum(_overlap(a, b, t - args["slept_s"], t) for a, b in gcs)
        text += (f"; longest late park {1e3 * (args['slept_s'] - args['asked_s']):.1f} ms at {t - lo:.1f} s, "
                 + (f"{1e3 * inside:.1f} ms of it inside a full collection" if inside else
                    "no full collection inside it"))
    return text


def read(state, *, what, track="process"):
    events = _events(state)
    if events is None:
        return None
    window = state["run"]["window"]
    lo, hi = window["t_start"], window["t_close"]
    if ("pulse", track) not in state:
        state[("pulse", track)] = True
        print(describe(events, track, lo, hi), file=sys.stderr)
    if what == "late_ms_max":
        return 1e3 * max((args["late_s"] for ev_track, name, _, t, _, args in events
                          if ev_track == track and name == "pulse.late" and lo <= t < hi),
                         default=0.0)
    raise ValueError(f"reader pulse: unknown what={what!r}")
