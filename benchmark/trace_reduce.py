"""From a profiler trace to numbers: device busy time, per-op and per-module
device time, exposed collective time, idle gaps by benchmark-level span.

The input is a table of events, ``(plane, line, name, start_ns, duration_ns)``,
read from the ``.xplane.pb`` file with ``jax.profiler.ProfileData`` or from a
recorded table (tests/benchmark/data).  Device planes are ``/device:TPU:<n>``;
their line ``XLA Ops`` holds one event per executed op and ``XLA Modules`` one
per executed program.  The host's tracer stays off (it slows this job tenfold),
so an idle gap is named by where it falls: inside a running program, or between
two programs, where the device waits for the host to send the next one.
"""

from __future__ import annotations

import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ENVIRONMENT_PLANE, START_STAT = "Task Environment", "profile_start_time"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


def read_xplane(path: str):
    """``(rows, profile_start_unix_ns)``: every event of the device planes' op
    and module lines, and the instant the events' ``start_ns`` count from, on
    the Unix clock: the stat ``profile_start_time`` of the plane ``Task
    Environment``, None where the xplane has none."""
    from jax.profiler import ProfileData

    rows, profile_start = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == ENVIRONMENT_PLANE:
            profile_start = next((int(v) for k, v in plane.stats if k == START_STAT), None)
        if not DEVICE_PLANE.search(plane.name):
            continue
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                rows.extend((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events)
    return rows, profile_start


def load(path: str) -> "Trace":
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return Trace([tuple(r) for r in json.load(f)])
    return Trace(*read_xplane(path))


def union_ns(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def short_op(name: str) -> str:
    """``%fusion.26 = bf16[1024,71,71,192]{...} fusion(...)`` -> ``fusion.26 bf16[1024,71,71,192]``."""
    lhs, _, rhs = name.partition(" = ")
    shape = rhs.split("{")[0].split(" ")[0]
    return (lhs.lstrip("%") + (" " + shape if shape else ""))[:96]


class Trace:
    def __init__(self, rows, profile_start_unix_ns=None):
        self.rows = rows
        self.profile_start_unix_ns = profile_start_unix_ns
        self.device_events = {}  # device id -> [(name, start, end)] of ops
        self.module_events = {}  # device id -> [(name, start, end)] of programs
        for plane, line, name, start, dur in rows:
            m = DEVICE_PLANE.search(plane)
            if not m:
                continue
            table = self.device_events if line == OPS_LINE else self.module_events
            table.setdefault(int(m.group(1)), []).append((name, start, start + dur))
        if not self.device_events:
            raise ValueError("the trace holds no device op: nothing ran on the chip while it was on")
        every = [e for evs in self.device_events.values() for e in evs]
        self.window_ns = (min(e[1] for e in every), max(e[2] for e in every))
        # Set by the harness, on the host's monotonic clock: the span the
        # profiler was on, (on, off); the start_trace call's two stamps,
        # (t_call, t_on); one Unix instant in ns and the monotonic instant it
        # was read at.
        self.host_span = self.start_call = self.unix_at = None

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def profile_start_host(self):
        """The instant the events count from, on the host's monotonic clock;
        None where the xplane did not say or no Unix instant was kept."""
        if self.profile_start_unix_ns is None or self.unix_at is None:
            return None
        unix_ns, monotonic = self.unix_at
        return monotonic + (self.profile_start_unix_ns - unix_ns) / 1e9

    def busy_by_device(self) -> dict:
        return {d: union_ns((s, e) for _, s, e in evs) / 1e9
                for d, evs in self.device_events.items()}

    def busy_s(self) -> float:
        """Seconds an op ran on the device, averaged over the devices used."""
        busy = self.busy_by_device()
        return sum(busy.values()) / len(busy)

    def idle_share(self) -> float:
        """Of the fullest-idle device."""
        return 1.0 - min(self.busy_by_device().values()) / self.window_s

    def module_runs(self, pattern: str) -> list:
        """Durations (s) of the runs on one device of the programs whose name
        matches ``pattern``.  A pattern that matches no program the device ran
        raises: a step renamed or split must not change silently what is read."""
        device = min(self.module_events or {None: None})
        events = self.module_events.get(device, [])
        runs = [(e - s) / 1e9 for name, s, e in events if re.search(pattern, name)]
        if not runs:
            raise LookupError(f"no program on the device matches {pattern!r}; it ran "
                              f"{sorted({name.split('(')[0] for name, _, _ in events})}")
        return runs

    def exposed_collective_s(self) -> float:
        """Per device, time inside collective ops during which no other op
        runs there; the worst device."""
        worst = 0.0
        for evs in self.device_events.values():
            coll = [(s, e) for n, s, e in evs if COLLECTIVE.search(n)]
            rest = [(s, e) for n, s, e in evs if not COLLECTIVE.search(n)]
            if not coll:
                continue
            exposed = union_ns(coll + rest) - union_ns(rest)
            worst = max(worst, exposed / 1e9)
        return worst

    def breakdown(self, top: int = 10) -> dict:
        device = min(self.device_events)
        evs = sorted(self.device_events[device], key=lambda e: e[1])
        by_op = {}
        for name, s, e in evs:
            name = short_op(name)
            by_op[name] = by_op.get(name, 0.0) + (e - s) / 1e9
        programs = sorted(self.module_events.get(device, []), key=lambda e: e[1])
        gaps, reach = {}, self.window_ns[0]
        for _, s, e in evs + [("end", self.window_ns[1], self.window_ns[1])]:
            if s > reach:
                label = self._label(programs, reach, s)
                gaps[label] = gaps.get(label, 0.0) + (s - reach) / 1e9
            reach = max(reach, e)
        rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
        return {"device_ops": rank(by_op), "idle_gaps": rank(gaps)}

    @staticmethod
    def _label(programs, lo: int, hi: int) -> str:
        """``inside <program>`` where a run of it covers ``[lo, hi)``, else
        ``host: before <program>`` for the program that the gap waits for."""
        for name, s, e in programs:
            if s <= lo and hi <= e:
                return "inside " + name.split("(")[0]
            if s >= hi:
                return "host: before " + name.split("(")[0]
        return "host: after the last program"


def peaks_for(root: str, device_kind: str) -> dict:
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json: add it with "
                       f"its source, there is no default")
    return table[device_kind]
