"""The harness: finds a cell's files by the names in ``BENCHMARK.json``, runs
its job kind once, reduces what the run left (stamps, counters, trace) to the
cell's metrics and prints the one result line.  Nothing here knows a model, a
mix or a metric by name: those are files (see benchmark/README.md)."""

from __future__ import annotations

import gc
import glob
import importlib
import json
import os
import shutil
import sys
import threading
import time
import traceback

import numpy as np

from benchmark import trace_reduce, traffic

def cell_file(root: str, kind: str, name: str) -> str:
    """``benchmark/<kind>/<name>.json`` under the checkout ``root``."""
    path = os.path.join(root, "benchmark", kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}: no file {path} for {name!r}")
    return path


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(root: str, workload: str):
    """(manifest, cell, configuration, mix) of ``workload``, each from its file."""
    manifest = load_manifest(root)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load(cell_file(root, "workloads", workload))
    return manifest, cell, config, mix


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The manifest's metrics of ``group`` that ``workload`` reports."""
    return [m for m in manifest[group] if workload in m.get("workloads", [workload])]


def unix_instant():
    """``(time.time_ns(), time.monotonic())`` read together: the monotonic
    clock is read on both sides of the Unix one, and of three readings the one
    whose two sides lie closest is kept, at their middle (a thread can be put
    off its core between two reads)."""
    reads = [(time.monotonic(), time.time_ns(), time.monotonic()) for _ in range(3)]
    before, unix_ns, after = min(reads, key=lambda r: r[2] - r[0])
    return unix_ns, (before + after) / 2


class Context:
    """What a job kind gets, and the few steps every kind takes the same way."""

    #: With ``--trace 1`` the profiler is on for this long, from this far into the window.
    trace_seconds, trace_lead_s = 8.0, 2.0

    def __init__(self, *, root, cell, config, mix, seed, seconds, trace, devices, t0, fault=None):
        self.root, self.cell, self.config, self.mix = root, cell, config, mix
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.devices, self.t0, self.fault = devices, t0, fault
        self.setup_s = None
        self.traced = None  # trace_reduce.Trace of the traced span
        self.memory_peak_bytes = None
        # Full collections as (start, seconds): they stall every thread of the
        # job, as they would in a deployment; the run says where they fell.
        self.collections = []
        self.oversleep_s = 0.0
        gc.callbacks.append(self._on_collection)

    def _on_collection(self, phase, info) -> None:
        if phase == "start":
            self._collecting_since = time.monotonic()
        elif info["generation"] == 2:
            self.collections.append((self._collecting_since,
                                     time.monotonic() - self._collecting_since))

    def note(self, text: str) -> None:
        print(f"[bench {time.monotonic() - self.t0:8.2f}s] {text}", file=sys.stderr, flush=True)

    def await_window(self, clock, handle, last_arrival=None, timeout=1100.0) -> None:
        """Block until the job's operators are warm and the window is open;
        ``last_arrival()`` is then watched for a sink gone silent."""
        deadline = time.monotonic() + timeout
        while not clock.started.wait(0.25):
            alive = any(st.thread.is_alive() for st in handle.executor.subtasks)
            if not alive or time.monotonic() > deadline:
                handle.wait(timeout=5)  # surfaces the job's own failure
                raise RuntimeError("the job ended or hung before its operators opened")
        self.setup_s = clock.t_start - self.t0
        self._cpu_at_open = time.process_time()
        self.note(f"window open: setup_s {self.setup_s:.3f}")
        if last_arrival is not None:
            threading.Thread(target=self._watch, args=(clock, last_arrival), daemon=True,
                             name="bench-watch").start()

    def _watch(self, clock, last_arrival, silence=1.0, tick=0.1) -> None:
        """Until the window closes, wake every ``tick`` seconds.  Keeps its own
        longest oversleep: a process kept off its cores oversleeps, a program
        that waits does not.  The first time the sink has been silent for
        ``silence`` seconds, notes where every thread of the process stands."""
        last, told = time.monotonic(), False
        while (now := time.monotonic()) < clock.t_close:
            self.oversleep_s = max(self.oversleep_s, now - last - tick)
            if not told and now - max(last_arrival(), clock.t_start) > silence:
                told = True
                names = {t.ident: t.name for t in threading.enumerate()}
                self.note(f"sink silent for {silence} s at {now - clock.t_start:.1f} s; threads:\n" + "\n".join(
                    f"  {names.get(ident, ident)}: " + " <- ".join(
                        f"{f.name}:{f.lineno} {os.path.basename(f.filename)}"
                        for f in reversed(traceback.extract_stack(frame)[-8:]))
                    for ident, frame in sys._current_frames().items()))
            last = now
            time.sleep(tick)

    def trace_window(self, clock) -> None:
        """With ``--trace 1``: profile ``trace_seconds`` of the window, from
        ``trace_lead_s`` in, and reduce the trace."""
        if not self.trace:
            return
        import jax

        out = os.path.join(self.root, "benchmark", "out", f"trace-{os.getpid()}")
        shutil.rmtree(out, ignore_errors=True)
        span = min(self.trace_seconds, max(self.seconds - self.trace_lead_s - 0.5, 0.5))
        time.sleep(max(clock.t_start + min(self.trace_lead_s, self.seconds / 4) - time.monotonic(), 0))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        # Device events only: with the host's tracer on, 7.5 s of this job wrote a
        # 467 MB trace, took 67 s to stop and ran at a tenth of its speed (PERF.md 6).
        options.host_tracer_level = 0
        options.enable_hlo_proto = False
        unix_at = unix_instant()
        t_call = time.monotonic()
        jax.profiler.start_trace(out, profiler_options=options)
        t_on = time.monotonic()
        time.sleep(span)
        t_off = time.monotonic()
        jax.profiler.stop_trace()
        t_stopped = time.monotonic()
        files = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler left no xplane file under {out}")
        size = os.path.getsize(files[0])
        self.traced = trace_reduce.load(files[0])
        self.traced.host_span = (t_on, t_off)
        # What the span join chooses its pairing by: the xplane counts from the
        # profile's start, which lies inside the start_trace call.
        self.traced.start_call, self.traced.unix_at = (t_call, t_on), unix_at
        began = self.traced.profile_start_host()
        self.note(f"traced {t_off - t_on:.2f}s: start_trace {t_on - t_call:.3f}s"
                  + ("" if began is None else f" (the profile began {(began - t_call) * 1e3:.3f} ms into it)")
                  + f", stop_trace {t_stopped - t_off:.2f}s, xplane {size / 1e6:.1f} MB read in "
                  f"{time.monotonic() - t_stopped:.2f}s, {len(self.traced.rows)} events kept")
        shutil.rmtree(out, ignore_errors=True)

    def finish(self, handle, clock, grace=90.0):
        """Wait for the job to drain after the window has closed.  A job that
        does not end is cancelled; what never arrived then counts as failed."""
        time.sleep(max(clock.t_close - time.monotonic(), 0))
        self._cpu_in_window = time.process_time() - self._cpu_at_open
        gc.callbacks.remove(self._on_collection)
        try:
            return handle.wait(timeout=max(clock.t_close - time.monotonic(), 0) + grace)
        except Exception as e:  # noqa: BLE001 - reported through failed/correct
            self.note(f"job did not end cleanly: {type(e).__name__}: {e}")
            handle.cancel()
            return None

    def note_stalls(self, clock, arrival) -> None:
        """Where the window stalled, for whoever has to explain a tail: the
        full collections, the longest silence at the sink, the process's CPU
        time and the watch thread's oversleep (a process kept off its cores by
        a neighbour uses less and oversleeps)."""
        inside = [(t - clock.t_start, s) for t, s in self.collections
                  if clock.t_start <= t < clock.t_close]
        text = f"{len(inside)} full collections in the window, {sum(s for _, s in inside):.3f} s"
        if inside:
            at, longest = max(inside, key=lambda c: c[1])
            text += f", longest {longest:.3f} s at {at:.1f} s"
        timed = np.sort(arrival[(arrival >= clock.t_start) & (arrival < clock.t_close)])
        if len(timed) > 1:
            k = int(np.argmax(np.diff(timed)))
            text += (f"; longest silence at the sink {timed[k + 1] - timed[k]:.3f} s "
                     f"at {timed[k] - clock.t_start:.1f} s")
        self.note(text + f"; process CPU {self._cpu_in_window:.1f} s in {clock.seconds:.0f} s; "
                  f"the watch thread overslept {self.oversleep_s:.3f} s at most")

    def read_device(self) -> None:
        """The peak on the fullest chip.  This runtime books a running program's
        scratch memory under ``bytes_reserved``, not ``bytes_in_use`` (PERF.md
        section 6, step 0), so the peak is the sum of the two peaks."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
        self.memory_peak_bytes = max(peaks)
        self.note(f"memory_stats {self.devices[0].memory_stats()}")


def checked(numbers: dict, limits: dict):
    """[(name, value, limit, ok)] for every limit; a number missing is not ok."""
    rows = []
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        rows.append((name, value, limit, bool(value <= limit)))
    return rows


def result_line(*, correct, attempted, failed, metrics, device, breakdown=None, checks=None) -> str:
    """The contract's last line.  ``checks`` comes last, each number beside its limit."""
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if checks is not None:
        line["checks"] = {name: {"value": value, "limit": limit} for name, value, limit, _ in checks}
    return json.dumps(line)


def run_cell(*, root, workload, seed, seconds, trace, devices, t0, fault=None):
    """One run of one cell on ``devices``; returns the result line's dict parts."""
    manifest, cell, config, mix = load_cell(root, workload)
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{workload} needs {cell['chips']} chips, jax found {len(devices)}")
    devices = list(devices)[:cell["chips"]]
    ctx = Context(root=root, cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
                  trace=trace, devices=devices, t0=t0, fault=fault)
    job = importlib.import_module("benchmark.jobs." + config["job"])
    out = job.run(ctx)

    checks = checked(out["numbers"], config["limits"])
    correct = out["failed"] == 0 and out["attempted"] > 0 and all(ok for *_, ok in checks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    breakdown = None
    if trace:
        state = {"ctx": ctx, "run": out, "cell": cell, "config": config,
                 "peaks": trace_reduce.peaks_for(root, dev.device_kind)}
        metrics = {}
        for m in metrics_of(manifest, "per_layer", workload):
            with open(cell_file(root, "layer_metrics", m["name"])) as f:
                spec = json.load(f)
            reader = importlib.import_module("benchmark.readers." + spec["reader"])
            value = reader.read(state, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx.traced.busy_s()
        device["window_s"] = ctx.traced.window_s
        breakdown = ctx.traced.breakdown()
    else:
        values = dict(out["metrics"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(manifest, "end_to_end", workload)}
    for name, value in out["numbers"].items():
        if name not in config["limits"]:
            print(f"not compared {name}: {value!r}", file=sys.stderr)
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} limit {limit!r} {'ok' if ok else 'NOT OK'}", file=sys.stderr)
    print(f"check failed_records: {out['failed']} of {out['attempted']} limit 0", file=sys.stderr,
          flush=True)
    return dict(correct=correct, attempted=out["attempted"], failed=out["failed"],
                metrics=metrics, device=device, breakdown=breakdown, checks=checks)
