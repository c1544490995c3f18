"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Runs the main paths once, in THIS process, through the entry points a
job author calls, on whatever accelerator jax finds — and refuses to run
at all when that is not a TPU:

1. the flagship stream job at full width: Inception-v3 (uint8 299x299
   records) through ``count_window`` -> ``ModelWindowFunction`` -> sink,
   checked record-for-record against one direct ``jax.jit`` call;
2. the serving plane (``serving.continuous_batching`` over the zoo's
   ``char_transformer``) and the pallas flash kernel COMPILED
   (``interpret=False``) against ``parallel.full_attention``;
3. on a host with four or more chips: the stream job at parallelism 4,
   one replica per chip, and three ResNet-50 data-parallel train steps
   on a ``{"data": 4}`` mesh.

Weights are random (seeded); depth and width are the models' own.  Any
failed check raises.  The last line of stdout is ``{"ok": true, "device":
{...}}`` and nothing more; the ``summary:`` line before it has the rest.  The
seconds it prints are set-up observations (compile, first windows), not
speeds: nothing here is a benchmark.

    python chip_smoke.py          # on a TPU machine; non-zero elsewhere
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


def _check(ok, *why) -> None:
    """A check that survives ``python -O``."""
    if not ok:
        raise AssertionError(*why)


def build_native_ring() -> str:
    """Rebuild native/lib from the committed source where a compiler is
    on the path (a failed build raises), then say which ring loads."""
    built = bool(shutil.which("make") and shutil.which(os.environ.get("CXX", "g++")))
    if built:
        subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "native")],
                       check=True, stdout=subprocess.DEVNULL, timeout=300)
    from flink_tensorflow_tpu.native import ring_impl

    impl = ring_impl()
    if built and impl != "native":
        raise RuntimeError("native ring was built but the python ring loaded")
    return impl


def _replica_devices(function_cls, placed: dict):
    """``function_cls`` that records, per subtask, where its runner put
    the parameters, which ring it took and how long open() (parameter
    transfer + warm-up compile) lasted."""
    import jax

    class Spied(function_cls):
        def open(self, ctx):
            t0 = time.monotonic()
            super().open(ctx)
            placed[ctx.subtask_index] = {
                "devices": {
                    d for leaf in jax.tree.leaves(self.runner._params_on_device)
                    for d in leaf.devices()
                },
                "ring_native": None if self._ring is None else self._ring.is_native,
                "open_s": time.monotonic() - t0,
                "opened_at": time.monotonic(),
            }

    return Spied


def phase1_stream(devices, *, records_n=512, batch=128, num_classes=1000,
                  want=None):
    """Inception-v3 stream job, one replica per device in ``devices``.

    Every record must come back exactly once, labels/scores must equal a
    direct jitted ``serve`` call over the same ``batch``-record slices on
    ``devices[0]`` (or ``want``, the ``(labels, scores)`` an earlier
    phase returned), and each replica's parameters must live on its own
    device."""
    import jax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue
    from flink_tensorflow_tpu.utils.profiling import device_memory_stats

    n = len(devices)
    mdef = get_model_def("inception_v3", num_classes=num_classes, uint8_input=True)
    model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
    # Distinct seeded bytes per record; the pool is read-only so the
    # TensorValues share its rows instead of copying them.
    pool = np.random.RandomState(0).randint(
        0, 256, (records_n, 299, 299, 3), dtype=np.uint8)
    pool.setflags(write=False)
    records = [TensorValue({"image": pool[i]}, {"id": i}) for i in range(records_n)]

    placed: dict = {}
    arrivals: list = []
    results: list = []

    def sink(record):
        results.append(record)
        arrivals.append(time.monotonic())

    env = StreamExecutionEnvironment(parallelism=n)
    env.configure(device_provider=lambda task, i: devices[i])
    stream = env.from_collection(records, parallelism=1)
    if n > 1:
        stream = stream.rebalance()
    (
        stream.count_window(batch, timeout_s=30.0)
        .apply(
            _replica_devices(ModelWindowFunction, placed)(
                model,
                policy=BucketPolicy(fixed_batch=batch),
                warmup_batches=(batch,),
                outputs=("label", "score"),
            ),
            name="inception", parallelism=n,
        )
        .sink_to_callable(sink)
    )
    env.execute("chip-smoke-inception", timeout=900)

    ids = [int(r.meta["id"]) for r in results]
    if n == 1:
        _check(ids == list(range(records_n)), "records lost, duplicated or reordered")
    else:
        _check(sorted(ids) == list(range(records_n)), "records lost or duplicated")
    _check(sorted(placed) == list(range(n)), placed)
    for i in range(n):
        _check(placed[i]["devices"] == {devices[i]},
               f"replica {i} params on {placed[i]['devices']}, want {devices[i]}")
    got_labels = np.empty((records_n,), np.int32)
    got_scores = np.empty((records_n,), np.float32)
    for i, r in zip(ids, results):
        got_labels[i] = int(r["label"])
        got_scores[i] = float(r["score"])
    _check(np.isfinite(got_scores).all(), "non-finite scores")

    if want is None:
        # The reference: the same serve method, jitted directly, fed the
        # same slices on the same device — no stream, no runner.
        serve = mdef.methods["serve"].fn
        direct = jax.jit(lambda v, x: {
            k: serve(v, {"image": x})[k] for k in ("label", "score")})
        params = jax.device_put(model.params, devices[0])
        want_labels = np.empty_like(got_labels)
        want_scores = np.empty_like(got_scores)
        for lo in range(0, records_n, batch):
            out = direct(params, jax.device_put(pool[lo:lo + batch], devices[0]))
            want_labels[lo:lo + batch] = np.asarray(out["label"])
            want_scores[lo:lo + batch] = np.asarray(out["score"])
    else:
        want_labels, want_scores = want
    np.testing.assert_array_equal(got_labels, want_labels)
    np.testing.assert_allclose(got_scores, want_scores, rtol=1e-5, atol=1e-7)

    peak = {}
    for d in devices:
        peak[d.id] = device_memory_stats(d).get("peak_bytes_in_use")
        if d.platform == "tpu":
            _check(peak[d.id], f"{d} reports no peak memory")
    return {
        "records": records_n,
        "windows": records_n // batch,
        "outputs": (got_labels, got_scores),
        # How much the label/score comparison can tell apart: random
        # weights may send every record to one class.
        "distinct_labels": int(np.unique(got_labels).size),
        "distinct_scores": int(np.unique(got_scores).size),
        "replica_device_ids": [d.id for d in devices],
        "ring_in_operator": sorted(
            {"native" if p["ring_native"] else "python" for p in placed.values()}),
        # open() = parameter transfer + the warm-up compile of the one
        # batch shape; the windows' wall time starts when the last
        # replica finished opening.  Neither is a speed.
        "warmup_compile_s": round(max(p["open_s"] for p in placed.values()), 2),
        "windows_wall_s": round(
            arrivals[-1] - max(p["opened_at"] for p in placed.values()), 2),
        "peak_bytes_in_use": peak,
    }


def phase2_serving(device, *, max_new_tokens=16, compiled_kernel=True):
    """Eight keyed sessions through ``serving.continuous_batching`` over
    the zoo's char_transformer at its registered width (capacity 128):
    every session must finish with ``max_new_tokens`` tokens.  Half the
    prompts are long enough to prefill at the full ``[8, 128]`` bucket.
    With ``compiled_kernel`` the prefill must lower to a Mosaic call."""
    import jax

    from flink_tensorflow_tpu import StreamExecutionEnvironment, serving
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.sources import PacedSplitSource

    mdef = get_model_def("char_transformer")
    capacity, vocab = mdef.config["capacity"], mdef.config["vocab_size"]
    model = mdef.to_model(mdef.init_params(jax.random.PRNGKey(0)))

    rng = np.random.RandomState(1)
    lengths = [12, 100, 19, 72, 27, 110, 8, 96]
    _check(max(lengths) + max_new_tokens <= capacity, "prompts outgrow capacity")
    requests = [
        serving.GenerateRequest(
            session_id=f"user-{i}",
            prompt=rng.randint(1, vocab, (n,)).astype(np.int32),  # 0 pads
            max_new_tokens=max_new_tokens,
        )
        for i, n in enumerate(lengths)
    ]
    if compiled_kernel:
        # The serving path takes the kernel's backend-keyed default, so
        # prove what that default lowers to here: a Mosaic custom call,
        # not the interpreter's expansion into plain HLO.
        hlo = jax.jit(model.method("prefill").fn).lower(
            jax.device_put(model.params, device),
            {"tokens": np.zeros((8, capacity), np.int32),
             "lengths": np.ones((8,), np.int32)},
        ).as_text()
        _check("tpu_custom_call" in hlo, "prefill did not lower to a Mosaic kernel")

    env = StreamExecutionEnvironment(parallelism=1)
    env.configure(device_provider=lambda task, i: device)
    events = (
        serving.continuous_batching(
            env.from_source(
                PacedSplitSource(requests, rate_hz=50.0, num_splits=4),
                name="sessions", parallelism=1,
            ).key_by(lambda r: r.session_id),
            model,
            config=serving.ServingConfig(
                max_active_seqs=8, token_budget=8 * capacity, capacity=capacity),
            name="continuous_batching", parallelism=1,
        )
        .sink_to_list()
    )
    env.execute("chip-smoke-serving", timeout=600)

    sessions: dict = {}
    for ev in events:
        sessions.setdefault(ev.session_id, {})[ev.index] = int(ev.token)
    _check(sorted(sessions) == sorted(r.session_id for r in requests),
           sorted(sessions))
    for sid, toks in sessions.items():
        _check(sorted(toks) == list(range(max_new_tokens)), sid, sorted(toks))
        _check(all(0 <= t < vocab for t in toks.values()), sid, toks)
    return {"sessions": len(sessions),
            "tokens": sum(len(t) for t in sessions.values()),
            "capacity": capacity}


def phase2_flash(device, *, interpret=False, cell_positions=4096):
    """The pallas flash kernel against ``parallel.full_attention`` at the
    serving prefill shape and at the long-sequence bf16 shape, then at the
    two language-model cells' own shapes (``cell_positions`` long, the tile
    the kernel chooses) against plain attention on the first 1,024 positions
    and on every eighth after, and latent attention's call (operands in
    ``[B, T, H x D]``, one rotary key head) against the plain call on
    concatenated operands: a layout Mosaic takes and miscompiles shows
    here, not in a benchmark run's ``logit_rms_err``.  With
    ``interpret=False`` a Mosaic refusal surfaces here as the compile
    error it is — nothing retries interpreted."""
    import jax
    import jax.numpy as jnp

    from flink_tensorflow_tpu.ops.flash_attention import flash_attention
    from flink_tensorflow_tpu.parallel import full_attention

    checked = []
    rng = np.random.RandomState(5)
    for shape, dtype, causals in (
        ((8, 128, 4, 16), jnp.float32, (True,)),
        ((2, 256, 4, 64), jnp.bfloat16, (False, True)),
    ):
        q, k, v = (jax.device_put(jnp.asarray(rng.randn(*shape), dtype), device)
                   for _ in range(3))
        for causal in causals:
            got, lse = flash_attention(q, k, v, causal=causal,
                                       interpret=interpret, return_lse=True)
            with jax.default_matmul_precision("highest"):
                want = full_attention(q, k, v, causal=causal)
                s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                               k.astype(jnp.float32)) / np.sqrt(shape[-1])
                if causal:
                    t = shape[1]
                    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
                want_lse = jax.nn.logsumexp(s, axis=-1)
            _check(got.shape == shape and got.dtype == dtype, got.shape, got.dtype)
            got32 = np.asarray(got, np.float32)
            _check(np.isfinite(got32).all(), "non-finite attention output")
            # tests/test_ops.py's on-chip tolerances; bf16 outputs may
            # also differ by the one ulp their final rounding can flip.
            rtol = 2.0 ** -7 if dtype == jnp.bfloat16 else 0.0
            np.testing.assert_allclose(got32, np.asarray(want, np.float32),
                                       atol=3e-3, rtol=rtol)
            np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                       atol=1e-4)
            checked.append({"shape": list(shape), "dtype": jnp.dtype(dtype).name,
                            "causal": causal, "interpret": interpret})
    t = cell_positions
    rows = np.unique(np.concatenate([np.arange(min(t, 1024)), np.arange(0, t, 8)]))
    for heads, kv_heads, d in ((20, 4, 128), (32, 8, 64)):  # Falcon-H1, LFM2
        q, k, v = (jax.device_put(jnp.asarray(rng.randn(2, t, h, d), jnp.bfloat16), device)
                   for h in (heads, kv_heads, kv_heads))
        got = flash_attention(q, k, v, causal=True, interpret=interpret)
        _check(got.shape == q.shape and got.dtype == q.dtype, got.shape, got.dtype)
        with jax.default_matmul_precision("highest"):
            k_all, v_all = (jnp.repeat(x[0].astype(jnp.float32), heads // kv_heads, axis=1)
                            for x in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q[0, rows].astype(jnp.float32), k_all) / np.sqrt(d)
            s = jnp.where(jnp.arange(t)[None, :] <= rows[:, None], s, -jnp.inf)
            want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v_all)
        err = np.abs(np.asarray(got[0, rows], np.float32) - np.asarray(want))
        _check(np.isfinite(err).all(), "non-finite attention output")
        # a bfloat16 result of size 1-2 rounds by up to 2**-8 of itself
        _check(err.max() < 1.2e-2, "flash against plain attention", float(err.max()))
        checked.append({"shape": [2, t, heads, d], "kv_heads": kv_heads, "dtype": "bfloat16",
                        "causal": True, "interpret": interpret,
                        "max_err": float(err.max())})
    # Latent attention's call at the Kimi cell's heads (64 of 128 + 64 on values of 128): q_nope, k_nope
    # and v where their products wrote them, q_pe in float32 and turned in the kernel, one rotary key
    # head for all, against the plain call on the concatenated operands.
    from flink_tensorflow_tpu.ops.mla import rope_pairs

    heads, nope, rope = 64, 128, 64
    q_nope, k_nope, v = (jax.device_put(jnp.asarray(rng.randn(2, t, heads, nope), jnp.bfloat16), device)
                         for _ in range(3))
    q_pe = jax.device_put(jnp.asarray(rng.randn(2, t, heads, rope), jnp.float32), device)
    angle = np.arange(t)[:, None] * 10.0 ** -np.linspace(0, 4, rope // 2)[None, :]
    cos, sin = (jax.device_put(jnp.asarray(f(angle), jnp.float32), device) for f in (np.cos, np.sin))
    k_pe = rope_pairs(jax.device_put(jnp.asarray(rng.randn(2, t, rope), jnp.float32), device),
                      cos, sin).astype(jnp.bfloat16)
    got = flash_attention(q_nope, k_nope, v, q_rope=q_pe, k_rope=k_pe, rotate=(cos, sin), causal=True,
                          scale=0.1447, interpret=interpret)
    want = flash_attention(
        jnp.concatenate([q_nope, rope_pairs(q_pe, cos, sin).astype(jnp.bfloat16)], axis=-1),
        jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None], (2, t, heads, rope))], axis=-1),
        v, causal=True, scale=0.1447, interpret=interpret)
    _check(got.shape == v.shape and got.dtype == v.dtype, got.shape, got.dtype)
    want = np.asarray(want, np.float32)
    err = np.abs(np.asarray(got, np.float32) - want)
    _check(np.isfinite(err).all(), "non-finite attention output")
    # The same products in the same order; q_pe is turned by the kernel's own float32 arithmetic, so a
    # rounding of it may fall the other way: a last place of a bfloat16 result, 2**-8 of its size.
    err = err / np.maximum(1.0, np.abs(want))
    _check(err.max() < 1.2e-2, "split entry against the plain call", float(err.max()))
    checked.append({"shape": [2, t, heads, nope], "rope": rope, "dtype": "bfloat16", "causal": True,
                    "interpret": interpret, "max_err": float(err.max()),
                    "differ": int((err > 0).sum()), "of": int(err.size)})
    return {"checked": checked}


def phase3_dp_train(devices, *, global_batch=128, steps=3, resnet_kwargs=None):
    """ResNet-50 data-parallel training through the stream on a
    ``{"data": len(devices)}`` mesh: finite losses, the step counter at
    ``steps``, and every batch sharded over all of ``devices``."""
    import jax
    import optax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import DPTrainWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.parallel import make_mesh
    from flink_tensorflow_tpu.tensors import RecordSchema, TensorValue, spec

    kw = dict(num_classes=1000, image_size=224, uint8_input=True)
    kw.update(resnet_kwargs or {})
    size, classes = kw["image_size"], kw["num_classes"]
    mdef = get_model_def("resnet50", **kw)
    mesh = make_mesh({"data": len(devices)}, devices)
    rng = np.random.RandomState(2)
    n = global_batch * steps
    pool = rng.randint(0, 256, (n, size, size, 3), dtype=np.uint8)
    records = [
        TensorValue({"image": pool[i], "label": np.int32(i % classes)})
        for i in range(n)
    ]
    schema = RecordSchema({"image": spec((size, size, 3), np.uint8),
                           "label": spec((), np.int32)})
    seen = {"batch_device_ids": [], "step": None}

    class Spied(DPTrainWindowFunction):
        def open(self, ctx):
            super().open(ctx)
            step_fn = self._step_fn

            def spy(state, batch):
                shards = batch["image"].addressable_shards
                _check({s.data.shape[0] for s in shards}
                       == {global_batch // len(devices)},
                       [s.data.shape for s in shards])
                seen["batch_device_ids"].append(sorted(s.device.id for s in shards))
                return step_fn(state, batch)

            self._step_fn = spy

        def on_finish(self, out):
            super().on_finish(out)
            seen["step"] = int(jax.device_get(self._state["step"]))

    env = StreamExecutionEnvironment(parallelism=1)
    env.set_mesh(mesh)
    out = (
        env.from_collection(records, parallelism=1, schema=schema)
        .count_window(global_batch)
        .apply(Spied(mdef, optax.adam(1e-3), train_schema=schema,
                     global_batch=global_batch),
               name="dp_train")
        .sink_to_list()
    )
    env.execute("chip-smoke-dp-train", timeout=900)
    losses = [float(r["loss"]) for r in out]
    _check(len(losses) == steps and np.isfinite(losses).all(), losses)
    _check(seen["step"] == steps, seen)
    want_ids = sorted(d.id for d in devices)
    _check(len(set(want_ids)) == len(devices), want_ids)
    _check(seen["batch_device_ids"] == [want_ids] * steps, seen)
    return {"steps": steps, "losses": [round(x, 4) for x in losses],
            "mesh_device_ids": [d.id for d in mesh.devices.flat]}


def result_line(devices) -> str:
    """The last line of stdout, as the driver's check reads it: one JSON
    object with exactly the keys ``ok`` and ``device``, the device as jax
    reports it.  Everything else the run learned goes on the line before."""
    dev = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}})


def main() -> int:
    from flink_tensorflow_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, jax found platform {dev.platform!r} "
            f"({len(devices)} x {dev.device_kind}); nothing was run")

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_before = cache_entries()
    ring = build_native_ring()
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {len(devices)}")
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {importlib.metadata.version('libtpu')}")
    print(f"ring: {ring}  compile cache: {cache_dir} ({cache_before} entries)",
          flush=True)

    phases = {}
    p1 = phase1_stream([dev])
    outputs = p1.pop("outputs")
    _check(p1["ring_in_operator"] == [ring], p1["ring_in_operator"], ring)
    phases["1_inception_stream"] = p1
    print("phase 1 passed:", json.dumps(p1), flush=True)

    p2 = {"serving": phase2_serving(dev), "flash": phase2_flash(dev)}
    phases["2_serving_and_flash"] = p2
    print("phase 2 passed:", json.dumps(p2), flush=True)

    if len(devices) >= 4:
        four = jax.local_devices()[:4]
        p3 = {"inception_parallelism_4": phase1_stream(four, want=outputs)}
        p3["inception_parallelism_4"].pop("outputs")
        p3["resnet50_dp_train"] = phase3_dp_train(four)
        print("phase 3 passed:", json.dumps(p3), flush=True)
    else:
        p3 = {"skipped": f"needs 4 chips, this machine has {len(devices)}"}
        print("phase 3 did not run:", p3["skipped"], flush=True)
    phases["3_four_chips"] = p3

    print("summary:", json.dumps({
        "ring": ring,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_entries()},
        "phases": phases,
        "claim": None,
    }), flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
