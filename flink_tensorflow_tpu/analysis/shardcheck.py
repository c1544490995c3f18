"""flink-tpu-shardcheck — SPMD layout, donation & HBM-budget static
analyzer for the sharded-workload arc.

The plan analyzer (PR 1) stops at the dataflow graph and the sanitizer
(PRs 5/14) at the record plane; neither ever looks INSIDE the jitted
functions where the sharded-serving/training arc lives.  This module
abstract-evaluates every jit unit a captured plan will execute —
``ModelFunction`` methods, ``OnlineTrainFunction``/``DPTrainWindowFunction``
steps, the serving operator's ``DecodeStepRunner`` prefill/decode calls —
under ``jax.eval_shape``/``jax.make_jaxpr`` against a *declared abstract
mesh* (``parallel.abstract_mesh``: shape without devices, so a CPU-only
dev box analyzes a v5e-8 layout it cannot materialize), then walks the
closed jaxprs to derive four verdicts, surfaced with operator/edge
provenance through the existing ``Diagnostic``/lint registry:

- ``shardcheck-collectives`` (INFO) — psum/all-gather/reduce-scatter/
  ppermute counts per jit unit per step, straight from the jaxpr.
- ``shardcheck-reshard`` (WARN; ERROR on device-resident chained edges)
  — an edge whose upstream declares an OUTPUT layout
  (``output_sharding_axes``) that mismatches the downstream's declared
  input sharding forces XLA to insert an implicit reshard per batch; on
  a PR-7 HBM-resident chained edge that reshard defeats the whole
  h2d-elision the chain exists for.
- ``shardcheck-donation`` (WARN) — large batch args not donated through
  the jit boundary (the KV-pool/param-buffer 2x-HBM trap), dead
  donations (donated arg with no shape-matching output to alias), and
  donations defeated by a dtype mismatch between the aliased pair.
- ``shardcheck-partition`` (ERROR) — a sharded dim (batch over
  data x fsdp, param dims over fsdp/tp per :class:`SpecLayout`) that
  does not divide its mesh-axis product: the first pjit call fails (or
  a collective hangs) after the job already started.
- ``shardcheck-hbm-budget`` (ERROR vs ``JobConfig.hbm_budget_bytes``;
  INFO summaries) — params + optimizer state + KV pool + peak
  activation liveness (linear scan over the jaxpr), per device under
  the mesh.  The admission gate of the paged-KV-economy arc.
- ``shardcheck-signatures`` (WARN unbounded / INFO bounded) — the
  static twin of the runtime recompile-churn lints: enumerate the
  compile signatures a plan can present from ``ServingConfig``
  padding-bucket ladders and runner batch/length buckets.

Everything is fail-soft: a jit unit whose abstract evaluation raises
becomes a note on the audit, never a crashed plan analysis.  Front
doors: ``analyze(graph)`` / ``env.validate_plan()`` (the rules register
at import, via analysis/rules.py), the ``flink-tpu-shardcheck`` console
script (JSON report ``flink-tpu-doctor --shardcheck`` folds in), and
``audit_plan()`` for tests/tools.
"""

from __future__ import annotations

import dataclasses
import math
import typing

from flink_tensorflow_tpu.analysis.diagnostics import Severity, edge_name

if typing.TYPE_CHECKING:
    from flink_tensorflow_tpu.analysis.rules import AnalysisContext

#: jaxpr primitives that lower to inter-device collectives (ICI/DCN
#: traffic).  ``lax.psum_scatter`` binds ``reduce_scatter``.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pgather", "all_gather", "all_to_all",
    "ppermute", "reduce_scatter",
})

#: Donation findings only fire for args at least this large — donating
#: a [B] int32 vector buys nothing and the noise would drown the KV-pool
#: and param-buffer traps the checker exists for.
DONATION_MIN_BYTES = 1 << 20


# ---------------------------------------------------------------------------
# SpecLayout — the fsdp x tp parameter-placement convention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Declarative fsdp x tp placement for a jit unit's params + batch.

    The sharded-serving arc's convention (scaling-book style): the batch
    shards over ``data`` (x ``fsdp`` when set), 2-D+ weight matrices
    shard ``(fsdp, tp)`` on their trailing two dims — flipped to
    ``(tp, fsdp)`` for output projections, whose contracting dim is the
    sharded one — and 1-D params (biases, norm scales) replicate.
    Functions/operators opt in by carrying a ``spec_layout`` attribute;
    without one, params are treated as replicated and only the batch
    divides over the declared ``sharding_axes``.
    """

    data_axis: str = "data"
    fsdp_axis: typing.Optional[str] = None
    tp_axis: typing.Optional[str] = None

    #: Param-name hints whose MATMUL places the sharded dim first
    #: (output projections: wo/w2/down_proj/out_proj/lm_head).
    out_proj_hints: typing.Tuple[str, ...] = (
        "wo", "w2", "down", "out", "head")

    def batch_axes(self) -> typing.Tuple[str, ...]:
        return tuple(a for a in (self.data_axis, self.fsdp_axis) if a)

    def param_spec(
        self, path: str, shape: typing.Sequence[int]
    ) -> typing.Tuple[typing.Optional[str], ...]:
        """Mesh axis (or None = replicated) per dim of one param leaf."""
        n = len(shape)
        if n < 2 or (self.fsdp_axis is None and self.tp_axis is None):
            return (None,) * n
        leaf = path.rsplit("/", 1)[-1].lower()
        flipped = any(h in leaf for h in self.out_proj_hints)
        spec: typing.List[typing.Optional[str]] = [None] * n
        first, second = ((self.tp_axis, self.fsdp_axis) if flipped
                         else (self.fsdp_axis, self.tp_axis))
        spec[-2], spec[-1] = first, second
        return tuple(spec)


# ---------------------------------------------------------------------------
# Audit data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Finding:
    """One shardcheck verdict, pre-shaped for the Diagnostic plumbing."""

    rule: str
    severity: Severity
    message: str
    node: typing.Optional[str] = None
    edge: typing.Optional[str] = None

    def to_json(self) -> dict:
        return {"rule": self.rule, "severity": self.severity.name,
                "message": self.message, "node": self.node, "edge": self.edge}


@dataclasses.dataclass
class OpAudit:
    """Everything shardcheck derived about one operator's jit unit(s)."""

    node: str
    kind: str  # model | train | serving
    #: primitive name -> occurrences per step, summed over jit units.
    collectives: typing.Dict[str, int] = dataclasses.field(default_factory=dict)
    #: per-device byte breakdown: params / optimizer / kv_pool / activations.
    hbm: typing.Dict[str, int] = dataclasses.field(default_factory=dict)
    #: bounded compile-signature count (None = unbounded/unknown).
    signatures: typing.Optional[int] = None
    #: predicted steady-state h2d bytes per decode step (serving only) —
    #: the static twin of DecodeStepRunner.step_h2d_bytes accounting.
    predicted_step_h2d_bytes: typing.Optional[int] = None
    #: why parts of the audit were skipped (fail-soft provenance).
    notes: typing.List[str] = dataclasses.field(default_factory=list)

    @property
    def hbm_total(self) -> int:
        return sum(self.hbm.values())

    def to_json(self) -> dict:
        return {
            "node": self.node, "kind": self.kind,
            "collectives": dict(self.collectives),
            "hbm_per_device_bytes": dict(self.hbm),
            "hbm_per_device_total": self.hbm_total,
            "signatures": self.signatures,
            "predicted_step_h2d_bytes": self.predicted_step_h2d_bytes,
            "notes": list(self.notes),
        }


@dataclasses.dataclass
class PlanAudit:
    """The full shardcheck result for one captured plan."""

    findings: typing.List[Finding]
    ops: typing.List[OpAudit]
    mesh_axes: typing.Optional[typing.Dict[str, int]]
    hbm_budget_bytes: typing.Optional[int]

    def op(self, node: str) -> typing.Optional[OpAudit]:
        for a in self.ops:
            if a.node == node:
                return a
        return None

    @property
    def total_hbm_per_device(self) -> int:
        return sum(a.hbm_total for a in self.ops)

    def to_json(self) -> dict:
        return {
            "mesh_axes": self.mesh_axes,
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "hbm_per_device_total": self.total_hbm_per_device,
            "operators": [a.to_json() for a in self.ops],
            "findings": [f.to_json() for f in self.findings],
        }


# ---------------------------------------------------------------------------
# jaxpr walkers
# ---------------------------------------------------------------------------


def _as_jaxprs(val) -> typing.Iterator:
    """Yield every (open) Jaxpr inside one eqn-param value."""
    if hasattr(val, "jaxpr") and hasattr(val, "consts"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns") and hasattr(val, "invars"):  # Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _as_jaxprs(v)


def _iter_levels(jaxpr) -> typing.Iterator:
    """``jaxpr`` plus every nested jaxpr (pjit/scan/cond/custom calls),
    each yielded as its own level — var namespaces do not mix across
    levels, so liveness scans one level at a time."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for sub in _as_jaxprs(val):
                yield from _iter_levels(sub)


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(math.prod(shape)) * int(dtype.itemsize)
    except TypeError:  # symbolic dims — not a concrete byte count
        return 0


def count_collectives(closed) -> typing.Dict[str, int]:
    """primitive name -> occurrences across every level of ``closed``.
    Under ``shard_map(check_vma=True)`` psum and all_gather bind their
    ``*_invariant`` twins; the census counts both under the plain name."""
    counts: typing.Dict[str, int] = {}
    for level in _iter_levels(closed.jaxpr):
        for eqn in level.eqns:
            name = eqn.primitive.name.removesuffix("_invariant")
            if name in COLLECTIVE_PRIMS:
                counts[name] = counts.get(name, 0) + 1
    return counts


def _level_peak_bytes(jaxpr) -> int:
    """Peak simultaneously-live intermediate bytes at one jaxpr level,
    by linear scan: a var goes live at its defining eqn and dies after
    its last use (jaxpr outvars live to the end).  Inputs/consts are
    excluded — params and batch buffers are budgeted separately."""
    last: typing.Dict[typing.Any, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "count"):  # Var (Literals carry no liveness)
                last[v] = i
    for v in jaxpr.outvars:
        if hasattr(v, "count"):
            last[v] = n
    live = peak = 0
    alive: typing.Dict[typing.Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if type(v).__name__ == "DropVar":
                continue
            b = _aval_bytes(v)
            alive[v] = b
            live += b
        if live > peak:
            peak = live
        for v in [v for v, _ in alive.items() if last.get(v, -1) <= i]:
            live -= alive.pop(v)
    return peak


def peak_activation_bytes(closed) -> int:
    """Max per-level liveness peak across the whole closed jaxpr — a
    static stand-in for XLA's temp-buffer high-water mark (XLA fuses and
    rematerializes, so this is an upper-ish bound, not an exact figure)."""
    return max((_level_peak_bytes(level)
                for level in _iter_levels(closed.jaxpr)), default=0)


# ---------------------------------------------------------------------------
# per-device placement math
# ---------------------------------------------------------------------------


def _param_paths(params) -> typing.List[typing.Tuple[str, typing.Any]]:
    """(slash path, leaf) pairs for a params pytree."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        parts = []
        for entry in path:
            key = getattr(entry, "key", None)
            if key is None:
                key = getattr(entry, "idx", None)
            if key is None:
                key = getattr(entry, "name", None)
            parts.append(str(key) if key is not None else "?")
        out.append(("/".join(parts) or "param", leaf))
    return out


def _leaf_shape_dtype(leaf):
    import numpy as np

    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None:
        arr = np.asarray(leaf)
        shape, dtype = arr.shape, arr.dtype
    return tuple(shape), np.dtype(dtype)


def _params_per_device(
    params, layout: SpecLayout,
    mesh_axes: typing.Optional[typing.Dict[str, int]],
    node: str, what: str,
    findings: typing.List[Finding],
) -> int:
    """Per-device bytes of a params pytree under ``layout``, emitting
    ``shardcheck-partition`` findings for indivisible sharded dims —
    each names the offending buffer and axis."""
    total = 0
    for path, leaf in _param_paths(params):
        shape, dtype = _leaf_shape_dtype(leaf)
        nbytes = int(math.prod(shape)) * dtype.itemsize if shape else dtype.itemsize
        divide = 1
        if mesh_axes:
            for dim, axis in enumerate(layout.param_spec(path, shape)):
                size = mesh_axes.get(axis, 1) if axis else 1
                if size <= 1:
                    continue
                if shape[dim] % size:
                    findings.append(Finding(
                        rule="shardcheck-partition", severity=Severity.ERROR,
                        message=(
                            f"{what} buffer {path!r} dim {dim} "
                            f"({shape[dim]}) does not divide mesh axis "
                            f"{axis!r} ({size}) — the pjit sharding is "
                            "ragged and the first call fails after the job "
                            "started; pad the dim or resize the axis"),
                        node=node))
                else:
                    divide *= size
        total += nbytes // divide
    return total


def _batch_axes_product(
    batch_axes: typing.Sequence[str],
    mesh_axes: typing.Optional[typing.Dict[str, int]],
) -> int:
    if not mesh_axes:
        return 1
    return math.prod(mesh_axes.get(a, 1) for a in batch_axes) or 1


def _check_batch_partition(
    batch: typing.Optional[int], batch_axes: typing.Sequence[str],
    mesh_axes: typing.Optional[typing.Dict[str, int]],
    node: str, findings: typing.List[Finding],
) -> int:
    """Divisibility of the batch dim over its sharding axes; returns the
    per-device divisor (1 when unsharded or indivisible)."""
    prod = _batch_axes_product(batch_axes, mesh_axes)
    if prod <= 1 or batch is None:
        return max(prod, 1)
    if batch % prod:
        findings.append(Finding(
            rule="shardcheck-partition", severity=Severity.ERROR,
            message=(
                f"batch {batch} does not divide the sharded batch axes' "
                f"device product ({'x'.join(batch_axes)} = {prod}) — "
                "per-device shards would be ragged; pick a multiple"),
            node=node))
        return 1
    return prod


# ---------------------------------------------------------------------------
# jit-unit audits
# ---------------------------------------------------------------------------


def _struct_of(pytree):
    """ShapeDtypeStruct mirror of a pytree (device-free trace input)."""
    import jax

    def conv(leaf):
        shape, dtype = _leaf_shape_dtype(leaf)
        return jax.ShapeDtypeStruct(shape, dtype)

    return jax.tree.map(conv, pytree)


def _donation_findings(
    *, donate: bool, inputs: typing.Dict[str, typing.Any],
    outputs: typing.Dict[str, typing.Any],
    node: str, where: str,
) -> typing.List[Finding]:
    """Donation verdicts for one jit unit's batch-input leaves.

    ``inputs``/``outputs`` are name -> ShapeDtypeStruct.  A donated
    input needs a shape+dtype-matching output for XLA to alias its HBM
    pages into; without donation, any such large pair holds both
    buffers live across the call — the 2x-HBM trap."""
    import numpy as np

    findings: typing.List[Finding] = []
    out_list = [(n, tuple(s.shape), np.dtype(s.dtype))
                for n, s in outputs.items()]
    for name, s in inputs.items():
        shape, dtype = tuple(s.shape), np.dtype(s.dtype)
        nbytes = int(math.prod(shape)) * dtype.itemsize if shape else dtype.itemsize
        if nbytes < DONATION_MIN_BYTES:
            continue
        exact = [o for o, osh, odt in out_list if osh == shape and odt == dtype]
        shape_only = [(o, odt) for o, osh, odt in out_list
                      if osh == shape and odt != dtype]
        mib = nbytes / 2**20
        if not donate and exact:
            findings.append(Finding(
                rule="shardcheck-donation", severity=Severity.WARN,
                message=(
                    f"{where}: arg {name!r} ({mib:.1f} MiB) has a shape/"
                    f"dtype-matching output ({exact[0]!r}) but is NOT "
                    "donated — both buffers stay live across the jitted "
                    "call (2x HBM); pass donate_inputs=True so XLA "
                    "aliases the pages"),
                node=node))
        elif donate and not exact and shape_only:
            o, odt = shape_only[0]
            findings.append(Finding(
                rule="shardcheck-donation", severity=Severity.WARN,
                message=(
                    f"{where}: donated arg {name!r} ({mib:.1f} MiB, "
                    f"{dtype}) is DEFEATED by a dtype mismatch — the "
                    f"shape-matching output {o!r} is {odt}, so XLA cannot "
                    "alias the buffer and silently keeps both; align the "
                    "dtypes to make the donation real"),
                node=node))
        elif donate and not exact:
            findings.append(Finding(
                rule="shardcheck-donation", severity=Severity.WARN,
                message=(
                    f"{where}: donated arg {name!r} ({mib:.1f} MiB) has "
                    "no shape-matching output to alias — the donation is "
                    "dead (XLA frees the buffer but reuses nothing); drop "
                    "donate_inputs or return an updated buffer"),
                node=node))
    return findings


def _signature_count(
    function, in_schema, node: str, findings: typing.List[Finding],
) -> typing.Optional[int]:
    """Bounded compile-signature count for a bucket-policied jit
    boundary, or None (unbounded) with a WARN."""
    policy = None
    hook = getattr(function, "plan_policy", None)
    if hook is not None:
        policy = hook()
    else:
        policy = getattr(function, "_policy", None)
    if policy is None:
        findings.append(Finding(
            rule="shardcheck-signatures", severity=Severity.WARN,
            message=("jit boundary has no bucket policy — every distinct "
                     "batch size compiles a fresh executable (unbounded "
                     "signature set); set a BucketPolicy"),
            node=node))
        return None
    if policy.fixed_batch is not None:
        batches = 1
    else:
        batches = len(getattr(policy.batch, "sizes", ()) or ()) or None
    if batches is None:
        findings.append(Finding(
            rule="shardcheck-signatures", severity=Severity.WARN,
            message=("batch bucket ladder is empty — the signature set is "
                     "unbounded; give the BucketPolicy a batch ladder"),
            node=node))
        return None
    dynamic = in_schema is not None and not in_schema.is_static
    if not dynamic:
        return batches
    lengths = len(getattr(policy.lengths, "sizes", ()) or ())
    if not lengths:
        findings.append(Finding(
            rule="shardcheck-signatures", severity=Severity.WARN,
            message=("dynamic input dims with no length ladder — every "
                     "observed length compiles a fresh executable "
                     "(unbounded signature set); set BucketPolicy.lengths"),
            node=node))
        return None
    return batches * lengths


def _audit_model_function(
    t, function, in_schema,
    layout: SpecLayout,
    mesh_axes: typing.Optional[typing.Dict[str, int]],
    findings: typing.List[Finding],
) -> OpAudit:
    from flink_tensorflow_tpu.analysis.chaining import sharding_axes_of
    from flink_tensorflow_tpu.models.base import Model

    audit = OpAudit(node=t.name, kind="model")
    source = getattr(function, "_source", None)
    schema = function.plan_input_schema() or in_schema
    audit.signatures = _signature_count(function, schema, t.name, findings)
    if not isinstance(source, Model):
        audit.notes.append("lazy model source — jit unit not traceable at "
                           "plan time (pass a resolved Model to analyze)")
        return audit
    try:
        method = source.method(function._method_name)
    except KeyError as ex:
        audit.notes.append(f"model method unresolvable: {ex}")
        return audit
    if schema is None:
        audit.notes.append("input schema unknown — jit unit skipped")
        return audit
    policy = function.plan_policy()
    sizes = getattr(policy.batch, "sizes", ()) or ()
    batch = policy.fixed_batch or (sizes[-1] if sizes else 1)
    axes = sharding_axes_of(function) or ()
    div = _check_batch_partition(batch, axes, mesh_axes, t.name, findings)
    audit.hbm["params"] = _params_per_device(
        source.params, layout, mesh_axes, t.name, "param", findings)
    if method.needs_lengths:
        audit.notes.append("method takes per-record lengths — abstract "
                           "trace skipped (no schema slot to trace from)")
        return audit
    try:
        import jax

        struct = schema.batched_struct(
            batch, length_bucket=function._warmup_length_bucket)
        params_struct = _struct_of(source.params)
        closed = jax.make_jaxpr(
            lambda p, x: method.fn(p, x))(params_struct, struct)
        outputs = jax.eval_shape(
            lambda p, x: method.fn(p, x), params_struct, struct)
        audit.collectives = count_collectives(closed)
        batch_bytes = sum(
            int(math.prod(s.shape)) * s.dtype.itemsize for s in struct.values())
        audit.hbm["activations"] = (
            peak_activation_bytes(closed) + batch_bytes) // div
        findings.extend(_donation_findings(
            donate=bool(getattr(function, "_donate", False)),
            inputs=struct,
            outputs={k: v for k, v in outputs.items()
                     if hasattr(v, "shape")},
            node=t.name, where=f"method {function._method_name!r}"))
    except Exception as ex:  # noqa: BLE001 - fail-soft by contract
        audit.notes.append(f"abstract trace failed: {ex!r}")
    return audit


def _audit_serving_operator(
    t, op,
    layout: SpecLayout,
    mesh_axes: typing.Optional[typing.Dict[str, int]],
    findings: typing.List[Finding],
) -> OpAudit:
    import numpy as np

    audit = OpAudit(node=t.name, kind="serving")
    cfg = op.serving_config
    sigs = cfg.compile_signatures()
    if sigs is None:
        findings.append(Finding(
            rule="shardcheck-signatures", severity=Severity.WARN,
            message=(
                "padding_buckets=False makes the serving signature set "
                "unbounded — every distinct active-set size compiles a "
                "fresh decode executable and every distinct prompt length "
                "a fresh prefill; enable padding_buckets"),
            node=t.name))
    else:
        audit.signatures = len(sigs)
    model = op.model
    audit.hbm["params"] = _params_per_device(
        model.params, layout, mesh_axes, t.name, "param", findings)
    try:
        import jax

        from flink_tensorflow_tpu.functions.runner import _build_decode_calls

        prefill = model.method("prefill")
        decode = model.method("decode_step")
        S, C = cfg.max_active_seqs, cfg.capacity
        B = cfg.bucket_admit(S)
        T = min(cfg.bucket_prompt_len(C), C)
        params_struct = _struct_of(model.params)
        tok = jax.ShapeDtypeStruct((B, T), np.int32)
        lens = jax.ShapeDtypeStruct((B,), np.int32)
        pf_out = jax.eval_shape(
            lambda p, tk, ln: prefill.fn(p, {"tokens": tk, "lengths": ln}),
            params_struct, tok, lens)
        k_like = pf_out["k_cache"]  # [B, L, T, H, Dh]
        _, layers, _, heads, hd = k_like.shape
        pool_dtype = np.dtype(k_like.dtype)
        paged = bool(getattr(cfg, "paged_kv", False))
        if paged:
            from flink_tensorflow_tpu.ops.paged_attention import (
                pages_per_session,
            )

            # The paged HBM budget is the PAGE pool, not seats x
            # capacity — oversubscription is the whole economy; the
            # overflow lives in the host/disk tiers, not in HBM.
            Pc = pages_per_session(C, cfg.page_tokens)
            P = cfg.resolved_hbm_pages()
            pool_shape = (P, layers, cfg.page_tokens, heads, hd)
        else:
            pool_shape = (S, layers, C, heads, hd)
        pool_bytes = 2 * int(math.prod(pool_shape)) * pool_dtype.itemsize
        pool_div = 1
        if mesh_axes and layout.tp_axis:
            tp = mesh_axes.get(layout.tp_axis, 1)
            if tp > 1:
                if heads % tp:
                    findings.append(Finding(
                        rule="shardcheck-partition", severity=Severity.ERROR,
                        message=(
                            f"KV pool buffer 'k_cache' heads dim ({heads}) "
                            f"does not divide mesh axis "
                            f"{layout.tp_axis!r} ({tp}) — the pool "
                            "sharding is ragged; pad heads or resize the "
                            "axis"),
                        node=t.name))
                else:
                    pool_div = tp
        audit.hbm["kv_pool"] = pool_bytes // pool_div
        # The runtime jit units, verbatim (module-level lru_cache: the
        # live runner will reuse these callables and executables).
        kc = jax.ShapeDtypeStruct(pool_shape, pool_dtype)
        s_tok = jax.ShapeDtypeStruct((S,), np.int32)
        s_len = jax.ShapeDtypeStruct((S,), np.int32)
        if paged:
            from flink_tensorflow_tpu.functions.runner import (
                _build_paged_calls,
            )

            prefill_into, step_full, _ = _build_paged_calls(
                prefill.fn, decode.fn, C, cfg.page_tokens, P)
            pf_tables = jax.ShapeDtypeStruct((B, Pc), np.int32)
            st_tables = jax.ShapeDtypeStruct((S, Pc), np.int32)
            pf_closed = jax.make_jaxpr(prefill_into)(
                params_struct, tok, lens, pf_tables, kc, kc)
            st_closed = jax.make_jaxpr(step_full)(
                params_struct, s_tok, s_len, st_tables, kc, kc)
            st_args = (params_struct, s_tok, s_len, st_tables, kc, kc)
        else:
            prefill_into, step_full, _ = _build_decode_calls(
                prefill.fn, decode.fn, C)
            slots = jax.ShapeDtypeStruct((B,), np.int32)
            mask = jax.ShapeDtypeStruct((S,), np.bool_)
            pf_closed = jax.make_jaxpr(prefill_into)(
                params_struct, tok, lens, slots, kc, kc)
            st_closed = jax.make_jaxpr(step_full)(
                params_struct, s_tok, s_len, mask, kc, kc)
            st_args = (params_struct, s_tok, s_len, mask, kc, kc)
        for closed in (pf_closed, st_closed):
            for name, n in count_collectives(closed).items():
                audit.collectives[name] = audit.collectives.get(name, 0) + n
        audit.hbm["activations"] = max(
            peak_activation_bytes(pf_closed), peak_activation_bytes(st_closed))
        # Donation by construction: the runner jits with
        # donate_argnums=(4, 5) (kc, vc) and step_full's jnp.where keeps
        # the pool shape — so the only way to lose the aliasing is a
        # dtype drift between the model's decode cache and the pool.
        step_out = jax.eval_shape(step_full, *st_args)
        out_k = step_out[1]
        if np.dtype(out_k.dtype) != pool_dtype or tuple(out_k.shape) != pool_shape:
            findings.append(Finding(
                rule="shardcheck-donation", severity=Severity.WARN,
                message=(
                    f"decode step: donated KV pool 'k_cache' "
                    f"({pool_dtype}, {pool_shape}) is DEFEATED — the step "
                    f"returns {np.dtype(out_k.dtype)} {tuple(out_k.shape)}, "
                    "so XLA cannot alias the pool pages and keeps both "
                    "copies (2x HBM); align the model's cache dtype"),
                node=t.name))
        # Predicted steady-state per-step h2d bytes — must mirror
        # DecodeStepRunner.decode_step's accounting exactly
        # (tests/test_roofline.py joins it against the runtime's
        # measured bytes): padding_buckets on ships
        # [S] int32 tokens + [S] int32 lengths + [S] bool mask; the
        # paged runner ships the [S, C/page_tokens] int32 block tables
        # instead of the mask (liveness rides the sentinel page id).
        if paged:
            audit.predicted_step_h2d_bytes = S * 4 + S * 4 + S * Pc * 4
        elif cfg.padding_buckets:
            audit.predicted_step_h2d_bytes = S * 4 + S * 4 + S * 1
        else:
            audit.predicted_step_h2d_bytes = None  # exact mode: varies
    except Exception as ex:  # noqa: BLE001 - fail-soft by contract
        audit.notes.append(f"abstract trace failed: {ex!r}")
    return audit


def _audit_train_function(
    t, function,
    layout: SpecLayout,
    mesh_axes: typing.Optional[typing.Dict[str, int]],
    findings: typing.List[Finding],
) -> OpAudit:
    import numpy as np

    from flink_tensorflow_tpu.analysis.chaining import sharding_axes_of

    audit = OpAudit(node=t.name, kind="train")
    batch = (getattr(function, "global_batch", None)
             or getattr(function, "mini_batch", None) or 1)
    schema = function.train_schema
    audit.signatures = _signature_count(function, schema, t.name, findings)
    axes = sharding_axes_of(function) or ()
    div = _check_batch_partition(batch, axes, mesh_axes, t.name, findings)
    try:
        import jax

        import optax
        from flink_tensorflow_tpu.parallel.dp import (
            init_train_state,
            make_train_step,
        )

        optimizer = function.optimizer or optax.sgd(0.01)
        state = jax.eval_shape(
            lambda: init_train_state(function.model_def, optimizer,
                                     jax.random.PRNGKey(0)))
        audit.hbm["params"] = _params_per_device(
            state["variables"], layout, mesh_axes, t.name, "param", findings)
        audit.hbm["optimizer"] = _params_per_device(
            state["opt_state"], layout, mesh_axes, t.name, "optimizer-state",
            findings)
        # The train batch contract of _train_batch_arrays: schema fields
        # at [B, ...] (+ <field>_len int32 for dynamic fields) + a [B]
        # f32 valid mask.
        shapes = schema.resolve_dynamic(
            getattr(function, "_warmup_length_bucket", 128))
        struct = {
            name: jax.ShapeDtypeStruct((batch, *shapes[name]),
                                       schema[name].dtype)
            for name in schema.names
        }
        for name in schema.names:
            if not schema[name].is_static:
                struct[f"{name}_len"] = jax.ShapeDtypeStruct(
                    (batch,), np.int32)
        struct["valid"] = jax.ShapeDtypeStruct((batch,), np.float32)
        step = make_train_step(function.model_def, optimizer)
        closed = jax.make_jaxpr(step)(state, struct)
        audit.collectives = count_collectives(closed)
        batch_bytes = sum(
            int(math.prod(s.shape)) * s.dtype.itemsize for s in struct.values())
        audit.hbm["activations"] = (
            peak_activation_bytes(closed) + batch_bytes) // div
        if getattr(function, "is_gang", False) and mesh_axes and len(
                [a for a, s in mesh_axes.items() if s > 1]) > 0:
            audit.notes.append(
                "gang step traced single-device (make_train_step); the DP "
                "psum over the grads is inserted by pjit at run time and "
                "is not in this count")
    except Exception as ex:  # noqa: BLE001 - fail-soft by contract
        audit.notes.append(f"abstract trace failed: {ex!r}")
    return audit


# ---------------------------------------------------------------------------
# the plan walk
# ---------------------------------------------------------------------------


def _layout_of(op, function) -> SpecLayout:
    for holder in (function, op):
        layout = getattr(holder, "spec_layout", None)
        if layout is not None:
            return layout
    return SpecLayout()


def _reshard_findings(
    ctx: "AnalysisContext", findings: typing.List[Finding],
) -> None:
    """Edge-level implicit-reshard audit: upstream declared OUTPUT layout
    vs downstream declared input sharding, escalated to ERROR on
    HBM-resident chained edges (where the reshard defeats the h2d
    elision the chain exists for)."""
    from flink_tensorflow_tpu.analysis.chaining import (
        compute_chains,
        sharding_axes_of,
    )

    plan = compute_chains(ctx.graph, operators=ctx.operators)
    resident_on = ctx.config is None or getattr(
        ctx.config, "device_resident", False)
    for t in ctx.order:
        down_fn = ctx.function_of(t)
        down_in = sharding_axes_of(down_fn)
        if down_in is None:
            continue
        for e in t.inputs:
            up_fn = ctx.function_of(e.upstream)
            if up_fn is None:
                continue
            up_out = getattr(up_fn, "output_sharding_axes", None)
            if up_out is None:
                up_out = sharding_axes_of(up_fn)
            if up_out is None or tuple(up_out) == tuple(down_in):
                continue
            resident = (resident_on
                        and (e.upstream.id, t.id) in plan.device_resident_edges)
            findings.append(Finding(
                rule="shardcheck-reshard",
                severity=Severity.ERROR if resident else Severity.WARN,
                message=(
                    f"upstream emits batches laid out over axes "
                    f"{tuple(up_out)} but this operator's pjit expects "
                    f"{tuple(down_in)} — XLA inserts an implicit reshard "
                    "(all-to-all traffic) on EVERY batch crossing this edge"
                    + ("; the edge is an HBM-resident chained hop, so the "
                       "reshard defeats the h2d elision the chain exists "
                       "for — align the layouts or cut the chain"
                       if resident else
                       "; align the upstream output_sharding_axes with the "
                       "consumer (or reshard once, upstream)")),
                node=t.name, edge=edge_name(e.upstream.name, t.name)))


def audit_plan(ctx: "AnalysisContext") -> PlanAudit:
    """Run the full shardcheck pass over an analysis context."""
    config = ctx.config
    mesh = getattr(config, "mesh", None) if config is not None else None
    mesh_axes = dict(mesh.shape) if mesh is not None else None
    budget = (getattr(config, "hbm_budget_bytes", None)
              if config is not None else None)
    findings: typing.List[Finding] = []
    ops: typing.List[OpAudit] = []
    for t in ctx.order:
        op = ctx.operators.get(t.id)
        if op is None:
            continue
        function = getattr(op, "function", None)
        layout = _layout_of(op, function)
        if getattr(op, "is_continuous_batching", False):
            ops.append(_audit_serving_operator(
                t, op, layout, mesh_axes, findings))
        elif hasattr(function, "model_def") and hasattr(function, "train_schema"):
            ops.append(_audit_train_function(
                t, function, layout, mesh_axes, findings))
        elif getattr(function, "is_jit_boundary", False) and hasattr(
                function, "plan_input_schema"):
            ops.append(_audit_model_function(
                t, function, ctx.input_schema(t), layout, mesh_axes, findings))
    _reshard_findings(ctx, findings)
    # Collective census: one INFO per jit unit that emits any.
    for a in ops:
        if a.collectives:
            census = ", ".join(f"{n}x{c}" for c, n in sorted(
                ((v, k) for k, v in a.collectives.items()), reverse=True))
            findings.append(Finding(
                rule="shardcheck-collectives", severity=Severity.INFO,
                message=f"per-step collectives in the jitted unit: {census}",
                node=a.node))
    # HBM budget: ERROR per over-budget operator, INFO summaries when a
    # mesh or budget was declared (silent otherwise — no declared target
    # means nothing to gate and the numbers would be noise).
    if budget is not None or mesh_axes is not None:
        for a in ops:
            if not a.hbm:
                continue
            breakdown = ", ".join(
                f"{k}={v / 2**20:.1f}MiB" for k, v in sorted(a.hbm.items()))
            total = a.hbm_total
            if budget is not None and total > budget:
                findings.append(Finding(
                    rule="shardcheck-hbm-budget", severity=Severity.ERROR,
                    message=(
                        f"static per-device HBM {total / 2**20:.1f} MiB "
                        f"exceeds hbm_budget_bytes "
                        f"({budget / 2**20:.1f} MiB): {breakdown} — shard "
                        "further (fsdp/tp), shrink the KV pool "
                        "(max_active_seqs/capacity), or raise the budget"),
                    node=a.node))
            else:
                findings.append(Finding(
                    rule="shardcheck-hbm-budget", severity=Severity.INFO,
                    message=(f"static per-device HBM "
                             f"{total / 2**20:.1f} MiB: {breakdown}"),
                    node=a.node))
        if budget is not None and len(ops) > 1:
            plan_total = sum(a.hbm_total for a in ops)
            findings.append(Finding(
                rule="shardcheck-hbm-budget",
                severity=(Severity.ERROR if plan_total > budget
                          else Severity.INFO),
                message=(
                    f"plan-total static per-device HBM "
                    f"{plan_total / 2**20:.1f} MiB vs budget "
                    f"{budget / 2**20:.1f} MiB (all jit units co-resident "
                    "on one device in the single-device placement)")))
    # Bounded-signature census (the unbounded WARNs were emitted inline).
    for a in ops:
        if a.signatures is not None:
            findings.append(Finding(
                rule="shardcheck-signatures", severity=Severity.INFO,
                message=(f"compile-signature set is bounded: "
                         f"{a.signatures} signature(s)"),
                node=a.node))
    return PlanAudit(findings=findings, ops=ops, mesh_axes=mesh_axes,
                     hbm_budget_bytes=budget)


def audit_of(ctx: "AnalysisContext") -> PlanAudit:
    """The per-context cached audit — six registered rules (and the
    CLI/report path) share ONE abstract-evaluation pass."""
    cached = ctx.__dict__.get("_shardcheck_audit")
    if cached is None:
        cached = audit_plan(ctx)
        ctx.__dict__["_shardcheck_audit"] = cached
    return cached


# ---------------------------------------------------------------------------
# lint registry wiring — each verdict family is its own rule id, reading
# the shared cached audit.  Registration happens via the bottom import
# in analysis/rules.py, so analyze()/validate_plan()/every CLI carries
# these without extra wiring.
# ---------------------------------------------------------------------------


def _emit_family(ctx, emit, rule_id: str) -> None:
    for f in audit_of(ctx).findings:
        if f.rule == rule_id:
            emit(f.message, node=f.node, edge=f.edge, severity=f.severity)


def _register_rules() -> None:
    from flink_tensorflow_tpu.analysis.rules import rule

    @rule("shardcheck-collectives", Severity.INFO)
    def _shardcheck_collectives(ctx, emit) -> None:
        """Collective census per jit unit: psum/all-gather/reduce-scatter/
        ppermute counts straight from the closed jaxpr — the per-step
        ICI/DCN bill the sharded arc pays, visible before any run."""
        _emit_family(ctx, emit, "shardcheck-collectives")

    @rule("shardcheck-reshard", Severity.WARN)
    def _shardcheck_reshard(ctx, emit) -> None:
        """Implicit-reshard audit: an edge whose upstream output layout
        mismatches the downstream pjit's declared input sharding makes
        XLA reshard EVERY batch; ERROR when the edge is an HBM-resident
        chained hop (the reshard defeats the h2d elision)."""
        _emit_family(ctx, emit, "shardcheck-reshard")

    @rule("shardcheck-donation", Severity.WARN)
    def _shardcheck_donation(ctx, emit) -> None:
        """Donation checker: large args not donated through a jit
        boundary (KV-pool/param-buffer 2x-HBM trap), dead donations, and
        donations defeated by dtype/shape mismatch — each finding names
        the offending buffer."""
        _emit_family(ctx, emit, "shardcheck-donation")

    @rule("shardcheck-partition", Severity.ERROR)
    def _shardcheck_partition(ctx, emit) -> None:
        """Indivisible sharded dims under the declared mesh: a batch that
        does not divide its data x fsdp product, a param/KV dim that does
        not divide its fsdp/tp axis — ragged shards fail (or hang) the
        first pjit call after the job already started."""
        _emit_family(ctx, emit, "shardcheck-partition")

    @rule("shardcheck-hbm-budget", Severity.ERROR)
    def _shardcheck_hbm_budget(ctx, emit) -> None:
        """Static per-device HBM budget: params + optimizer state + KV
        pool + peak activation liveness (jaxpr linear scan) per device
        under the mesh, gated against JobConfig.hbm_budget_bytes."""
        _emit_family(ctx, emit, "shardcheck-hbm-budget")

    @rule("shardcheck-signatures", Severity.WARN)
    def _shardcheck_signatures(ctx, emit) -> None:
        """Compile-signature enumeration: the static twin of the runtime
        recompile-churn lints — bounded counts (INFO) from
        ServingConfig/BucketPolicy ladders, WARN on unbounded sets."""
        _emit_family(ctx, emit, "shardcheck-signatures")


# ---------------------------------------------------------------------------
# report + CLI
# ---------------------------------------------------------------------------


def report_for_env(env, pipeline: typing.Optional[str] = None) -> dict:
    """The JSON shardcheck report for one captured plan — the format
    ``flink-tpu-doctor --shardcheck`` folds into its diagnosis."""
    from flink_tensorflow_tpu.analysis.analyzer import analyze  # noqa: F401 - registers rules
    from flink_tensorflow_tpu.analysis.rules import AnalysisContext
    from flink_tensorflow_tpu.analysis.schema_prop import propagate

    graph = env.graph
    order = graph.topological_order()
    operators = {}
    for t in graph.transformations:
        try:
            operators[t.id] = t.operator_factory()
        except Exception:  # noqa: BLE001 - factory-error is the analyzer's finding
            operators[t.id] = None
    flow = propagate(graph, order, operators)
    ctx = AnalysisContext(graph=graph, order=order, operators=operators,
                          schemas=flow.out, schema_sets=flow.out_sets,
                          config=env.config)
    audit = audit_of(ctx)
    report = audit.to_json()
    report["pipeline"] = pipeline
    report["errors"] = sum(
        1 for f in audit.findings if f.severity == Severity.ERROR)
    return report


def _parse_mesh(spec: str) -> typing.Dict[str, int]:
    axes: typing.Dict[str, int] = {}
    for part in spec.split(","):
        if not part.strip():
            continue
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def main(argv=None) -> int:
    """``flink-tpu-shardcheck`` — the console script."""
    import argparse
    import dataclasses as dc
    import json
    import sys

    parser = argparse.ArgumentParser(
        prog="flink-tpu-shardcheck",
        description="SPMD layout, donation & HBM-budget static analyzer: "
                    "abstract-evaluates every jit unit of a captured plan "
                    "against a declared (abstract) mesh — no devices, no "
                    "execution.",
    )
    parser.add_argument("pipelines", nargs="+", metavar="pipeline.py",
                        help="pipeline script(s) defining main(argv)")
    parser.add_argument("--job-args", default="--smoke --cpu",
                        help="argv passed to each pipeline's main() while "
                             "building its graph (default: '--smoke --cpu')")
    parser.add_argument("--mesh", metavar="data=4,model=2",
                        help="override the job's mesh with an ABSTRACT mesh "
                             "of these axes (v5e-8 fsdp x tp: "
                             "'data=1,fsdp=4,tp=2')")
    parser.add_argument("--hbm-budget-bytes", type=int, default=None,
                        help="override JobConfig.hbm_budget_bytes "
                             "(v5e: 16 GiB/chip)")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON report per pipeline")
    parser.add_argument("--out", metavar="REPORT.json",
                        help="also write the (last) JSON report here — the "
                             "file flink-tpu-doctor --shardcheck reads")
    parser.add_argument("--cost-table", metavar="TABLE.json",
                        help="also price the (last) captured plan "
                             "(analysis/costmodel: per jit unit, per "
                             "compile signature — FLOPs, HBM bytes, "
                             "collective bytes, expected h2d/d2h) and "
                             "write the CostTable here — the file "
                             "flink-tpu-roofline --cost-table reads")
    args = parser.parse_args(argv)

    from flink_tensorflow_tpu.analysis.capture import capture_pipeline_file

    job_args = args.job_args.split()
    exit_code = 0
    report = None
    last_env = None
    for path in args.pipelines:
        try:
            env = capture_pipeline_file(path, job_args)
        except Exception as ex:  # noqa: BLE001 - report and keep going
            print(f"{path}: capture failed: {ex}", file=sys.stderr)
            exit_code = max(exit_code, 2)
            continue
        config = env.config
        if args.mesh:
            from flink_tensorflow_tpu.parallel.mesh import abstract_mesh

            config = dc.replace(config, mesh=abstract_mesh(_parse_mesh(args.mesh)))
        if args.hbm_budget_bytes is not None:
            config = dc.replace(config, hbm_budget_bytes=args.hbm_budget_bytes)
        env.config = config
        last_env = env
        report = report_for_env(env, pipeline=path)
        if args.json:
            print(json.dumps(report))
        else:
            mesh = report["mesh_axes"]
            print(f"== {path} (mesh: {mesh or 'none declared'}, "
                  f"budget: {report['hbm_budget_bytes'] or 'none'}) ==")
            for a in report["operators"]:
                line = f"  [{a['kind']}] {a['node']}"
                if a["hbm_per_device_total"]:
                    line += (f"  hbm/device="
                             f"{a['hbm_per_device_total'] / 2**20:.1f}MiB")
                if a["signatures"] is not None:
                    line += f"  signatures={a['signatures']}"
                if a["collectives"]:
                    line += f"  collectives={a['collectives']}"
                print(line)
                for note in a["notes"]:
                    print(f"      note: {note}")
            for f in report["findings"]:
                where = f" [{f['edge'] or f['node'] or 'plan'}]"
                print(f"  {f['severity']:5s} {f['rule']}{where}: "
                      f"{f['message']}")
        if report["errors"]:
            exit_code = max(exit_code, 1)
    if args.out and report is not None:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2)
    if args.cost_table and last_env is not None:
        from flink_tensorflow_tpu.analysis.costmodel import cost_table_for_env

        table = cost_table_for_env(last_env)
        with open(args.cost_table, "w") as fh:
            json.dump(table.to_json(), fh, indent=2)
        print(f"cost table -> {args.cost_table}")
    return exit_code


if __name__ == "__main__":
    import sys

    sys.exit(main())
