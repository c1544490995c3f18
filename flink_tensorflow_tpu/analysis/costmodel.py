"""Static cost model — the plan-time half of the roofline plane.

shardcheck (PR 16) abstract-evaluates every jit unit of a captured plan
to audit layout/donation/HBM; this module walks the SAME closed jaxprs
one level deeper and prices them: estimated FLOPs (dot_general/conv
dominate; scan bodies multiply by trip count), HBM bytes moved (an
un-fused per-eqn upper bound), collective bytes, and the expected
h2d/d2h per call — per jit unit, per compile signature.  The result is
a :class:`CostTable` attached to the captured plan
(``JobConfig.roofline``) and shipped to every worker, where
``metrics/roofline.py`` joins it against measured step times to publish
continuous ``roofline.*`` gauges (achieved FLOP/s, MFU, bound
classification) and to diff the predicted compile-signature ladder
against runtime jit cache misses.

Estimation contract (kept honest at run time by the predicted-vs-measured
join of ``metrics/roofline.py``):

- FLOPs: ``dot_general`` = 2·batch·M·N·K from the invar avals;
  ``conv_general_dilated`` = 2·out_elems·(kernel elems / out features);
  reductions and a modest elementwise set count one FLOP per element;
  ``scan`` bodies multiply by ``length``; ``while`` bodies count ONCE
  (trip count is dynamic — noted on the entry's operator).
- HBM bytes: per-eqn invar+outvar traffic summed over every level —
  an UN-FUSED upper bound (XLA fuses most elementwise chains), with
  pure-layout prims (reshape/broadcast/iota) excluded since they never
  materialize post-fusion.  Good enough to rank memory- vs
  compute-bound; not a promise of DMA counters.
- h2d/d2h: mirrors the runners' accounting exactly —
  ``DecodeStepRunner`` prefill ships tokens+lengths+slots and fetches
  ``[B]`` next-tokens; the padded decode step ships ``[S]``
  tokens+lengths+mask and fetches ``[S]`` tokens;
  ``CompiledMethodRunner`` ships the padded batch struct.

Everything is fail-soft, mirroring shardcheck: a unit whose abstract
trace raises becomes a note on its :class:`OperatorCost`, never a
crashed export.  Front doors: ``cost_table_for_env(env)`` (what
``environment._make_executor`` calls when ``JobConfig.roofline`` is set
without an explicit table) and ``flink-tpu-shardcheck --cost-table
OUT.json`` (the offline artifact ``flink-tpu-roofline`` joins against
traces/snapshots).
"""

from __future__ import annotations

import dataclasses
import math
import typing

from flink_tensorflow_tpu.analysis.shardcheck import (
    COLLECTIVE_PRIMS,
    _as_jaxprs,
    _struct_of,
)

if typing.TYPE_CHECKING:
    from flink_tensorflow_tpu.analysis.rules import AnalysisContext

#: Elementwise/transcendental prims priced at one FLOP per output
#: element.  Deliberately modest — matmuls/convs dominate every MFU
#: figure this table feeds; the set just keeps pure-VPU units non-zero.
ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "integer_pow",
    "exp", "log", "tanh", "logistic", "erf", "rsqrt", "sqrt", "neg",
    "abs", "select_n", "add_any",
})

#: Reductions priced at one FLOP per INPUT element (the adds/compares).
REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "cumsum", "cumlogsumexp",
})

#: Pure-layout prims excluded from the HBM traffic estimate — they
#: never materialize after XLA fusion, and a broadcast scalar priced at
#: its output shape would drown the real traffic.
LAYOUT_PRIMS = frozenset({
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "iota",
    "copy",
})

#: Signature-ladder trace cap: pricing every (admit x prompt) prefill
#: bucket re-traces the model per combo; past this many the largest
#: combos are kept and the truncation is noted (the runtime join simply
#: finds no entry for an unpriced signature — never wrong, just blank).
MAX_SIGNATURE_TRACES = 32


# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostEntry:
    """The static price of ONE call of one jit unit at one signature."""

    unit: str             # prefill | decode_step | <method name> | train_step
    signature: str        # the runtime compile-signature name this prices
    flops: int = 0
    hbm_bytes: int = 0    # un-fused per-eqn traffic upper bound
    collective_bytes: int = 0
    h2d_bytes: int = 0    # expected host->device per call
    d2h_bytes: int = 0    # expected device->host per call

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: dict) -> "CostEntry":
        return cls(**{f.name: doc.get(f.name, 0 if f.name not in
                                      ("unit", "signature") else "")
                      for f in dataclasses.fields(cls)})


@dataclasses.dataclass
class OperatorCost:
    """Every priced jit unit of one operator, plus its predicted
    compile-signature ladder (the runtime compile-event diff target)."""

    node: str
    kind: str  # model | train | serving
    entries: typing.List[CostEntry] = dataclasses.field(default_factory=list)
    #: Every signature the plan can legally present — a runtime jit
    #: cache miss OUTSIDE this ladder is a `roofline-recompile` finding.
    predicted_signatures: typing.Tuple[str, ...] = ()
    notes: typing.List[str] = dataclasses.field(default_factory=list)

    def entry(self, unit: str,
              signature: typing.Optional[str] = None
              ) -> typing.Optional[CostEntry]:
        """Exact (unit, signature) match, else the unit's sole entry."""
        of_unit = [e for e in self.entries if e.unit == unit]
        if signature is not None:
            for e in of_unit:
                if e.signature == signature:
                    return e
        return of_unit[0] if len(of_unit) == 1 else None

    def to_json(self) -> dict:
        return {
            "node": self.node, "kind": self.kind,
            "predicted_signatures": list(self.predicted_signatures),
            "entries": [e.to_json() for e in self.entries],
            "notes": list(self.notes),
        }

    @classmethod
    def from_json(cls, doc: dict) -> "OperatorCost":
        return cls(
            node=doc["node"], kind=doc.get("kind", "?"),
            entries=[CostEntry.from_json(e) for e in doc.get("entries", ())],
            predicted_signatures=tuple(doc.get("predicted_signatures", ())),
            notes=list(doc.get("notes", ())),
        )


@dataclasses.dataclass
class CostTable:
    """The full static cost export for one captured plan."""

    ops: typing.List[OperatorCost] = dataclasses.field(default_factory=list)
    mesh_axes: typing.Optional[typing.Dict[str, int]] = None

    def op(self, node: str) -> typing.Optional[OperatorCost]:
        for oc in self.ops:
            if oc.node == node:
                return oc
        return None

    def to_json(self) -> dict:
        return {
            "kind": "flink-tpu-cost-table",
            "mesh_axes": self.mesh_axes,
            "operators": [oc.to_json() for oc in self.ops],
        }

    @classmethod
    def from_json(cls, doc: dict) -> "CostTable":
        if doc.get("kind") not in (None, "flink-tpu-cost-table"):
            raise ValueError(f"not a cost table: kind={doc.get('kind')!r}")
        return cls(
            ops=[OperatorCost.from_json(o) for o in doc.get("operators", ())],
            mesh_axes=doc.get("mesh_axes"),
        )


# ---------------------------------------------------------------------------
# jaxpr pricing walk
# ---------------------------------------------------------------------------


def _aval_elems(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", None)
    if shape is None:
        return 0
    try:
        return int(math.prod(shape))
    except TypeError:  # symbolic dims
        return 0


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    return _aval_elems(v) * int(dtype.itemsize)


def _dot_flops(eqn) -> int:
    """2·batch·M·N·K from the dot_general dimension numbers."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[i] for i in lb) if lb else 1
    k = math.prod(lhs.shape[i] for i in lc) if lc else 1
    m = math.prod(lhs.shape[i] for i in range(len(lhs.shape))
                  if i not in set(lb) | set(lc))
    n = math.prod(rhs.shape[i] for i in range(len(rhs.shape))
                  if i not in set(rb) | set(rc))
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    """2·out_elems·(kernel elems per output feature), grouped convs
    priced correctly because the rhs in-feature dim is already divided
    by feature_group_count in the aval."""
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_features = max(1, rhs.shape[dn.rhs_spec[0]])
    per_out = math.prod(rhs.shape) // out_features
    return 2 * int(math.prod(out.shape)) * per_out


def _jaxpr_cost(jaxpr) -> typing.Tuple[int, int, int]:
    """(flops, hbm_bytes, collective_bytes) of one jaxpr level,
    recursing into sub-jaxprs with scan trip-count multiplication (the
    one place the flat ``_iter_levels`` walk would lose information)."""
    flops = hbm = coll = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name.rstrip("0123456789")
        subs = [s for val in eqn.params.values() for s in _as_jaxprs(val)]
        if subs:
            if name == "cond":
                # Branches are alternatives: price the most expensive.
                costs = [_jaxpr_cost(s) for s in subs]
                flops += max(c[0] for c in costs)
                hbm += max(c[1] for c in costs)
                coll += max(c[2] for c in costs)
            else:
                mult = (int(eqn.params.get("length", 1))
                        if name == "scan" else 1)
                for s in subs:
                    f, h, c = _jaxpr_cost(s)
                    flops += mult * f
                    hbm += mult * h
                    coll += mult * c
            continue
        if name == "dot_general":
            flops += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            flops += _conv_flops(eqn)
        elif name in ELEMENTWISE_PRIMS:
            flops += sum(_aval_elems(v) for v in eqn.outvars)
        elif name in REDUCE_PRIMS:
            flops += sum(_aval_elems(v) for v in eqn.invars
                         if hasattr(v, "aval"))
        if name in COLLECTIVE_PRIMS:
            coll += sum(_aval_bytes(v) for v in eqn.outvars)
        if name not in LAYOUT_PRIMS:
            hbm += sum(_aval_bytes(v) for v in eqn.invars
                       if hasattr(v, "aval"))
            hbm += sum(_aval_bytes(v) for v in eqn.outvars)
    return flops, hbm, coll


def cost_of_closed(closed) -> typing.Tuple[int, int, int]:
    """(flops, hbm_bytes, collective_bytes) of one closed jaxpr."""
    return _jaxpr_cost(closed.jaxpr)


def flops_of_closed(closed) -> int:
    return cost_of_closed(closed)[0]


# ---------------------------------------------------------------------------
# per-operator pricing (mirrors shardcheck's three audit paths)
# ---------------------------------------------------------------------------


def _entry_of(unit: str, signature: str, closed,
              h2d_bytes: int, d2h_bytes: int) -> CostEntry:
    flops, hbm, coll = cost_of_closed(closed)
    return CostEntry(unit=unit, signature=signature, flops=flops,
                     hbm_bytes=hbm, collective_bytes=coll,
                     h2d_bytes=h2d_bytes, d2h_bytes=d2h_bytes)


def serving_signature(kind: str, batch: int, length: int) -> str:
    """The runtime compile-signature name for one
    ``ServingConfig.compile_signatures()`` tuple — shared by the
    plan-time ladder and ``DecodeStepRunner``'s observe hooks so the
    compile-event diff joins on equal strings."""
    if kind == "decode":
        return f"decode:{batch}"
    return f"{kind}:{batch}x{length}"


def _cost_serving(t, op) -> OperatorCost:
    import jax
    import numpy as np

    from flink_tensorflow_tpu.functions.runner import _build_decode_calls

    cost = OperatorCost(node=t.name, kind="serving")
    cfg = op.serving_config
    sigs = cfg.compile_signatures()
    if sigs is None:
        cost.notes.append(
            "padding_buckets off — the signature set is unbounded; no "
            "predicted ladder, every runtime compile is unpredicted by "
            "construction")
        return cost
    cost.predicted_signatures = tuple(
        serving_signature(k, b, n) for (k, b, n) in sigs)
    model = op.model
    try:
        prefill = model.method("prefill")
        decode = model.method("decode_step")
        S, C = cfg.max_active_seqs, cfg.capacity
        B = cfg.bucket_admit(S)
        T = min(cfg.bucket_prompt_len(C), C)
        params_struct = _struct_of(model.params)
        pf_out = jax.eval_shape(
            lambda p, tk, ln: prefill.fn(p, {"tokens": tk, "lengths": ln}),
            params_struct,
            jax.ShapeDtypeStruct((B, T), np.int32),
            jax.ShapeDtypeStruct((B,), np.int32))
        k_like = pf_out["k_cache"]  # [B, L, T, H, Dh]
        _, layers, _, heads, hd = k_like.shape
        pool_dtype = np.dtype(k_like.dtype)
        paged = bool(getattr(cfg, "paged_kv", False))
        if paged:
            from flink_tensorflow_tpu.functions.runner import (
                _build_paged_calls,
            )
            from flink_tensorflow_tpu.ops.paged_attention import (
                pages_per_session,
            )

            pt = cfg.page_tokens
            Pc = pages_per_session(C, pt)  # table width per session
            P = cfg.resolved_hbm_pages()
            kp = jax.ShapeDtypeStruct(
                (P, layers, pt, heads, hd), pool_dtype)
            prefill_into, step_full, _ = _build_paged_calls(
                prefill.fn, decode.fn, C, pt, P)
        else:
            kc = jax.ShapeDtypeStruct((S, layers, C, heads, hd), pool_dtype)
            prefill_into, step_full, _ = _build_decode_calls(
                prefill.fn, decode.fn, C)
        combos = [(b, min(n, C)) for (kind, b, n) in sigs
                  if kind == "prefill"]
        combos = sorted(set(combos))
        if len(combos) > MAX_SIGNATURE_TRACES:
            cost.notes.append(
                f"prefill ladder has {len(combos)} signatures — priced "
                f"the largest {MAX_SIGNATURE_TRACES} (unpriced "
                "signatures join with no MFU, never a wrong one)")
            combos = combos[-MAX_SIGNATURE_TRACES:]
        for b, n in combos:
            tok = jax.ShapeDtypeStruct((b, n), np.int32)
            lens = jax.ShapeDtypeStruct((b,), np.int32)
            if paged:
                tables = jax.ShapeDtypeStruct((b, Pc), np.int32)
                closed = jax.make_jaxpr(prefill_into)(
                    params_struct, tok, lens, tables, kp, kp)
                # Paged prefill: the scatter table [b, Pc] int32 rides
                # up instead of the [b] slot vector.
                h2d = b * n * 4 + b * 4 + b * Pc * 4
            else:
                slots = jax.ShapeDtypeStruct((b,), np.int32)
                closed = jax.make_jaxpr(prefill_into)(
                    params_struct, tok, lens, slots, kc, kc)
                # Mirrors DecodeStepRunner.prefill: tokens + lengths +
                # slot vector up, [B] next-tokens down.
                h2d = b * n * 4 + b * 4 + b * 4
            cost.entries.append(_entry_of(
                "prefill", serving_signature("prefill", b, n), closed,
                h2d_bytes=h2d, d2h_bytes=b * 4))
        if paged:
            st_closed = jax.make_jaxpr(step_full)(
                params_struct,
                jax.ShapeDtypeStruct((S,), np.int32),
                jax.ShapeDtypeStruct((S,), np.int32),
                jax.ShapeDtypeStruct((S, Pc), np.int32),
                kp, kp)
            # Paged decode: block tables ARE host state, re-serialized
            # every step — [S, Pc] int32 replaces the dense [S] bool
            # active mask (liveness rides the sentinel page id).
            step_h2d = S * 4 + S * 4 + S * Pc * 4
        else:
            st_closed = jax.make_jaxpr(step_full)(
                params_struct,
                jax.ShapeDtypeStruct((S,), np.int32),
                jax.ShapeDtypeStruct((S,), np.int32),
                jax.ShapeDtypeStruct((S,), np.bool_),
                kc, kc)
            # Mirrors decode_step under padding buckets: [S] int32
            # tokens + [S] int32 lengths + [S] bool mask up, [S]
            # next-tokens down (72 B a step at S=8).
            step_h2d = S * 4 + S * 4 + S * 1
        cost.entries.append(_entry_of(
            "decode_step", serving_signature("decode", S, 1), st_closed,
            h2d_bytes=step_h2d, d2h_bytes=S * 4))
        # cache_move entries price the tier machinery's data motion
        # (park/extract/insert/spill revival).  Transfers are not
        # executables, so these deliberately stay OUT of
        # predicted_signatures — observing one must never count as a
        # compile-ladder miss.
        esz = pool_dtype.itemsize
        if paged:
            page_bytes = 2 * layers * pt * heads * hd * esz
            for n_pages in range(1, Pc + 1):
                cost.entries.append(CostEntry(
                    unit="cache_move",
                    signature=f"cache:pages:{n_pages}",
                    h2d_bytes=n_pages * page_bytes,
                    d2h_bytes=n_pages * page_bytes))
        else:
            block_bytes = 2 * layers * C * heads * hd * esz
            cost.entries.append(CostEntry(
                unit="cache_move", signature="cache:block",
                h2d_bytes=block_bytes, d2h_bytes=block_bytes))
    except Exception as ex:  # noqa: BLE001 - fail-soft by contract
        cost.notes.append(f"abstract pricing failed: {ex!r}")
    return cost


def _cost_model_function(t, function, in_schema) -> OperatorCost:
    import jax

    from flink_tensorflow_tpu.models.base import Model

    cost = OperatorCost(node=t.name, kind="model")
    source = getattr(function, "_source", None)
    schema = function.plan_input_schema() or in_schema
    if not isinstance(source, Model) or schema is None:
        cost.notes.append("lazy model source or unknown schema — jit "
                          "unit not priceable at plan time")
        return cost
    try:
        method = source.method(function._method_name)
    except KeyError as ex:
        cost.notes.append(f"model method unresolvable: {ex}")
        return cost
    if method.needs_lengths:
        cost.notes.append("method takes per-record lengths — pricing "
                          "skipped (no schema slot to trace from)")
        return cost
    policy = function.plan_policy()
    sizes = tuple(getattr(policy.batch, "sizes", ()) or ())
    batches = ((policy.fixed_batch,) if policy.fixed_batch
               else sizes or (1,))
    if len(batches) > 8:
        cost.notes.append(f"batch ladder has {len(batches)} sizes — "
                          "priced the largest 8")
        batches = batches[-8:]
    # The runtime signature (CompiledMethodRunner joins on
    # batch.padded_size alone) folds length buckets together; pricing
    # uses the warmup length bucket, noted when lengths are dynamic.
    if any(not schema[n].is_static for n in schema.names):
        cost.notes.append(
            "dynamic-length fields priced at the warmup length bucket; "
            "runtime signatures key on padded batch only")
    cost.predicted_signatures = tuple(f"b{b}" for b in batches)
    params_struct = _struct_of(source.params)
    for b in batches:
        try:
            struct = schema.batched_struct(
                b, length_bucket=function._warmup_length_bucket)
            closed = jax.make_jaxpr(
                lambda p, x: method.fn(p, x))(params_struct, struct)
            outputs = jax.eval_shape(
                lambda p, x: method.fn(p, x), params_struct, struct)
            h2d = sum(int(math.prod(s.shape)) * s.dtype.itemsize
                      for s in struct.values())
            d2h = sum(int(math.prod(v.shape)) * v.dtype.itemsize
                      for v in outputs.values() if hasattr(v, "shape"))
            cost.entries.append(_entry_of(
                method.name, f"b{b}", closed, h2d_bytes=h2d, d2h_bytes=d2h))
        except Exception as ex:  # noqa: BLE001 - fail-soft by contract
            cost.notes.append(f"abstract pricing failed at b{b}: {ex!r}")
            break
    return cost


def _cost_train(t, function) -> OperatorCost:
    import jax
    import numpy as np

    cost = OperatorCost(node=t.name, kind="train")
    batch = (getattr(function, "global_batch", None)
             or getattr(function, "mini_batch", None) or 1)
    sig = f"train:b{batch}"
    cost.predicted_signatures = (sig,)
    try:
        import optax
        from flink_tensorflow_tpu.parallel.dp import (
            init_train_state,
            make_train_step,
        )

        schema = function.train_schema
        optimizer = function.optimizer or optax.sgd(0.01)
        state = jax.eval_shape(
            lambda: init_train_state(function.model_def, optimizer,
                                     jax.random.PRNGKey(0)))
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
        h2d = sum(int(math.prod(s.shape)) * s.dtype.itemsize
                  for s in struct.values())
        cost.entries.append(_entry_of(
            "train_step", sig, closed, h2d_bytes=h2d, d2h_bytes=0))
    except Exception as ex:  # noqa: BLE001 - fail-soft by contract
        cost.notes.append(f"abstract pricing failed: {ex!r}")
    return cost


# ---------------------------------------------------------------------------
# the plan walk + front doors
# ---------------------------------------------------------------------------


def cost_table_for_ctx(ctx: "AnalysisContext") -> CostTable:
    """Price every jit unit of one analysis context (cached per ctx —
    the shardcheck CLI and the plan-time auto-build share one pass)."""
    cached = ctx.__dict__.get("_costmodel_table")
    if cached is not None:
        return cached
    config = ctx.config
    mesh = getattr(config, "mesh", None) if config is not None else None
    table = CostTable(mesh_axes=dict(mesh.shape) if mesh is not None else None)
    for t in ctx.order:
        op = ctx.operators.get(t.id)
        if op is None:
            continue
        function = getattr(op, "function", None)
        if getattr(op, "is_continuous_batching", False):
            table.ops.append(_cost_serving(t, op))
        elif hasattr(function, "model_def") and hasattr(function,
                                                        "train_schema"):
            table.ops.append(_cost_train(t, function))
        elif getattr(function, "is_jit_boundary", False) and hasattr(
                function, "plan_input_schema"):
            table.ops.append(_cost_model_function(
                t, function, ctx.input_schema(t)))
    ctx.__dict__["_costmodel_table"] = table
    return table


def cost_table_for_env(env) -> CostTable:
    """Price every jit unit of one captured environment's plan — the
    ``environment._make_executor`` auto-build when ``JobConfig.roofline``
    is set without an explicit table."""
    from flink_tensorflow_tpu.analysis.rules import AnalysisContext
    from flink_tensorflow_tpu.analysis.schema_prop import propagate

    graph = env.graph
    order = graph.topological_order()
    operators = {}
    for t in graph.transformations:
        try:
            operators[t.id] = t.operator_factory()
        except Exception:  # noqa: BLE001 - unbuildable op is simply unpriced
            operators[t.id] = None
    flow = propagate(graph, order, operators)
    ctx = AnalysisContext(graph=graph, order=order, operators=operators,
                          schemas=flow.out, schema_sets=flow.out_sets,
                          config=env.config)
    return cost_table_for_ctx(ctx)
