"""AFMoE (``model_type: afmoe``, Arcee's Trinity) — sliding-window and full
attention mixed in a fixed pattern, a gated attention output, sandwich norms
and routed experts beside a shared one.  The keywords of :func:`build` are the
keys of the model's public ``config.json``, plus the chip's share of an
expert-parallel layer.

Layer ``i`` is ``h += norm_post_attn(attn_i(norm_in(h))); h +=
norm_post_mlp(ff_i(norm_pre_mlp(h)))`` (four RMS norms a layer: the sandwich).
``attn_i`` is grouped-query attention whose queries and keys are rms-normed a
head at a time; where ``layer_types[i]`` is ``"sliding_attention"`` they are
turned by rotate-half RoPE and a query sees the ``sliding_window`` positions up
to its own (the flash kernel's band, ops/flash_attention.py ``window=``), and
where it is ``"full_attention"`` they take no positions (NoPE) and a query sees
every position up to its own.  The attention's output is gated before its
projection: ``(attn * sigmoid(u W_g)) W_o``.  ``ff_i`` is a dense gated MLP for
the first ``num_dense_layers`` layers and after them ``routed(x) + shared(x)``:
the routed layer of ops/moe.py (a sigmoid router with a selection bias, top
``num_experts_per_tok`` of ``router_experts``, weights normalised and times
``route_scale``, no token dropped) and a gated MLP of width
``moe_intermediate_size x num_shared_experts`` that every token takes.  With
``mup_enabled`` the embedding is scaled by ``sqrt(hidden_size)``.  The head is
untied.

**The share.**  ``num_experts`` counts the experts HELD here, ``[first_expert,
first_expert + num_experts)`` of the ``router_experts`` the router chooses
among (the published 256; left out: every expert is held).  The routed layer
computes its own experts' part of the result; what the absent experts would add
is left out, and that partial result goes on to the next layer: a chip's part of
an expert-parallel layer without its exchange.  Attention, the router and the
shared expert are whole on every chip.

The stream path scores a record, one fixed-length sequence of token ids, in
one forward pass: ``serve`` maps ``{"tokens": int32[B, T]}`` to the next-token
distribution after the last position: ``logits`` (float32, the vocabulary
held), ``label``, ``score``, and ``routing`` (``int16[B, T, expert layers, k]``:
the experts every token chose, held here or not).  It also counts, for the
operator's metrics and never for a record: ``expert_rows``, ``expert_rows_max``
and ``expert_passes`` (as models/zoo/kimi_k2.py counts them) and
``attention_tiles`` (a record's compute tiles of ``512 x 512`` that its
attention calls visit, summed over layers and heads: ``tile_plan(...)
.tiles_visited``, the rule of the kernel's own loop bounds).

Precision: parameters are stored in ``param_dtype`` (bfloat16) and go to the
matrix products as they are, with float32 accumulation; activations enter a
product in ``compute_dtype``.  The residual stream, the norms (the two a head
too), RoPE, the gate's sigmoid, the router (its product at ``HIGHEST``), the
softmax statistics of the attention kernel and the logits are float32.  Params
are a plain pytree; the layers are not stacked, so a caller's device-resident
tree is used leaf by leaf as it is.
"""

from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from flink_tensorflow_tpu.models.base import ModelMethod
from flink_tensorflow_tpu.models.zoo.falcon_h1 import _rms_norm, _rope
from flink_tensorflow_tpu.models.zoo.registry import ModelDef, register_model_def
from flink_tensorflow_tpu.ops.flash_attention import flash_attention, tile_plan
from flink_tensorflow_tpu.ops.moe import routed_experts
from flink_tensorflow_tpu.tensors.schema import RecordSchema, TensorSpec

F32 = jnp.float32
#: Trinity-Large's 60 layers: three sliding-window layers, then a full one.
_LAYER_TYPES = tuple("full_attention" if i % 4 == 3 else "sliding_attention" for i in range(60))


@register_model_def("afmoe")
def build(
    *,
    seq_len: int = 4096,
    vocab_size: int = 200192,
    hidden_size: int = 3072,
    intermediate_size: int = 12288,
    moe_intermediate_size: int = 3072,
    num_hidden_layers: int = 60,
    layer_types: typing.Sequence[str] = _LAYER_TYPES,
    num_dense_layers: int = 6,
    num_attention_heads: int = 48,
    num_key_value_heads: int = 8,
    head_dim: int = 128,
    sliding_window: int = 4096,
    num_experts: int = 256,
    router_experts: typing.Optional[int] = None,
    first_expert: int = 0,
    num_experts_per_tok: int = 4,
    num_shared_experts: int = 1,
    score_func: str = "sigmoid",
    route_norm: bool = True,
    route_scale: float = 2.448,
    n_group: int = 1,
    topk_group: int = 1,
    hidden_act: str = "silu",
    mup_enabled: bool = True,
    rms_norm_eps: float = 1e-5,
    rope_theta: float = 10000.0,
    rope_scaling: typing.Optional[typing.Mapping] = None,
    tie_word_embeddings: bool = False,
    param_dtype: str = "bfloat16",
    compute_dtype: str = "bfloat16",
    **published,
) -> ModelDef:
    """Defaults are Trinity-Large-Preview's published sizes.  ``seq_len`` is the
    fixed record length ``T``.  Keys of the published config that change no
    shape and no arithmetic here (``model_type``, ``max_position_embeddings``,
    ``global_attn_every_n_layers``, ...) are accepted and kept in ``config``."""
    config = {k: v for k, v in locals().items() if k != "published"} | published
    layer_types = tuple(layer_types)
    router_experts = num_experts if router_experts is None else router_experts
    if (score_func, hidden_act) != ("sigmoid", "silu") or not route_norm or rope_scaling \
            or tie_word_embeddings or num_shared_experts < 1:
        raise ValueError("built as published: a sigmoid router with a selection bias and normalised "
                         "top-k weights, silu, unscaled RoPE, an untied head, a shared expert")
    if n_group != 1 or topk_group != 1:
        raise ValueError(f"one group of experts (the top-k is over all of them), not n_group {n_group}")
    if len(layer_types) != num_hidden_layers or set(layer_types) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types names {len(layer_types)} layers of kinds {set(layer_types)}, "
                         f"num_hidden_layers is {num_hidden_layers}")
    if num_attention_heads % num_key_value_heads:
        raise ValueError("query heads must divide over the key/value heads")
    if not 0 <= num_dense_layers < num_hidden_layers:
        raise ValueError("at least one routed layer after the dense ones")
    if not 0 <= first_expert <= router_experts - num_experts or router_experts > 32767:
        raise ValueError(f"experts [{first_expert}, {first_expert + num_experts}) of the router's "
                         f"{router_experts}; routing is int16")
    d, heads, kv, hd = hidden_size, num_attention_heads, num_key_value_heads, head_dim
    f, width = moe_intermediate_size, moe_intermediate_size * num_shared_experts
    pdt, cdt = jnp.dtype(param_dtype), jnp.dtype(compute_dtype)
    exact = jax.lax.Precision.HIGHEST if cdt == F32 else None
    # The kernel's own count of the compute tiles a call visits, a head: the band or the triangle.
    tiles = sum(tile_plan(seq_len, seq_len, hd, cdt, True, window=sliding_window if kind == "sliding_attention"
                          else None).tiles_visited for kind in layer_types) * heads

    def dot(x, w):
        return jnp.dot(x.astype(cdt), w.astype(cdt), precision=exact, preferred_element_type=F32)

    def init_fn(rng):
        keys = iter(jax.random.split(rng, 2 + 16 * num_hidden_layers))

        def dense(shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, F32) * (gain / math.sqrt(shape[-2]))).astype(pdt)

        ones = lambda n: jnp.ones((n,), pdt)  # noqa: E731
        gated = lambda w: {"w1": dense((d, w)), "w3": dense((d, w)), "w2": dense((w, d))}  # noqa: E731
        params = {"embed": (jax.random.normal(next(keys), (vocab_size, d), F32) / math.sqrt(d)).astype(pdt),
                  "layers": [], "norm_f": ones(d), "head": dense((d, vocab_size))}
        for i in range(num_hidden_layers):
            layer = {"norm_in": ones(d), "norm_post_attn": ones(d), "norm_pre_mlp": ones(d),
                     "norm_post_mlp": ones(d), "attn": {
                         "wq": dense((d, heads * hd)), "wk": dense((d, kv * hd)), "wv": dense((d, kv * hd)),
                         "wg": dense((d, heads * hd)), "wo": dense((heads * hd, d)),
                         "q_norm": ones(hd), "k_norm": ones(hd)}}
            if i < num_dense_layers:
                layer["mlp"] = gated(intermediate_size)
            else:
                layer["moe"] = {"router": dense((d, router_experts)), "bias": jnp.zeros((router_experts,), pdt),
                                "w13": dense((num_experts, d, 2 * f)), "w2": dense((num_experts, f, d))}
                layer["shared"] = gated(width)
            params["layers"].append(layer)
        return params

    def attention(p, u, sliding: bool):
        with jax.named_scope("window_attention" if sliding else "full_attention"):
            b, t, _ = u.shape
            q = _rms_norm(dot(u, p["wq"]).reshape(b, t, heads, hd), p["q_norm"], rms_norm_eps)
            k = _rms_norm(dot(u, p["wk"]).reshape(b, t, kv, hd), p["k_norm"], rms_norm_eps)
            v = dot(u, p["wv"]).reshape(b, t, kv, hd)
            if sliding:  # NoPE on the full layers
                q, k = _rope(q, float(rope_theta)), _rope(k, float(rope_theta))
            out = flash_attention(q.astype(cdt), k.astype(cdt), v.astype(cdt), causal=True,
                                  window=sliding_window if sliding else None)
            gate = jax.nn.sigmoid(dot(u, p["wg"]))
            return dot(out.reshape(b, t, heads * hd) * gate, p["wo"])

    def mlp(p, x):
        with jax.named_scope("mlp"):
            return dot(jax.nn.silu(dot(x, p["w1"])) * dot(x, p["w3"]), p["w2"])

    def serve(params, inputs):
        tokens = inputs["tokens"]  # [B, T] int32
        h = params["embed"][tokens].astype(F32)
        if mup_enabled:
            h = h * math.sqrt(d)
        routing, rows, rows_max, passes = [], 0, 0, 0
        for p, kind in zip(params["layers"], layer_types):
            a = attention(p["attn"], _rms_norm(h, p["norm_in"], rms_norm_eps), kind == "sliding_attention")
            h = h + _rms_norm(a, p["norm_post_attn"], rms_norm_eps)
            x = _rms_norm(h, p["norm_pre_mlp"], rms_norm_eps)
            if "mlp" in p:
                m = mlp(p["mlp"], x)
            else:
                moe = p["moe"]
                routed = routed_experts(x, moe["router"], moe["bias"], moe["w13"], moe["w2"],
                                        k=num_experts_per_tok, first=first_expert, scaling=route_scale,
                                        eps=1e-20, compute_dtype=cdt)
                with jax.named_scope("shared_expert"):
                    m = routed.out + mlp(p["shared"], x)
                routing.append(routed.experts)
                rows, rows_max = rows + routed.rows, rows_max + routed.rows_max
                passes = passes + routed.passes
            h = h + _rms_norm(m, p["norm_post_mlp"], rms_norm_eps)
        with jax.named_scope("head"):
            last = _rms_norm(h[:, -1], params["norm_f"], rms_norm_eps)
            logits = dot(last, params["head"])
        prob = jax.nn.softmax(logits, axis=-1)
        return {"logits": logits,
                "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "score": jnp.max(prob, axis=-1),
                "routing": jnp.stack(routing, axis=2).astype(jnp.int16),
                "expert_rows": rows, "expert_rows_max": rows_max,
                "expert_passes": jnp.asarray(passes, jnp.int32),
                "attention_tiles": jnp.full((tokens.shape[0],), tiles, jnp.int32)}

    schema = RecordSchema({"tokens": TensorSpec((seq_len,), np.int32)})
    methods = {"serve": ModelMethod(name="serve", input_schema=schema,
                                    output_names=("logits", "label", "score", "routing"), fn=serve,
                                    count_names=("expert_rows", "expert_rows_max", "expert_passes",
                                                 "attention_tiles"),
                                    compute_dtype=cdt)}
    return ModelDef(architecture="afmoe", config=config, module=None, input_schema=schema,
                    methods=methods, init_fn=init_fn)
