"""Mellum 2 (``model_type: mellum``, JetBrains) — sliding-window and
yarn-scaled full attention mixed in a fixed pattern, and in every layer a
softmax router over narrow experts with no shared one.  The keywords of
:func:`build` are the keys of the model's public ``config.json``.

Layer ``i`` is ``h += attn_i(norm_in(h)); h += routed_i(norm_post(h))``.
``attn_i`` is grouped-query attention (no bias, no q/k norm), its queries and
keys turned by rotate-half RoPE over the whole head by the section of
``rope_parameters`` that ``layer_types[i]`` names: ``"sliding_attention"``
turns by ``rope_theta`` alone and a query sees the ``sliding_window``
positions up to its own (the flash kernel's band, ops/flash_attention.py
``window=``); ``"full_attention"`` turns by yarn's frequencies
(ops/mla.py ``yarn_inv_freq``), its ``cos`` and ``sin`` times
``attention_factor``, and a query sees every position up to its own.
``routed_i`` is the routed layer of ops/moe.py with a softmax router: top
``num_experts_per_tok`` of ``num_experts`` by ``softmax(x W_r)``, the chosen
probabilities over their sum (``norm_topk_prob``), no token dropped; every
expert is held.  The head is untied.

The stream path scores a record, one fixed-length sequence of token ids, in
one forward pass: ``serve`` maps ``{"tokens": int32[B, T]}`` to the next-token
distribution after the last position: ``logits`` (float32, the whole
vocabulary), ``label``, ``score``, and ``routing`` (``int16[B, T, layers,
k]``: the experts every token chose).  It also counts, for the operator's
metrics and never for a record, ``expert_rows``, ``expert_rows_max``,
``expert_passes`` and ``attention_tiles``, as models/zoo/afmoe.py counts them.

Precision: parameters are stored in ``param_dtype`` (bfloat16) and go to the
matrix products as they are, with float32 accumulation; activations enter a
product in ``compute_dtype``.  The residual stream, the norms, RoPE, the
router (its product at ``HIGHEST``, its softmax), the softmax statistics of
the attention kernel and the logits are float32.  Params are a plain pytree;
the layers are not stacked, so a caller's device-resident tree is used leaf
by leaf as it is.
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
from flink_tensorflow_tpu.ops.mla import yarn_inv_freq, yarn_mscale
from flink_tensorflow_tpu.ops.moe import routed_experts
from flink_tensorflow_tpu.tensors.schema import RecordSchema, TensorSpec

F32 = jnp.float32
#: Mellum2-12B-A2.5B's 28 layers: three sliding-window layers, then a full one.
_LAYER_TYPES = tuple("full_attention" if i % 4 == 3 else "sliding_attention" for i in range(28))
_ROPE_PARAMETERS = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                       "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def rope_of(section: typing.Mapping, head_dim: int):
    """(theta, frequencies or None, scale of ``cos`` and ``sin``) of one
    section of ``rope_parameters``: ``default`` turns by ``rope_theta``,
    ``yarn`` by yarn's frequencies with ``attention_factor`` (0.1 ln factor + 1
    where the section gives none)."""
    theta = float(section["rope_theta"])
    kind = section.get("rope_type", "default")
    if kind == "default":
        return theta, None, 1.0
    if kind != "yarn":
        raise ValueError(f"RoPE of type default or yarn, not {kind!r}")
    scale = section.get("attention_factor") or yarn_mscale(section["factor"], 1.0)
    return theta, yarn_inv_freq(head_dim, theta, section), float(scale)


@register_model_def("mellum")
def build(
    *,
    seq_len: int = 4096,
    vocab_size: int = 98304,
    hidden_size: int = 2304,
    intermediate_size: int = 7168,
    moe_intermediate_size: int = 896,
    num_hidden_layers: int = 28,
    layer_types: typing.Sequence[str] = _LAYER_TYPES,
    mlp_layer_types: typing.Optional[typing.Sequence[str]] = None,
    num_attention_heads: int = 32,
    num_key_value_heads: int = 4,
    head_dim: int = 128,
    sliding_window: int = 1024,
    num_experts: int = 64,
    num_experts_per_tok: int = 8,
    norm_topk_prob: bool = True,
    hidden_act: str = "silu",
    attention_bias: bool = False,
    rms_norm_eps: float = 1e-6,
    rope_parameters: typing.Mapping = _ROPE_PARAMETERS,
    tie_word_embeddings: bool = False,
    param_dtype: str = "bfloat16",
    compute_dtype: str = "bfloat16",
    **published,
) -> ModelDef:
    """Defaults are Mellum2-12B-A2.5B's published sizes.  ``seq_len`` is the
    fixed record length ``T``.  Keys of the published config that change no
    shape and no arithmetic here (``model_type``, ``max_position_embeddings``,
    ``max_window_layers``, ``use_sliding_window``, ...) are accepted and kept
    in ``config``; ``layer_types`` alone says which layers are windowed."""
    config = {k: v for k, v in locals().items() if k != "published"} | published
    layer_types = tuple(layer_types)
    mlp_layer_types = ("sparse",) * num_hidden_layers if mlp_layer_types is None else tuple(mlp_layer_types)
    if hidden_act != "silu" or attention_bias or tie_word_embeddings or not norm_topk_prob:
        raise ValueError("built as published: silu, no attention bias, an untied head, normalised top-k weights")
    if len(layer_types) != num_hidden_layers or set(layer_types) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types names {len(layer_types)} layers of kinds {set(layer_types)}, "
                         f"num_hidden_layers is {num_hidden_layers}")
    if mlp_layer_types != ("sparse",) * num_hidden_layers:
        raise ValueError(f"mlp_layer_types: every layer sparse, as published, not {set(mlp_layer_types)}")
    if num_attention_heads % num_key_value_heads:
        raise ValueError("query heads must divide over the key/value heads")
    if num_experts > 32767:
        raise ValueError(f"{num_experts} experts: routing is int16")
    d, heads, kv, hd, f = hidden_size, num_attention_heads, num_key_value_heads, head_dim, moe_intermediate_size
    rope = {kind: rope_of(rope_parameters[kind], hd) for kind in set(layer_types)}
    pdt, cdt = jnp.dtype(param_dtype), jnp.dtype(compute_dtype)
    exact = jax.lax.Precision.HIGHEST if cdt == F32 else None
    # The kernel's own count of the compute tiles a call visits, a head: the band or the triangle.
    tiles = sum(tile_plan(seq_len, seq_len, hd, cdt, True, window=sliding_window if kind == "sliding_attention"
                          else None).tiles_visited for kind in layer_types) * heads

    def dot(x, w):
        return jnp.dot(x.astype(cdt), w.astype(cdt), precision=exact, preferred_element_type=F32)

    def init_fn(rng):
        keys = iter(jax.random.split(rng, 2 + 8 * num_hidden_layers))

        def dense(shape):
            return (jax.random.normal(next(keys), shape, F32) / math.sqrt(shape[-2])).astype(pdt)

        ones = lambda n: jnp.ones((n,), pdt)  # noqa: E731
        params = {"embed": jax.random.normal(next(keys), (vocab_size, d), F32).astype(pdt),
                  "layers": [], "norm_f": ones(d), "head": dense((d, vocab_size))}
        for _ in range(num_hidden_layers):
            params["layers"].append({
                "norm_in": ones(d), "norm_post": ones(d),
                "attn": {"wq": dense((d, heads * hd)), "wk": dense((d, kv * hd)), "wv": dense((d, kv * hd)),
                         "wo": dense((heads * hd, d))},
                "moe": {"router": dense((d, num_experts)), "w13": dense((num_experts, d, 2 * f)),
                        "w2": dense((num_experts, f, d))}})
        return params

    def attention(p, u, kind: str):
        sliding = kind == "sliding_attention"
        with jax.named_scope("window_attention" if sliding else "full_attention"):
            b, t, _ = u.shape
            theta, inv_freq, scale = rope[kind]
            q = _rope(dot(u, p["wq"]).reshape(b, t, heads, hd), theta, inv_freq, scale)
            k = _rope(dot(u, p["wk"]).reshape(b, t, kv, hd), theta, inv_freq, scale)
            v = dot(u, p["wv"]).reshape(b, t, kv, hd)
            out = flash_attention(q.astype(cdt), k.astype(cdt), v.astype(cdt), causal=True,
                                  window=sliding_window if sliding else None)
            return dot(out.reshape(b, t, heads * hd), p["wo"])

    def serve(params, inputs):
        tokens = inputs["tokens"]  # [B, T] int32
        h = params["embed"][tokens].astype(F32)
        routing, rows, rows_max, passes = [], 0, 0, 0
        for p, kind in zip(params["layers"], layer_types):
            h = h + attention(p["attn"], _rms_norm(h, p["norm_in"], rms_norm_eps), kind)
            moe = p["moe"]
            routed = routed_experts(_rms_norm(h, p["norm_post"], rms_norm_eps), moe["router"], None,
                                    moe["w13"], moe["w2"], k=num_experts_per_tok, score_func="softmax",
                                    compute_dtype=cdt)
            h = h + routed.out
            routing.append(routed.experts)
            rows, rows_max, passes = rows + routed.rows, rows_max + routed.rows_max, passes + routed.passes
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
    return ModelDef(architecture="mellum", config=config, module=None, input_schema=schema,
                    methods=methods, init_fn=init_fn)
