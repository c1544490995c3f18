"""LFM2-MoE — gated short convolutions, grouped-query attention and routed
experts (``model_type: lfm2_moe``; the keywords of :func:`build` are the keys
of the model's public ``config.json``).

Layer ``i`` is ``x += op_i(norm(x)); x += ff_i(norm(x))``.  ``op_i`` is the
gated short convolution where ``layer_types[i] == "conv"`` (``B, C, z`` from
one projection; ``C * causal_conv1d(B * z)``; an output projection; no
activation) and grouped-query attention where ``"full_attention"`` (queries
and keys rms-normed per head, then rotate-half RoPE).  ``ff_i`` is a dense
gated MLP for the first ``num_dense_layers`` layers and after them the routed
layer of ops/moe.py: a sigmoid router with a selection bias, top
``num_experts_per_tok`` of ``num_experts``, no shared expert, no token
dropped.  The head is the embedding transposed.

The stream path scores a record, one fixed-length sequence of token ids, in
one forward pass: ``serve`` maps ``{"tokens": int32[B, T]}`` to the next-token
distribution after the last position: ``logits`` (float32, the whole
vocabulary), ``label``, ``score``, and ``routing`` (``int8[B, T, expert
layers, k]``: the experts every token chose, for a caller that asks).  It
also counts, for the operator's metrics and never for a record:
``expert_rows`` (a record's (token, slot) pairs that fell on a held expert,
summed over the expert layers) and ``expert_rows_max`` (the fullest expert's
rows of the batch, summed likewise).

Precision: parameters are stored in ``param_dtype`` (bfloat16) and go to the
matrix products as they are, with float32 accumulation; activations enter a
product in ``compute_dtype``.  The residual stream, the norms, the gating
products of the convolution, the router (its product at ``HIGHEST``), the
softmax statistics of the attention kernel and the logits are float32.
Params are a plain pytree; the layers are not stacked, so a caller's
device-resident tree is used leaf by leaf as it is.
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
from flink_tensorflow_tpu.ops.flash_attention import flash_attention
from flink_tensorflow_tpu.ops.moe import routed_experts
from flink_tensorflow_tpu.ops.ssd import causal_conv1d
from flink_tensorflow_tpu.tensors.schema import RecordSchema, TensorSpec

F32 = jnp.float32
#: The 24 layers of LFM2-8B-A1B.
_LAYER_TYPES = tuple("full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24))


@register_model_def("lfm2_moe")
def build(
    *,
    seq_len: int = 4096,
    vocab_size: int = 65536,
    hidden_size: int = 2048,
    intermediate_size: int = 7168,
    moe_intermediate_size: int = 1792,
    num_hidden_layers: int = 24,
    layer_types: typing.Sequence[str] = _LAYER_TYPES,
    num_dense_layers: int = 2,
    num_attention_heads: int = 32,
    num_key_value_heads: int = 8,
    num_experts: int = 32,
    num_experts_per_tok: int = 4,
    norm_topk_prob: bool = True,
    use_expert_bias: bool = True,
    routed_scaling_factor: float = 1.0,
    conv_L_cache: int = 3,
    conv_bias: bool = False,
    norm_eps: float = 1e-5,
    rope_theta: float = 1e6,
    tie_word_embeddings: bool = True,
    param_dtype: str = "bfloat16",
    compute_dtype: str = "bfloat16",
    **published,
) -> ModelDef:
    """Defaults are LFM2-8B-A1B's published sizes.  ``seq_len`` is the fixed
    record length ``T``.  Keys of the published config that change no shape
    and no arithmetic here (``model_type``, ``max_position_embeddings``, ...)
    are accepted and kept in ``config``."""
    config = {k: v for k, v in locals().items() if k != "published"} | published
    layer_types = tuple(layer_types)
    if len(layer_types) != num_hidden_layers or set(layer_types) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types names {len(layer_types)} layers of kinds {set(layer_types)}, "
                         f"num_hidden_layers is {num_hidden_layers}")
    if num_attention_heads % num_key_value_heads or hidden_size % num_attention_heads:
        raise ValueError("query heads must divide the hidden size and over the key/value heads")
    if not (norm_topk_prob and use_expert_bias and tie_word_embeddings) or conv_bias:
        raise ValueError("built as published: normalised top-k weights, a selection bias, a tied "
                         "head and no bias in the convolution")
    if num_experts > 127 or not 0 <= num_dense_layers < num_hidden_layers:
        raise ValueError("routing is int8, over at least one routed layer")
    d, layers, taps = hidden_size, num_hidden_layers, conv_L_cache
    head_dim = d // num_attention_heads
    q_dim, kv_dim = d, num_key_value_heads * head_dim
    pdt, cdt = jnp.dtype(param_dtype), jnp.dtype(compute_dtype)
    exact = jax.lax.Precision.HIGHEST if cdt == F32 else None

    def dot(x, w):
        return jnp.dot(x.astype(cdt), w.astype(cdt), precision=exact, preferred_element_type=F32)

    def init_fn(rng):
        keys = iter(jax.random.split(rng, 1 + 8 * layers))

        def dense(shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, F32) * (gain / math.sqrt(shape[-2]))).astype(pdt)

        ones = lambda n: jnp.ones((n,), pdt)  # noqa: E731
        params = {"embed": (jax.random.normal(next(keys), (vocab_size, d), F32) * 0.1).astype(pdt),
                  "layers": [], "norm_f": ones(d)}
        for i, kind in enumerate(layer_types):
            layer = {"norm_op": ones(d), "norm_ff": ones(d)}
            if kind == "conv":
                layer["conv"] = {"in_proj": dense((d, 3 * d)), "conv_w": dense((taps, d)),
                                 "out_proj": dense((d, d))}
            else:
                layer["attn"] = {"wq": dense((d, q_dim)), "wk": dense((d, kv_dim)), "wv": dense((d, kv_dim)),
                                 "wo": dense((q_dim, d)), "q_norm": ones(head_dim), "k_norm": ones(head_dim)}
            if i < num_dense_layers:
                layer["mlp"] = {"w1": dense((d, intermediate_size)), "w3": dense((d, intermediate_size)),
                                "w2": dense((intermediate_size, d))}
            else:
                layer["moe"] = {"router": dense((d, num_experts)), "bias": jnp.zeros((num_experts,), pdt),
                                "w13": dense((num_experts, d, 2 * moe_intermediate_size)),
                                "w2": dense((num_experts, moe_intermediate_size, d))}
            params["layers"].append(layer)
        return params

    def short_conv(p, u):
        with jax.named_scope("short_conv"):
            b_, c_, z = jnp.split(dot(u, p["in_proj"]), 3, axis=-1)
            y = c_ * causal_conv1d(b_ * z, p["conv_w"].astype(F32), jnp.zeros((d,), F32))
            return dot(y, p["out_proj"])

    def attention(p, u):
        with jax.named_scope("attention"):
            b, t, _ = u.shape
            q = dot(u, p["wq"]).reshape(b, t, num_attention_heads, head_dim)
            k = dot(u, p["wk"]).reshape(b, t, num_key_value_heads, head_dim)
            v = dot(u, p["wv"]).reshape(b, t, num_key_value_heads, head_dim)
            q = _rope(_rms_norm(q, p["q_norm"], norm_eps), float(rope_theta))
            k = _rope(_rms_norm(k, p["k_norm"], norm_eps), float(rope_theta))
            out = flash_attention(q.astype(cdt), k.astype(cdt), v.astype(cdt), causal=True)
            return dot(out.reshape(b, t, q_dim), p["wo"])

    def mlp(p, x):
        with jax.named_scope("mlp"):
            return dot(jax.nn.silu(dot(x, p["w1"])) * dot(x, p["w3"]), p["w2"])

    def serve(params, inputs):
        tokens = inputs["tokens"]  # [B, T] int32
        h = params["embed"][tokens].astype(F32)
        routing, rows, rows_max = [], 0, 0
        for p in params["layers"]:
            u = _rms_norm(h, p["norm_op"], norm_eps)
            h = h + (short_conv(p["conv"], u) if "conv" in p else attention(p["attn"], u))
            x = _rms_norm(h, p["norm_ff"], norm_eps)
            if "mlp" in p:
                h = h + mlp(p["mlp"], x)
            else:
                moe = p["moe"]
                routed = routed_experts(x, moe["router"], moe["bias"], moe["w13"], moe["w2"],
                                        k=num_experts_per_tok, scaling=routed_scaling_factor,
                                        compute_dtype=cdt)
                h = h + routed.out
                routing.append(routed.experts)
                rows, rows_max = rows + routed.rows, rows_max + routed.rows_max
        with jax.named_scope("head"):
            last = _rms_norm(h[:, -1], params["norm_f"], norm_eps)
            logits = jax.lax.dot_general(last.astype(cdt), params["embed"].astype(cdt),
                                         (((1,), (1,)), ((), ())), precision=exact,
                                         preferred_element_type=F32)
        prob = jax.nn.softmax(logits, axis=-1)
        return {"logits": logits,
                "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "score": jnp.max(prob, axis=-1),
                "routing": jnp.stack(routing, axis=2).astype(jnp.int8),
                "expert_rows": rows, "expert_rows_max": rows_max}

    schema = RecordSchema({"tokens": TensorSpec((seq_len,), np.int32)})
    methods = {"serve": ModelMethod(name="serve", input_schema=schema,
                                    output_names=("logits", "label", "score", "routing"), fn=serve,
                                    count_names=("expert_rows", "expert_rows_max"),
                                    compute_dtype=cdt)}
    return ModelDef(architecture="lfm2_moe", config=config, module=None, input_schema=schema,
                    methods=methods, init_fn=init_fn)
