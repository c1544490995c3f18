"""Kimi-K2 (``model_type: kimi_k2``; DeepSeek-V3's layer, key for key) —
latent attention and routed experts beside a shared one.  The keywords of
:func:`build` are the keys of the model's public ``config.json``, plus the
chip's share of an expert-parallel layer.

Layer ``i`` is ``x += attn_i(norm_in(x)); x += ff_i(norm_post(x))``.  ``attn_i``
is latent attention (ops/mla.py: low-rank projections with a norm in the
middle, a rotary part of the key that all heads share, yarn frequencies, heads
of ``qk_nope_head_dim + qk_rope_head_dim`` on keys and ``v_head_dim`` on
values).  ``ff_i`` is a dense gated MLP for the first ``first_k_dense_replace``
layers and after them ``routed(u) + shared(u)``: the routed layer of
ops/moe.py (a sigmoid router with a selection bias, top
``num_experts_per_tok`` of ``router_experts``, weights normalised and times
``routed_scaling_factor``, no token dropped) and a gated MLP of width
``moe_intermediate_size x n_shared_experts`` that every token takes.  The
head is untied.

**The share.**  ``n_routed_experts`` counts the experts HELD here, ``[
first_expert, first_expert + n_routed_experts)`` of the ``router_experts`` the
router chooses among (the published 384; left out: every expert is held).  The
routed layer computes its own experts' part of the result; what the absent
experts would add is left out, and that partial result goes on to the next
layer: a chip's part of an expert-parallel layer without its exchange.
Attention, the router and the shared expert are whole on every chip.

The stream path scores a record, one fixed-length sequence of token ids, in
one forward pass: ``serve`` maps ``{"tokens": int32[B, T]}`` to the next-token
distribution after the last position: ``logits`` (float32, the vocabulary
held), ``label``, ``score``, and ``routing`` (``int16[B, T, expert layers, k]``:
the experts every token chose, held here or not).  It also counts, for the
operator's metrics and never for a record: ``expert_rows`` (a record's (token,
slot) pairs that fell on a held expert, summed over the expert layers),
``expert_rows_max`` (the fullest held expert's rows of the batch, summed
likewise) and ``expert_passes`` (the passes the routed layers took over their
buffers, summed: one a layer while its pairs fit the share's capacity).

Precision: parameters are stored in ``param_dtype`` (bfloat16) and go to the
matrix products as they are, with float32 accumulation; activations enter a
product in ``compute_dtype``.  The residual stream, the norms (the two latent
ones too), RoPE, the router (its product at ``HIGHEST``), the softmax
statistics of the attention kernel and the logits are float32.  Params are a
plain pytree; the layers are not stacked, so a caller's device-resident tree
is used leaf by leaf as it is.
"""

from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from flink_tensorflow_tpu.models.base import ModelMethod
from flink_tensorflow_tpu.models.zoo.registry import ModelDef, register_model_def
from flink_tensorflow_tpu.ops.mla import latent_attention, rms_norm
from flink_tensorflow_tpu.ops.moe import routed_experts
from flink_tensorflow_tpu.tensors.schema import RecordSchema, TensorSpec

F32 = jnp.float32
#: Kimi-K2's published ``rope_scaling``.
_YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
         "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


@register_model_def("kimi_k2")
def build(
    *,
    seq_len: int = 4096,
    vocab_size: int = 163840,
    hidden_size: int = 7168,
    intermediate_size: int = 18432,
    moe_intermediate_size: int = 2048,
    num_hidden_layers: int = 61,
    first_k_dense_replace: int = 1,
    moe_layer_freq: int = 1,
    num_attention_heads: int = 64,
    num_key_value_heads: int = 64,
    q_lora_rank: int = 1536,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    attention_bias: bool = False,
    rope_theta: float = 50000.0,
    rope_scaling: typing.Mapping = _YARN,
    n_routed_experts: int = 384,
    router_experts: typing.Optional[int] = None,
    first_expert: int = 0,
    n_shared_experts: int = 1,
    num_experts_per_tok: int = 8,
    n_group: int = 1,
    topk_group: int = 1,
    topk_method: str = "noaux_tc",
    scoring_func: str = "sigmoid",
    norm_topk_prob: bool = True,
    routed_scaling_factor: float = 2.827,
    hidden_act: str = "silu",
    rms_norm_eps: float = 1e-5,
    tie_word_embeddings: bool = False,
    param_dtype: str = "bfloat16",
    compute_dtype: str = "bfloat16",
    **published,
) -> ModelDef:
    """Defaults are Kimi-K2's published sizes.  ``seq_len`` is the fixed record
    length ``T``.  Keys of the published config that change no shape and no
    arithmetic here (``model_type``, ``max_position_embeddings``, ``ep_size``,
    ...) are accepted and kept in ``config``."""
    config = {k: v for k, v in locals().items() if k != "published"} | published
    router_experts = n_routed_experts if router_experts is None else router_experts
    if (topk_method, scoring_func, hidden_act) != ("noaux_tc", "sigmoid", "silu") or not norm_topk_prob \
            or attention_bias or tie_word_embeddings or moe_layer_freq != 1 or n_shared_experts < 1:
        raise ValueError("built as published: a sigmoid router with a selection bias and normalised "
                         "top-k weights, silu, an untied head, no bias in attention, every layer "
                         "after the dense ones routed, a shared expert")
    if n_group != 1 or topk_group != 1:
        raise ValueError(f"one group of experts (the top-k is over all of them), not n_group {n_group}")
    if num_key_value_heads != num_attention_heads:
        raise ValueError("latent attention widens a key and a value for every query head")
    if not 0 <= first_k_dense_replace < num_hidden_layers:
        raise ValueError("at least one routed layer after the dense ones")
    if not 0 <= first_expert <= router_experts - n_routed_experts or router_experts > 32767:
        raise ValueError(f"experts [{first_expert}, {first_expert + n_routed_experts}) of the router's "
                         f"{router_experts}; routing is int16")
    d, heads, f = hidden_size, num_attention_heads, moe_intermediate_size
    pdt, cdt = jnp.dtype(param_dtype), jnp.dtype(compute_dtype)
    exact = jax.lax.Precision.HIGHEST if cdt == F32 else None

    def dot(x, w):
        return jnp.dot(x.astype(cdt), w.astype(cdt), precision=exact, preferred_element_type=F32)

    def init_fn(rng):
        keys = iter(jax.random.split(rng, 2 + 16 * num_hidden_layers))

        def dense(shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, F32) * (gain / math.sqrt(shape[-2]))).astype(pdt)

        ones = lambda n: jnp.ones((n,), pdt)  # noqa: E731
        gated = lambda width: {"w1": dense((d, width)), "w3": dense((d, width)),  # noqa: E731
                               "w2": dense((width, d))}
        params = {"embed": (jax.random.normal(next(keys), (vocab_size, d), F32)).astype(pdt),
                  "layers": [], "norm_f": ones(d), "head": dense((d, vocab_size))}
        for i in range(num_hidden_layers):
            layer = {"norm_in": ones(d), "norm_post": ones(d), "attn": {
                "q_a": dense((d, q_lora_rank)), "q_a_norm": ones(q_lora_rank),
                "q_b": dense((q_lora_rank, heads * (qk_nope_head_dim + qk_rope_head_dim))),
                "kv_a": dense((d, kv_lora_rank + qk_rope_head_dim)), "kv_a_norm": ones(kv_lora_rank),
                "kv_b": dense((kv_lora_rank, heads * (qk_nope_head_dim + v_head_dim))),
                "o": dense((heads * v_head_dim, d))}}
            if i < first_k_dense_replace:
                layer["mlp"] = gated(intermediate_size)
            else:
                layer["moe"] = {"router": dense((d, router_experts)), "bias": jnp.zeros((router_experts,), pdt),
                                "w13": dense((n_routed_experts, d, 2 * f)),
                                "w2": dense((n_routed_experts, f, d))}
                layer["shared"] = gated(f * n_shared_experts)
            params["layers"].append(layer)
        return params

    def mlp(p, x):
        with jax.named_scope("mlp"):
            return dot(jax.nn.silu(dot(x, p["w1"])) * dot(x, p["w3"]), p["w2"])

    def serve(params, inputs):
        tokens = inputs["tokens"]  # [B, T] int32
        h = params["embed"][tokens].astype(F32)
        routing, rows, rows_max, passes = [], 0, 0, 0
        for p in params["layers"]:
            u = rms_norm(h, p["norm_in"], rms_norm_eps)
            h = h + latent_attention(
                p["attn"], u, num_heads=heads, qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim, rope_theta=rope_theta,
                rope_scaling=rope_scaling, eps=rms_norm_eps, compute_dtype=cdt)
            x = rms_norm(h, p["norm_post"], rms_norm_eps)
            if "mlp" in p:
                h = h + mlp(p["mlp"], x)
            else:
                moe = p["moe"]
                routed = routed_experts(x, moe["router"], moe["bias"], moe["w13"], moe["w2"],
                                        k=num_experts_per_tok, first=first_expert,
                                        scaling=routed_scaling_factor, eps=1e-20, compute_dtype=cdt)
                with jax.named_scope("shared_expert"):
                    h = h + routed.out + mlp(p["shared"], x)
                routing.append(routed.experts)
                rows, rows_max = rows + routed.rows, rows_max + routed.rows_max
                passes = passes + routed.passes
        with jax.named_scope("head"):
            last = rms_norm(h[:, -1], params["norm_f"], rms_norm_eps)
            logits = dot(last, params["head"])
        prob = jax.nn.softmax(logits, axis=-1)
        return {"logits": logits,
                "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "score": jnp.max(prob, axis=-1),
                "routing": jnp.stack(routing, axis=2).astype(jnp.int16),
                "expert_rows": rows, "expert_rows_max": rows_max,
                "expert_passes": jnp.asarray(passes, jnp.int32)}

    schema = RecordSchema({"tokens": TensorSpec((seq_len,), np.int32)})
    methods = {"serve": ModelMethod(name="serve", input_schema=schema,
                                    output_names=("logits", "label", "score", "routing"), fn=serve,
                                    count_names=("expert_rows", "expert_rows_max", "expert_passes"),
                                    compute_dtype=cdt)}
    return ModelDef(architecture="kimi_k2", config=config, module=None, input_schema=schema,
                    methods=methods, init_fn=init_fn)
