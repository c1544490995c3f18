"""Falcon-H1 — a hybrid block: a Mamba-2 mixer and grouped-query attention
side by side on the same normed input, both added to the residual, then a
gated MLP (``model_type: falcon_h1``; the keywords of :func:`build` are the
keys of the model's public ``config.json``).

The stream path scores a record, one fixed-length sequence of token ids, in
one forward pass: ``serve`` maps ``{"tokens": int32[B, T]}`` to the next-token
distribution after the last position (the config's own
``num_logits_to_keep: 1``): ``logits`` (float32, the whole vocabulary),
``label`` and ``score`` as the image models' ``serve`` gives them.

Precision: parameters are stored in ``param_dtype`` (bfloat16) and go to the
matrix products as they are, with float32 accumulation; activations enter a
product in ``compute_dtype``.  The residual stream, the norms, the softplus,
the scan's decays and passed state (ops/ssd.py), the softmax statistics of
the attention kernel (ops/flash_attention.py) and the logits are float32.

Fourteen muP multipliers of the config scale the embeddings, the five
segments of the SSM's input projection, the keys, both mixers' inputs and
outputs, the MLP's gate and output, and the logits.  Params are a plain
pytree; the layers are not stacked, so a caller's device-resident tree is
used leaf by leaf as it is.
"""

from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from flink_tensorflow_tpu.models.base import ModelMethod
from flink_tensorflow_tpu.models.zoo.registry import ModelDef, register_model_def
from flink_tensorflow_tpu.ops.flash_attention import flash_attention
from flink_tensorflow_tpu.ops.ssd import causal_conv1d, ssd_scan
from flink_tensorflow_tpu.tensors.schema import RecordSchema, TensorSpec

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight.astype(F32)


def _rope(x, theta: float, inv_freq=None, scale: float = 1.0):
    """Rotate-half over the whole head: ``x`` is ``[B, T, heads, head_dim]`` float32.
    Pair ``j`` turns by ``theta^(-2j/head_dim)`` a position, or by ``inv_freq[j]``
    where given (yarn's, ops/mla.py ``yarn_inv_freq``); ``cos`` and ``sin`` are
    times ``scale`` where it is not 1 (yarn's ``attention_factor``)."""
    t, hd = x.shape[1], x.shape[-1]
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    else:
        inv = jnp.asarray(np.asarray(inv_freq, np.float32))
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + half * sin


@register_model_def("falcon_h1")
def build(
    *,
    seq_len: int = 4096,
    vocab_size: int = 261120,
    hidden_size: int = 5120,
    intermediate_size: int = 21504,
    num_hidden_layers: int = 72,
    num_attention_heads: int = 20,
    num_key_value_heads: int = 4,
    head_dim: int = 128,
    mamba_d_ssm: int = 4096,
    mamba_n_heads: int = 32,
    mamba_d_head: int = 128,
    mamba_n_groups: int = 2,
    mamba_d_state: int = 256,
    mamba_d_conv: int = 4,
    mamba_chunk_size: int = 128,
    rms_norm_eps: float = 1e-5,
    rope_theta: float = 1e11,
    embedding_multiplier: float = 5.656854249492381,
    lm_head_multiplier: float = 0.0078125,
    attention_in_multiplier: float = 1.0,
    attention_out_multiplier: float = 0.0375,
    key_multiplier: float = 0.011048543456039804,
    ssm_in_multiplier: float = 0.25,
    ssm_out_multiplier: float = 0.08838834764831845,
    ssm_multipliers: typing.Sequence[float] = (0.3535533905932738, 0.25, 0.1767766952966369,
                                               0.5, 0.3535533905932738),
    mlp_multipliers: typing.Sequence[float] = (0.1767766952966369, 0.011160714285714284),
    param_dtype: str = "bfloat16",
    compute_dtype: str = "bfloat16",
    **published,
) -> ModelDef:
    """Defaults are Falcon-H1-34B's published sizes.  ``seq_len`` is the fixed
    record length ``T``.  Keys of the published config that change no shape
    and no arithmetic here (``model_type``, ``hidden_act``, the bias flags,
    all false or as implemented, ...) are accepted and kept in ``config``."""
    config = {k: v for k, v in locals().items() if k != "published"} | published
    if num_attention_heads % num_key_value_heads or mamba_n_heads % mamba_n_groups:
        raise ValueError("query heads must divide over key/value heads, SSM heads over groups")
    if mamba_n_heads * mamba_d_head != mamba_d_ssm:
        raise ValueError(f"{mamba_n_heads} SSM heads of {mamba_d_head} are not mamba_d_ssm {mamba_d_ssm}")
    if not (len(ssm_multipliers) == 5 and len(mlp_multipliers) == 2):
        raise ValueError("ssm_multipliers scales z, x, B, C, dt; mlp_multipliers the gate and the output")
    d, inter, layers = hidden_size, intermediate_size, num_hidden_layers
    q_dim, kv_dim = num_attention_heads * head_dim, num_key_value_heads * head_dim
    bc_dim = mamba_n_groups * mamba_d_state
    conv_dim = mamba_d_ssm + 2 * bc_dim
    in_proj_dim = 2 * mamba_d_ssm + 2 * bc_dim + mamba_n_heads
    pdt, cdt = jnp.dtype(param_dtype), jnp.dtype(compute_dtype)
    exact = jax.lax.Precision.HIGHEST if cdt == F32 else None
    # Scales the conv's input, segment by segment (x, B, C).
    xbc_scale = np.concatenate([np.full(mamba_d_ssm, ssm_multipliers[1]),
                                np.full(bc_dim, ssm_multipliers[2]),
                                np.full(bc_dim, ssm_multipliers[3])]).astype(np.float32)

    def dot(x, w):
        return jnp.dot(x.astype(cdt), w.astype(cdt), precision=exact, preferred_element_type=F32)

    def init_fn(rng):
        """Fan-in scaling with each multiplier divided out, so that every
        branch is of the residual's order whatever the multipliers are."""
        keys = iter(jax.random.split(rng, 2 + 12 * layers))

        def dense(shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, F32) * (gain / math.sqrt(shape[0]))).astype(pdt)

        ones = lambda n: jnp.ones((n,), pdt)  # noqa: E731
        params = {"embed": (jax.random.normal(next(keys), (vocab_size, d), F32)
                            / embedding_multiplier).astype(pdt),
                  "layers": [], "norm_f": ones(d),
                  "head": dense((d, vocab_size), 1.0 / lm_head_multiplier)}
        for _ in range(layers):
            dt = jnp.exp(jax.random.uniform(next(keys), (mamba_n_heads,), F32,
                                            math.log(1e-3), math.log(1e-1)))
            params["layers"].append({
                "norm_in": ones(d), "norm_ff": ones(d),
                "attn": {"wq": dense((d, q_dim), 1.0 / attention_in_multiplier),
                         "wk": dense((d, kv_dim), 1.0 / (attention_in_multiplier * key_multiplier)),
                         "wv": dense((d, kv_dim), 1.0 / attention_in_multiplier),
                         "wo": dense((q_dim, d), 1.0 / attention_out_multiplier)},
                "ssm": {"in_proj": dense((d, in_proj_dim), 1.0 / (ssm_in_multiplier * ssm_multipliers[1])),
                        "conv_w": dense((mamba_d_conv, conv_dim)),
                        "conv_b": jnp.zeros((conv_dim,), pdt),
                        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
                        "a_log": jnp.log(jax.random.uniform(next(keys), (mamba_n_heads,), F32, 1.0, 16.0)
                                         ).astype(pdt),
                        "d": ones(mamba_n_heads), "norm": ones(mamba_d_ssm),
                        "out_proj": dense((mamba_d_ssm, d), 1.0 / ssm_out_multiplier)},
                "mlp": {"gate": dense((d, inter), 1.0 / mlp_multipliers[0]), "up": dense((d, inter)),
                        "down": dense((inter, d), 1.0 / mlp_multipliers[1])},
            })
        return params

    def attention(p, x):
        with jax.named_scope("attention"):
            b, t, _ = x.shape
            q = dot(x, p["wq"]).reshape(b, t, num_attention_heads, head_dim)
            k = (dot(x, p["wk"]) * key_multiplier).reshape(b, t, num_key_value_heads, head_dim)
            v = dot(x, p["wv"]).reshape(b, t, num_key_value_heads, head_dim)
            q, k = _rope(q, float(rope_theta)), _rope(k, float(rope_theta))
            out = flash_attention(q.astype(cdt), k.astype(cdt), v.astype(cdt), causal=True)
            return dot(out.reshape(b, t, q_dim), p["wo"])

    def mamba2(p, x):
        b, t, _ = x.shape
        zxbcdt = dot(x * ssm_in_multiplier, p["in_proj"])
        z, xbc, dt = jnp.split(zxbcdt, [mamba_d_ssm, mamba_d_ssm + conv_dim], axis=-1)
        xbc = jax.nn.silu(causal_conv1d(xbc * xbc_scale, p["conv_w"].astype(F32),
                                        p["conv_b"].astype(F32)))
        xs, bm, cm = jnp.split(xbc, [mamba_d_ssm, mamba_d_ssm + bc_dim], axis=-1)
        xs = xs.reshape(b, t, mamba_n_heads, mamba_d_head)
        dt = jax.nn.softplus(dt * ssm_multipliers[4] + p["dt_bias"].astype(F32))
        y = ssd_scan(xs, dt, -jnp.exp(p["a_log"].astype(F32)),
                     bm.reshape(b, t, mamba_n_groups, mamba_d_state),
                     cm.reshape(b, t, mamba_n_groups, mamba_d_state),
                     chunk=mamba_chunk_size, compute_dtype=cdt)
        with jax.named_scope("gated_norm"):
            y = (y + p["d"].astype(F32)[:, None] * xs).reshape(b, t, mamba_d_ssm)
            # mamba_rms_norm, norm_before_gate false: gate, then rms-norm each group.
            y = (y * jax.nn.silu(z * ssm_multipliers[0])).reshape(
                b, t, mamba_n_groups, mamba_d_ssm // mamba_n_groups)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + rms_norm_eps)
            y = y.reshape(b, t, mamba_d_ssm) * p["norm"].astype(F32)
        return dot(y, p["out_proj"])

    def mlp(p, x):
        with jax.named_scope("mlp"):
            gate = jax.nn.silu(dot(x, p["gate"]) * mlp_multipliers[0])
            return dot(dot(x, p["up"]) * gate, p["down"]) * mlp_multipliers[1]

    def serve(params, inputs):
        tokens = inputs["tokens"]  # [B, T] int32
        h = params["embed"][tokens].astype(F32) * embedding_multiplier
        for p in params["layers"]:
            u = _rms_norm(h, p["norm_in"], rms_norm_eps)
            h = (h + ssm_out_multiplier * mamba2(p["ssm"], u)
                 + attention_out_multiplier * attention(p["attn"], u * attention_in_multiplier))
            h = h + mlp(p["mlp"], _rms_norm(h, p["norm_ff"], rms_norm_eps))
        with jax.named_scope("head"):
            last = _rms_norm(h[:, -1], params["norm_f"], rms_norm_eps)
            logits = dot(last, params["head"]) * lm_head_multiplier
        prob = jax.nn.softmax(logits, axis=-1)
        return {"logits": logits,
                "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "score": jnp.max(prob, axis=-1)}

    schema = RecordSchema({"tokens": TensorSpec((seq_len,), np.int32)})
    methods = {"serve": ModelMethod(name="serve", input_schema=schema,
                                    output_names=("logits", "label", "score"), fn=serve,
                                    compute_dtype=cdt)}
    return ModelDef(architecture="falcon_h1", config=config, module=None, input_schema=schema,
                    methods=methods, init_fn=init_fn)
