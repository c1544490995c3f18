"""Falcon-H1 (``model_type: falcon_h1``) as plain ``jax.numpy``: float32 at
``Precision.HIGHEST``, one record and one layer at a time, the state-space
scan as the recurrence it is, position by position.  Imports nothing of the
program.  Source: the model's public ``config.json`` (its keys are this
module's ``model`` dict) and the Falcon-H1 report's block: every layer runs a
Mamba-2 mixer and grouped-query attention side by side on the same normed
input, adds both to the residual, then a gated MLP.

Departures from the published implementation, all of them:

- the published code scans in chunks (``mamba_chunk_size``); the recurrence
  here has no chunk, which is the point of it.  ``fault="state_dropped"``
  alone reads the chunk size;
- the muP multipliers of the SSM's input projection are applied to the
  projection's output (the published code folds them into a vector it calls
  ``mup_vector``: the same product);
- ``num_logits_to_keep: 1`` is taken at its word: the head runs on the last
  position alone;
- weights are random (``make_params``), each leaf's spread chosen so that
  the three branches of a layer each add a term of rms 1 to a residual that
  starts at rms 1: with fan-in scaling alone the published multipliers
  (0.0078-0.35) would leave them a thousandth of it, and no error in them
  would reach the logits.  ``spreads()`` says how; ``A``, ``dt`` and ``D`` are
  drawn as Mamba-2 initialises them.

The weights arrive in bfloat16 (as the program holds them) and are widened a
layer at a time, so that a 34B-wide layer fits beside the stored tree.
``quant`` rounds the operands of every contraction to a narrower type,
scaled tensor by tensor into the type's range (the low-precision control);
``fault`` plants one of ``FAULTS`` (the controls of a broken mixer).
"""

from __future__ import annotations

import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FAULTS = ("state_dropped", "no_attention", "no_conv")
#: A leaf larger than this is drawn in row blocks, each from its own key, so
#: that no float32 copy of a whole 1.3 G-element table is ever held.
BLOCK_ELEMENTS = 1 << 25


def sizes(model: dict) -> dict:
    """The derived sizes of one layer."""
    groups, state = model["mamba_n_groups"], model["mamba_d_state"]
    d_ssm = model["mamba_d_ssm"]
    return {
        "q": model["num_attention_heads"] * model["head_dim"],
        "kv": model["num_key_value_heads"] * model["head_dim"],
        "d_ssm": d_ssm, "bc": groups * state, "conv": d_ssm + 2 * groups * state,
        "in_proj": 2 * d_ssm + 2 * groups * state + model["mamba_n_heads"],
    }


def leaf_shapes(model: dict) -> dict:
    """{name: shape} of every weight; a matrix is ``[in, out]``."""
    d, inter, vocab = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    heads, s = model["mamba_n_heads"], sizes(model)
    shapes = {"embed": (vocab, d), "norm_f": (d,), "head": (d, vocab)}
    for i in range(model["num_hidden_layers"]):
        shapes.update({f"layers.{i}.{name}": shape for name, shape in {
            "norm_in": (d,), "norm_ff": (d,),
            "attn.wq": (d, s["q"]), "attn.wk": (d, s["kv"]), "attn.wv": (d, s["kv"]),
            "attn.wo": (s["q"], d),
            "ssm.in_proj": (d, s["in_proj"]), "ssm.conv_w": (model["mamba_d_conv"], s["conv"]),
            "ssm.conv_b": (s["conv"],), "ssm.dt_bias": (heads,), "ssm.a_log": (heads,),
            "ssm.d": (heads,), "ssm.norm": (s["d_ssm"],), "ssm.out_proj": (s["d_ssm"], d),
            "mlp.gate": (d, inter), "mlp.up": (d, inter), "mlp.down": (inter, d),
        }.items()})
    return shapes


#: What the rms of ``up(x) * silu(gate(x))`` and of the attention's output come
#: to with the spreads below, read off the reference at the published widths
#: and 4,096 positions (a softmax over thousands of keys averages values that
#: one over a few does not: at 24 positions the second is 0.75).
GATED_RMS, ATTENTION_RMS = 0.97, 0.40


def spreads(model: dict) -> dict:
    """{leaf's name within a layer, or top-level name: (distribution, a, b)}:
    ``normal`` with mean a and spread b, ``log_of_uniform`` the logarithm of a
    uniform draw on [a, b), ``dt_bias`` the inverse softplus of a dt drawn
    log-uniformly on [a, b)."""
    d, s = model["hidden_size"], sizes(model)
    fan = 1.0 / math.sqrt(d)
    ssm_m, mlp_m = model["ssm_multipliers"], model["mlp_multipliers"]
    attn_in = model["attention_in_multiplier"]
    return {
        # The residual starts at rms 1.
        "embed": ("normal", 0.0, 1.0 / model["embedding_multiplier"]),
        "norm_f": ("normal", 1.0, 0.1), "norm_in": ("normal", 1.0, 0.1),
        "norm_ff": ("normal", 1.0, 0.1), "ssm.norm": ("normal", 1.0, 0.1),
        # Logits of spread 2.5 over the vocabulary.
        "head": ("normal", 0.0, 2.5 * fan / model["lm_head_multiplier"]),
        # Queries of rms 2, keys of rms 1.5: scores of spread 3, a softmax that
        # picks a few keys out of thousands; values of rms 1.
        "attn.wq": ("normal", 0.0, 2.0 * fan / attn_in),
        "attn.wk": ("normal", 0.0, 1.5 * fan / (attn_in * model["key_multiplier"])),
        "attn.wv": ("normal", 0.0, fan / attn_in),
        "attn.wo": ("normal", 0.0, 1.0 / (math.sqrt(s["q"]) * ATTENTION_RMS
                                          * model["attention_out_multiplier"])),
        # x before the conv has rms 1; z, B, C and dt follow by their multipliers
        # (1.41, 0.71, 2, 1.41 at the published ones).
        "ssm.in_proj": ("normal", 0.0, fan / (model["ssm_in_multiplier"] * ssm_m[1])),
        "ssm.conv_w": ("normal", 0.0, 1.0 / math.sqrt(model["mamba_d_conv"])),
        "ssm.conv_b": ("normal", 0.0, 0.1),
        # Mamba-2's own: dt log-uniform on [0.001, 0.1), A uniform on [1, 16), D one.
        "ssm.dt_bias": ("dt_bias", 0.001, 0.1), "ssm.a_log": ("log_of_uniform", 1.0, 16.0),
        "ssm.d": ("normal", 1.0, 0.0),
        # The gated norm leaves rms 1, so this alone sets the branch's rms.
        "ssm.out_proj": ("normal", 0.0, 1.0 / (math.sqrt(s["d_ssm"]) * model["ssm_out_multiplier"])),
        "mlp.gate": ("normal", 0.0, 1.5 * fan / mlp_m[0]), "mlp.up": ("normal", 0.0, fan),
        "mlp.down": ("normal", 0.0, 1.0 / (math.sqrt(model["intermediate_size"]) * GATED_RMS
                                            * mlp_m[1])),
    }


def key_of(seed):
    """A key from any whole number up to a little over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("shape", "rule"))
def _draw(key, shape, rule):
    dist, a, b = rule
    if dist == "normal":
        rows = shape[0]
        per = max(1, min(rows, BLOCK_ELEMENTS // max(math.prod(shape[1:]), 1)))
        while rows % per:
            per -= 1
        keys = jax.random.split(key, rows // per)
        block = lambda k: (a + b * jax.random.normal(k, (per, *shape[1:]), jnp.float32)  # noqa: E731
                           ).astype(jnp.bfloat16)
        return lax.map(block, keys).reshape(shape)
    u = jax.random.uniform(key, shape, jnp.float32)
    if dist == "log_of_uniform":
        leaf = jnp.log(a + u * (b - a))
    elif dist == "dt_bias":
        dt = jnp.exp(math.log(a) + u * (math.log(b) - math.log(a)))
        leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus(leaf) == dt
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return leaf.astype(jnp.bfloat16)


def make_params(model: dict, seed) -> dict:
    """{name: bfloat16 leaf}, each from the seed's key folded with its own
    name, made on the default device, the large tables first."""
    rules, key = spreads(model), key_of(seed)
    shapes = leaf_shapes(model)
    out = {}
    for name in sorted(shapes, key=lambda n: -math.prod(shapes[n])):
        rule = rules[name.split(".", 2)[-1] if name.startswith("layers.") else name]
        out[name] = _draw(jax.random.fold_in(key, zlib.crc32(name.encode())), shapes[name], rule)
    return out


def make_tokens(model: dict, records: int, length: int, seed):
    """``int32[records, length]``, uniform over the whole vocabulary."""
    import numpy as np

    return np.random.default_rng(int(seed)).integers(
        0, model["vocab_size"], (records, length), dtype=np.int32)


# -- the forward pass -------------------------------------------------------

def _rounder(quant):
    if quant is None:
        return lambda a: a
    dtype = jnp.dtype(quant)
    top = float(jnp.finfo(dtype).max)

    def rounded(a):
        scale = jnp.max(jnp.abs(a)) / top
        scale = jnp.where(scale > 0, scale, 1.0)
        return (a / scale).astype(dtype).astype(jnp.float32) * scale

    return rounded


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half over the whole head; ``x`` is ``[T, heads, head_dim]``."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + half * sin


def _dot(q_):
    """A matrix product whose operands pass through the rounder first."""
    return lambda a, w: jnp.dot(q_(a), q_(w), precision=HIGHEST)


def _attention(p, x, model, q_):
    t, dot = x.shape[0], _dot(q_)
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    q = dot(x, p["attn.wq"]).reshape(t, heads, hd)
    k = (dot(x, p["attn.wk"]) * model["key_multiplier"]).reshape(t, kv, hd)
    v = dot(x, p["attn.wv"]).reshape(t, kv, hd)
    q, k = _rope(q, float(model["rope_theta"])), _rope(k, float(model["rope_theta"]))
    causal = jnp.tril(jnp.ones((t, t), bool))

    def group(args):  # one key/value head and the query heads that read it
        qg, kg, vg = args  # [T, heads // kv, hd], [T, hd], [T, hd]
        s = jnp.einsum("tgd,sd->gts", q_(qg), q_(kg), precision=HIGHEST) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", q_(w), q_(vg), precision=HIGHEST)

    out = lax.map(group, (q.reshape(t, kv, heads // kv, hd).transpose(1, 0, 2, 3),
                          k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return dot(out.transpose(1, 0, 2, 3).reshape(t, heads * hd), p["attn.wo"])


def _mamba2(p, x, model, q_, fault):
    t, s, dot = x.shape[0], sizes(model), _dot(q_)
    heads, hd = model["mamba_n_heads"], model["mamba_d_head"]
    groups, n = model["mamba_n_groups"], model["mamba_d_state"]
    m = model["ssm_multipliers"]
    zxbcdt = dot(x * model["ssm_in_multiplier"], p["ssm.in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [s["d_ssm"], s["d_ssm"] + s["conv"]], axis=-1)
    z, dt = z * m[0], dt * m[4]
    xbc = xbc * jnp.concatenate([jnp.full((s["d_ssm"],), m[1]), jnp.full((s["bc"],), m[2]),
                                 jnp.full((s["bc"],), m[3])])
    if fault != "no_conv":
        width = model["mamba_d_conv"]
        padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
        xbc = sum(padded[k:k + t] * p["ssm.conv_w"][k] for k in range(width)) + p["ssm.conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, b, c = jnp.split(xbc, [s["d_ssm"], s["d_ssm"] + s["bc"]], axis=-1)
    xs = xs.reshape(t, heads, hd)
    b = jnp.repeat(b.reshape(t, groups, n), heads // groups, axis=1)  # head j reads group j // 16
    c = jnp.repeat(c.reshape(t, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["ssm.dt_bias"])  # [T, heads]
    decay = jnp.exp(dt * -jnp.exp(p["ssm.a_log"]))
    chunk = model["mamba_chunk_size"]

    def step(state, at):  # state: [heads, head_dim, n]
        x_t, b_t, c_t, dt_t, decay_t, i = at
        if fault == "state_dropped":
            state = jnp.where(i % chunk == 0, 0.0, state)
        state = decay_t[:, None, None] * state + q_(dt_t[:, None] * x_t)[:, :, None] * q_(b_t)[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", q_(state), q_(c_t), precision=HIGHEST)

    _, y = lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32),
                    (xs, b, c, dt, decay, jnp.arange(t)))
    y = (y + p["ssm.d"][:, None] * xs).reshape(t, s["d_ssm"])
    # mamba_rms_norm with norm_before_gate false: gate, then rms-norm each group.
    y = (y * jax.nn.silu(z)).reshape(t, groups, s["d_ssm"] // groups)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + model["rms_norm_eps"])
    return dot(y.reshape(t, s["d_ssm"]) * p["ssm.norm"], p["ssm.out_proj"])


def _layer(p, h, model, quant, fault):
    """One layer on one record: ``h`` is ``[T, hidden]`` float32, ``p`` the
    layer's leaves as stored (bfloat16), widened here."""
    p = {name: w.astype(jnp.float32) for name, w in p.items()}
    q_, eps = _rounder(quant), model["rms_norm_eps"]
    dot = _dot(q_)
    u = _norm(h, p["norm_in"], eps)
    branches = {"ssm": model["ssm_out_multiplier"] * _mamba2(p, u, model, q_, fault),
                "attention": model["attention_out_multiplier"] * _attention(
                    p, u * model["attention_in_multiplier"], model, q_)}
    if fault == "no_attention":
        branches["attention"] = jnp.zeros_like(h)
    h = h + branches["ssm"] + branches["attention"]
    x = _norm(h, p["norm_ff"], eps)
    gate = jax.nn.silu(dot(x, p["mlp.gate"]) * model["mlp_multipliers"][0])
    branches["mlp"] = dot(dot(x, p["mlp.up"]) * gate, p["mlp.down"]) * model["mlp_multipliers"][1]
    rms = lambda a: jnp.sqrt(jnp.mean(jnp.square(a)))  # noqa: E731
    return h + branches["mlp"], {"residual": rms(h), **{k: rms(v) for k, v in branches.items()}}


@functools.lru_cache(maxsize=16)
def _compiled(model_json: str, quant, fault):
    model = json.loads(model_json)
    q_ = _rounder(quant)

    def embed(table, tokens):
        return table[tokens].astype(jnp.float32) * model["embedding_multiplier"]

    def head(norm_f, weight, h_last):
        x = _norm(h_last, norm_f.astype(jnp.float32), model["rms_norm_eps"])
        return _dot(q_)(x, weight.astype(jnp.float32)) * model["lm_head_multiplier"]

    return (jax.jit(embed), jax.jit(lambda p, h: _layer(p, h, model, quant, fault)), jax.jit(head))


def forward(params: dict, tokens, model: dict, *, quant=None, fault=None, rms=None):
    """Logits ``float32[N, vocab]`` after the last position of each of the
    ``N`` sequences of ``tokens`` (``int[N, T]``).  ``rms``, a list, receives
    for each record and layer the rms of the residual and of the three terms
    added to it."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    embed, layer, head = _compiled(json.dumps(model, sort_keys=True), quant, fault)
    out = []
    for row in jnp.asarray(tokens):
        h = embed(params["embed"], row)
        for i in range(model["num_hidden_layers"]):
            prefix = f"layers.{i}."
            h, seen = layer({name[len(prefix):]: w for name, w in params.items()
                             if name.startswith(prefix)}, h)
            if rms is not None:
                rms.append({k: float(v) for k, v in seen.items()})
        out.append(head(params["norm_f"], params["head"], h[-1]))
    return jnp.stack(out)


# -- work from shapes ---------------------------------------------------------

def forward_flops(model: dict, tokens: int) -> int:
    """Operations of one record's forward pass over ``tokens`` positions, two a
    multiply-add: every matrix product; causal attention and the scan's
    within-chunk products on the lower triangle; the scan's chunk states,
    their passing on and their read-out; the head on one position.  Not
    counted: the conv (8 operations a channel a position), norms, gates."""
    d, inter, s = model["hidden_size"], model["intermediate_size"], sizes(model)
    heads, hd = model["mamba_n_heads"], model["mamba_d_head"]
    groups, n, chunk = model["mamba_n_groups"], model["mamba_d_state"], model["mamba_chunk_size"]
    projections = d * (s["q"] + 2 * s["kv"]) + s["q"] * d + d * s["in_proj"] + s["d_ssm"] * d + 3 * d * inter
    triangle = lambda t: t * (t + 1) // 2  # noqa: E731
    attention = 2 * model["num_attention_heads"] * model["head_dim"] * triangle(tokens)
    scan = 0
    for lo in range(0, tokens, chunk):
        length = min(chunk, tokens - lo)
        scan += triangle(length) * (groups * n + heads * hd)  # C B^T, and its product with x
        scan += 2 * length * heads * hd * n                   # the chunk's state, and its read-out
        scan += heads * hd * n                                # passing the state on
    per_layer = tokens * projections + attention + scan
    return 2 * (model["num_hidden_layers"] * per_layer + d * model["vocab_size"])


def attention_kernel_cost(model: dict, tokens: int, batch: int):
    """(operations, bytes) of one call of a causal grouped-query attention
    kernel over ``batch`` sequences of ``tokens`` positions: Q K^T and P V on
    the lower triangle, two operations a multiply-add; q read and the output
    written once, each key/value head read once, in bfloat16."""
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    flops = 2 * 2 * batch * heads * hd * (tokens * (tokens + 1) // 2)
    moved = 2 * batch * tokens * hd * (2 * heads + 2 * kv)
    return flops, moved
