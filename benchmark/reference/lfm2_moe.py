"""LFM2-MoE (``model_type: lfm2_moe``) as plain ``jax.numpy``: float32 at
``Precision.HIGHEST``, one record and one layer at a time, no kernel, no sort,
no batching; an expert's rows are picked on the host, one expert at a time.
Imports nothing of the program.  Source: the model's public ``config.json``
(its keys are this module's ``model`` dict); the gated short convolution, the
attention and the dense MLP as ``transformers/models/lfm2/modeling_lfm2.py``
(4.57.6) has them; the router from the config's own keys.

Layer ``i``: ``x += op_i(norm_op(x)); x += ff_i(norm_ff(x))``.

- ``op_i``, ``layer_types[i] == "conv"``: ``B, C, z = split3(u W_in)``;
  ``C * causal_conv1d(B * z)`` (depthwise, ``conv_L_cache`` taps, zeros before
  the first position, no bias, no activation); ``W_out``.
- ``op_i``, ``"full_attention"``: ``q``, ``k`` rms-normed over the head
  (``q_norm``, ``k_norm``), rotate-half RoPE over the whole head, causal
  softmax attention scaled by ``head_dim ** -0.5``, query head ``h`` on
  key/value head ``h // (heads / kv heads)``; ``W_o``.
- ``ff_i``, ``i < num_dense_layers``: ``w2(silu(w1 x) * w3 x)``.
- ``ff_i`` after that, a token: ``s = sigmoid(x W_r)``; ``sel = top_k(s +
  b)``, ties to the lower index; ``w = s[sel] / (sum(s[sel]) + 1e-6) *
  routed_scaling_factor``; ``sum_j w_j * W2_e(silu(W1_e x) * W3_e x)`` over
  ``e = sel_j``.  ``b`` chooses and never weighs.  An expert's ``W1 | W3`` is
  one stored leaf, ``w13[e]``, gate first.
- after the last layer ``norm_f``, then the head on the last position: the
  embedding transposed.

Departures from the published implementation, all of them: the head runs on
the last position alone; the head is tied (the family's default, the config
does not say); the 1e-6 in ``w`` (the config does not say); ``W1 | W3`` stored
side by side; weights are random (``make_params``, ``spreads``).

``routing=`` holds the experts to given ids (the program's): the weights are
still this reference's own, from its own scores at those ids.  A top-k choice
is discontinuous, so two computations that differ by a rounding choose
differently in a few pairs of a hundred, and each such pair moves a quarter
of a layer's term; held to one routing they are compared on what the routing
leaves, and ``routed=`` receives how far the given ids lie from this
reference's own choice.  ``quant`` rounds the operands of every contraction
but the router's (which the precision statement holds to float32) to a
narrower type; ``fault`` plants one of ``FAULTS``.
"""

from __future__ import annotations

import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.falcon_h1 import HIGHEST, _dot, _draw, _norm, _rope, _rounder, key_of

FAULTS = ("no_selection_bias", "weights_not_normalised", "no_conv", "no_qk_norm", "expert_zeroed")
#: The expert that ``fault="expert_zeroed"`` silences in every layer.
ZEROED_EXPERT = 0
#: An expert's rows are padded to a multiple of this, so that one expert's
#: product compiles for a few shapes and not for every count.
ROW_BUCKET = 512
#: rms of ``silu(g) * u`` for normal ``g``, ``u`` of spreads (1.5, 1) and
#: (1.25, 1.25); of a sum of ``k`` = 4 independent unit terms under the
#: router's weights (near 0.25 each: the root of the sum of their squares).
GATED_RMS, EXPERT_GATED_RMS, COMBINED_RMS = 0.958, 0.966, 0.501
#: rms of the attention's output before ``W_o`` with the spreads below, read
#: off this reference at the published widths and 4,096 positions.
ATTENTION_RMS = 0.33
#: The embedding's spread.  The head is tied, so an embedding of spread 1 in a
#: residual of rms 5.4 after 14 layers would give the record's last token a
#: logit 8 spreads above the rest, and label and score would test nothing.
EMBED_SPREAD = 0.1


def sizes(model: dict) -> dict:
    hd = model["hidden_size"] // model["num_attention_heads"]
    kinds = list(model["layer_types"])
    return {"head_dim": hd, "q": model["hidden_size"], "kv": model["num_key_value_heads"] * hd,
            "conv_layers": kinds.count("conv"), "attention_layers": kinds.count("full_attention"),
            "expert_layers": model["num_hidden_layers"] - model["num_dense_layers"]}


def leaf_shapes(model: dict) -> dict:
    """{name: shape} of every weight; a matrix is ``[in, out]``."""
    d, s = model["hidden_size"], sizes(model)
    inter, f, experts = model["intermediate_size"], model["moe_intermediate_size"], model["num_experts"]
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    shapes = {"embed": (model["vocab_size"], d), "norm_f": (d,)}
    for i, kind in enumerate(model["layer_types"]):
        layer = {"norm_op": (d,), "norm_ff": (d,)}
        if kind == "conv":
            layer.update({"conv.in_proj": (d, 3 * d), "conv.conv_w": (model["conv_L_cache"], d),
                          "conv.out_proj": (d, d)})
        else:
            layer.update({"attn.wq": (d, s["q"]), "attn.wk": (d, s["kv"]), "attn.wv": (d, s["kv"]),
                          "attn.wo": (s["q"], d), "attn.q_norm": (s["head_dim"],),
                          "attn.k_norm": (s["head_dim"],)})
        if i < model["num_dense_layers"]:
            layer.update({"mlp.w1": (d, inter), "mlp.w3": (d, inter), "mlp.w2": (inter, d)})
        else:
            layer.update({"moe.router": (d, experts), "moe.bias": (experts,),
                          "moe.w13": (experts, d, 2 * f), "moe.w2": (experts, f, d)})
        shapes.update({f"layers.{i}.{name}": shape for name, shape in layer.items()})
    return shapes


def spreads(model: dict) -> dict:
    """{leaf's name within a layer, or top-level name: ("normal", mean,
    spread)}.  By Falcon's rule: every operator and every feed-forward adds a
    term of rms 1 to the residual.  The residual starts at ``EMBED_SPREAD``
    (see there); every branch reads it through a norm, so nothing else scales
    with it."""
    d, s = model["hidden_size"], sizes(model)
    fan = 1.0 / math.sqrt(d)
    return {
        "embed": ("normal", 0.0, EMBED_SPREAD),
        # Tied head: the final norm's weights carry the logits' spread of 2.5.
        "norm_f": ("normal", 2.5 * fan / EMBED_SPREAD, 0.25 * fan / EMBED_SPREAD),
        "norm_op": ("normal", 1.0, 0.1), "norm_ff": ("normal", 1.0, 0.1),
        # B, C, z of rms 1; three taps of spread 3 ** -0.5; the gated product has rms 1.
        "conv.in_proj": ("normal", 0.0, fan),
        "conv.conv_w": ("normal", 0.0, 1.0 / math.sqrt(model["conv_L_cache"])),
        "conv.out_proj": ("normal", 0.0, fan),
        # Queries and keys are unit after their norms, so the norms' weights
        # carry the scores' spread: queries of rms 2 on keys of rms 1.5, scores
        # of spread 3 (head_dim ** 0.5 * 2 * 1.5 / head_dim ** 0.5); values of rms 1.
        "attn.wq": ("normal", 0.0, fan), "attn.wk": ("normal", 0.0, fan), "attn.wv": ("normal", 0.0, fan),
        "attn.q_norm": ("normal", 2.0, 0.2), "attn.k_norm": ("normal", 1.5, 0.15),
        "attn.wo": ("normal", 0.0, 1.0 / (math.sqrt(s["q"]) * ATTENTION_RMS)),
        "mlp.w1": ("normal", 0.0, 1.5 * fan), "mlp.w3": ("normal", 0.0, fan),
        "mlp.w2": ("normal", 0.0, 1.0 / (math.sqrt(model["intermediate_size"]) * GATED_RMS)),
        # Router scores of spread 1.5 before the sigmoid; a selection bias of spread 0.05.
        "moe.router": ("normal", 0.0, 1.5 * fan), "moe.bias": ("normal", 0.0, 0.05),
        # Gate and up are one leaf, so one spread: 1.25 each.
        "moe.w13": ("normal", 0.0, 1.25 * fan),
        "moe.w2": ("normal", 0.0, 1.0 / (math.sqrt(model["moe_intermediate_size"])
                                         * EXPERT_GATED_RMS * COMBINED_RMS)),
    }


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
    """``int32[records, length]``: ids Zipf with exponent 1 over the whole
    vocabulary (rank r with probability ~ 1 / r), rank to id by a seeded
    permutation.  Text is Zipfian, and a hot token takes the same experts in
    the early layers, which is what makes routing uneven."""
    rng = np.random.default_rng(int(seed))
    vocab = model["vocab_size"]
    id_of_rank = rng.permutation(vocab).astype(np.int32)
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
    ranks = np.searchsorted(cdf, rng.random((records, length)) * cdf[-1])
    return id_of_rank[np.minimum(ranks, vocab - 1)]


# -- the forward pass -------------------------------------------------------

def _short_conv(p, u, model, q_, fault):
    t, dot = u.shape[0], _dot(q_)
    b_, c_, z = jnp.split(dot(u, p["conv.in_proj"]), 3, axis=-1)
    y = b_ * z
    if fault != "no_conv":
        taps = model["conv_L_cache"]
        padded = jnp.pad(y, ((taps - 1, 0), (0, 0)))
        y = sum(padded[k:k + t] * p["conv.conv_w"][k] for k in range(taps))
    return dot(c_ * y, p["conv.out_proj"])


def _attention(p, u, model, q_, fault):
    t, dot, s = u.shape[0], _dot(q_), sizes(model)
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], s["head_dim"]
    q = dot(u, p["attn.wq"]).reshape(t, heads, hd)
    k = dot(u, p["attn.wk"]).reshape(t, kv, hd)
    v = dot(u, p["attn.wv"]).reshape(t, kv, hd)
    if fault != "no_qk_norm":
        q, k = _norm(q, p["attn.q_norm"], model["norm_eps"]), _norm(k, p["attn.k_norm"], model["norm_eps"])
    q, k = _rope(q, float(model["rope_theta"])), _rope(k, float(model["rope_theta"]))
    causal = jnp.tril(jnp.ones((t, t), bool))

    def group(args):  # one key/value head and the query heads that read it
        qg, kg, vg = args  # [T, heads // kv, hd], [T, hd], [T, hd]
        scores = jnp.einsum("tgd,sd->gts", q_(qg), q_(kg), precision=HIGHEST) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", q_(w), q_(vg), precision=HIGHEST)

    out = lax.map(group, (q.reshape(t, kv, heads // kv, hd).transpose(1, 0, 2, 3),
                          k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return dot(out.transpose(1, 0, 2, 3).reshape(t, heads * hd), p["attn.wo"])


def _rms(a):
    return jnp.sqrt(jnp.mean(jnp.square(a)))


def _widened(p):
    return {name: w.astype(jnp.float32) for name, w in p.items()}


@functools.lru_cache(maxsize=32)
def _compiled(model_json: str, quant, fault):
    model = json.loads(model_json)
    q_, eps, k = _rounder(quant), model["norm_eps"], model["num_experts_per_tok"]
    dot = _dot(q_)

    def operator(p, h):
        """``h + op(norm_op(h))`` and its normed form for the feed-forward."""
        p = _widened(p)
        u = _norm(h, p["norm_op"], eps)
        added = (_short_conv if "conv.in_proj" in p else _attention)(p, u, model, q_, fault)
        h = h + added
        return h, _norm(h, p["norm_ff"], eps), _rms(added)

    def dense_ff(p, h, x):
        p = _widened(p)
        added = dot(jax.nn.silu(dot(x, p["mlp.w1"])) * dot(x, p["mlp.w3"]), p["mlp.w2"])
        return h + added, _rms(added)

    def scores(router, bias, x):
        """The router's scores and what it selects by: never rounded."""
        s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32), precision=HIGHEST))
        by = s if fault == "no_selection_bias" else s + bias.astype(jnp.float32)
        return s, by, lax.top_k(by, k)[1]

    def weights(s, sel):
        picked = jnp.take_along_axis(s, sel, axis=-1)
        if fault == "weights_not_normalised":
            return picked
        return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * model["routed_scaling_factor"]

    def expert(out, x, w13, w2, rows, weight):
        """``out`` with one expert's weighted output added at ``rows`` (padding
        rows carry the weight 0)."""
        f = w2.shape[0]
        both = dot(x[rows], w13.astype(jnp.float32))
        y = dot(jax.nn.silu(both[:, :f]) * both[:, f:], w2.astype(jnp.float32))
        return out.at[rows].add(y * weight[:, None])

    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    def head(norm_f, table, h_last):
        x = _norm(h_last, norm_f.astype(jnp.float32), eps)
        return dot(x, table.astype(jnp.float32).T)

    return {name: jax.jit(fn) for name, fn in dict(
        operator=operator, dense_ff=dense_ff, scores=scores, weights=weights, expert=expert,
        embed=embed, head=head).items()}


def _held_against_own(by, given, k, delta):
    """How far the given experts lie from the reference's own choice: for each
    pair the shortfall of the given expert's selection score under the
    reference's own ``k``-th best.  (wrong, near, largest shortfall): pairs
    more than ``delta`` under it, or naming one expert twice; pairs under it
    by ``delta`` at most."""
    by, given = np.asarray(by, np.float64), np.asarray(given)
    kth = np.sort(by, axis=1)[:, -k]
    short = kth[:, None] - np.take_along_axis(by, given, axis=1)
    ordered = np.sort(given, axis=1)
    twice = (ordered[:, 1:] == ordered[:, :-1]).any(axis=1)
    wrong = (short > delta) | twice[:, None]
    return int(wrong.sum()), int(((short > 0) & ~wrong).sum()), float(max(short.max(), 0.0))


def _routed_ff(fns, p, h, x, model, fault, given, delta):
    """The routed layer on one record; ``given``: ``[T, k]`` expert ids or None."""
    k = model["num_experts_per_tok"]
    s, by, own = fns["scores"](p["moe.router"], p["moe.bias"], x)
    seen = None
    if given is None:
        sel = np.asarray(own)
    else:
        sel = np.asarray(given).astype(np.int32)
        seen = _held_against_own(by, sel, k, delta)
    weight = np.asarray(fns["weights"](s, jnp.asarray(sel)))
    out = jnp.zeros_like(h)
    for e in range(model["num_experts"]):
        rows, slot = np.nonzero(sel == e)
        if not len(rows) or (fault == "expert_zeroed" and e == ZEROED_EXPERT):
            continue
        padded = -len(rows) % ROW_BUCKET
        out = fns["expert"](out, x, p["moe.w13"][e], p["moe.w2"][e],
                            np.pad(rows, (0, padded)), np.pad(weight[rows, slot], (0, padded)))
    return h + out, sel, seen, _rms(out)


def forward(params: dict, tokens, model: dict, *, quant=None, fault=None, rms=None,
            routing=None, routed=None, chosen=None, routing_delta=0.0):
    """Logits ``float32[N, vocab]`` after the last position of each of the
    ``N`` sequences of ``tokens`` (``int[N, T]``).

    ``routing`` (``int[N, T, expert layers, k]``) holds the experts to the
    given ids; ``routed``, a list, then receives for each record ``{"pairs",
    "wrong", "near", "gap_max"}`` over its (token, layer, slot) pairs
    (:func:`_held_against_own`, with ``routing_delta``).  ``chosen``, a list,
    receives each record's experts as used (``int8[T, expert layers, k]``).
    ``rms``, a list, receives for each record and layer the rms of the
    residual and of the two terms added to it."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    fns = _compiled(json.dumps(model, sort_keys=True), quant, fault)
    dense = model["num_dense_layers"]
    out = []
    for n, row in enumerate(np.asarray(tokens)):
        h = fns["embed"](params["embed"], row)
        used, seen = [], []
        for i in range(model["num_hidden_layers"]):
            prefix = f"layers.{i}."
            p = {name[len(prefix):]: w for name, w in params.items() if name.startswith(prefix)}
            ff = {name: w for name, w in p.items() if name.startswith(("mlp.", "moe."))}
            h, x, op_rms = fns["operator"]({name: w for name, w in p.items() if name not in ff}, h)
            if i < dense:
                h, ff_rms = fns["dense_ff"](ff, h, x)
            else:
                given = None if routing is None else np.asarray(routing)[n, :, i - dense]
                h, sel, held, ff_rms = _routed_ff(fns, ff, h, x, model, fault, given, routing_delta)
                used.append(sel)
                seen.append(held)
            if rms is not None:
                rms.append({"residual": float(_rms(h)), "op": float(op_rms), "ff": float(ff_rms)})
        out.append(fns["head"](params["norm_f"], params["embed"], h[-1]))
        if chosen is not None:
            chosen.append(np.stack(used, axis=1).astype(np.int8))
        if routed is not None and routing is not None:
            routed.append({"pairs": sum(s.size for s in used), "wrong": sum(s[0] for s in seen),
                           "near": sum(s[1] for s in seen), "gap_max": max(s[2] for s in seen)})
    return jnp.stack(out)


# -- work from shapes ---------------------------------------------------------

def forward_flops(model: dict, tokens: int) -> int:
    """Operations of one record's forward pass over ``tokens`` positions, two a
    multiply-add: every matrix product (of the experts the ``k`` chosen ones a
    token, not all), the routers, causal attention on the lower triangle, the
    head on one position.  Not counted: the conv's taps and gates (10
    operations a channel a position), norms, RoPE, the sort."""
    d, s = model["hidden_size"], sizes(model)
    operators = s["conv_layers"] * 4 * d * d + s["attention_layers"] * (d * (s["q"] + 2 * s["kv"]) + s["q"] * d)
    attention = s["attention_layers"] * 2 * s["q"] * (tokens * (tokens + 1) // 2)
    dense = model["num_dense_layers"] * 3 * d * model["intermediate_size"]
    routed = s["expert_layers"] * (model["num_experts_per_tok"] * 3 * d * model["moe_intermediate_size"]
                                   + d * model["num_experts"])
    return 2 * (tokens * (operators + dense + routed) + attention + d * model["vocab_size"])


def attention_kernel_cost(model: dict, tokens: int, batch: int):
    """(operations, bytes) of one call of a causal grouped-query attention
    kernel over ``batch`` sequences of ``tokens`` positions: Q K^T and P V on
    the lower triangle, two operations a multiply-add; q read and the output
    written once, each key/value head read once, in bfloat16."""
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], sizes(model)["head_dim"]
    flops = 2 * 2 * batch * heads * hd * (tokens * (tokens + 1) // 2)
    moved = 2 * batch * tokens * hd * (2 * heads + 2 * kv)
    return flops, moved


def expert_kernel_cost(model: dict, tokens: int, batch: int):
    """(operations, bytes) of ONE routed layer's two grouped products over
    ``batch`` sequences of ``tokens`` positions: ``k`` rows a token through
    ``W1 | W3`` and ``W2``, two operations a multiply-add; every expert's
    weights read once, the rows read once and written once, in bfloat16 (what
    passes between the two products need never leave the chip)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    rows = tokens * batch * model["num_experts_per_tok"]
    flops = 2 * rows * 3 * d * f
    moved = 2 * (model["num_experts"] * 3 * d * f + 2 * rows * d)
    return flops, moved
