"""Mellum 2 (``model_type: mellum``, JetBrains) as plain ``jax.numpy``: float32
at ``Precision.HIGHEST``, one record and one layer at a time, no kernel, no
sort, no batching; attention by plain softmax over blocks of query rows, each
against the keys its band reaches, masked from positions (reference/afmoe.py
``attention``); an expert's rows are picked on the host, one expert at a time.
Imports nothing of the program.  Source: the model's public ``config.json``
(its keys are this module's ``model`` dict), whose keys give every equation
below.

Layer ``i``, ``norm`` an RMS norm with a weight and ``rms_norm_eps``:
``h += attn_i(norm_in(h))``; ``h += routed_i(norm_post(h))``.

- The embedding: ``h0 = E[ids]``, no scale.
- ``attn_i``, ``u`` ``[T, d]``: ``q = u W_q`` (32 heads of 128), ``k = u W_k``,
  ``v = u W_v`` (4 heads each; query head ``n`` reads key/value head ``n //
  8``); no bias, no q/k norm.  Rotate-half RoPE over the whole head on ``q``
  and ``k`` by the section of ``rope_parameters`` that ``layer_types[i]``
  names.  ``"sliding_attention"`` (``rope_type`` default): pair ``j`` turns by
  ``f_j = rope_theta^(-2j/128)`` a position; key ``j`` is seen by query ``i``
  iff ``0 <= i - j < sliding_window``.  ``"full_attention"`` (yarn): pair
  ``j`` turns by ``f_j`` where ``j <= low``, by ``f_j / factor`` where ``j >=
  high`` and by ``f_j ((1 - r) + r / factor)``, ``r = (j - low) / (high -
  low)``, between, with ``low = floor(b(beta_fast))``, ``high =
  ceil(b(beta_slow))`` and ``b(n) = 128 ln(original_max_position_embeddings /
  (2 pi n)) / (2 ln rope_theta)``; ``cos`` and ``sin`` times
  ``attention_factor``; key ``j`` is seen iff ``j <= i``.  Scores times
  ``head_dim^-0.5``; softmax; ``P v``; ``W_o``.
- ``routed_i``, a token: ``s = softmax(x W_r)`` over all ``num_experts``;
  ``sel = top_k(s)``, ties to the lower index; ``w = s[sel] / sum(s[sel])``
  (``norm_topk_prob``); ``sum_j w_j E_{sel_j}(x)``, ``E_e`` a gated MLP
  ``w2(silu(w1 x) * w3 x)``.  Every layer is routed (``mlp_layer_types`` all
  ``sparse``), every expert held, no shared expert.
- after the last layer ``norm_f``, then the untied head on the last position.

Departures from the published model, all of them: the head runs on the last
position alone; the multi-token-prediction head that the model card names (no
key of the config gives it) is not built: it predicts a further token for
speculative decoding and leaves the main head's logits as they are; no q/k norm,
since no key of the config names one; ``max_window_layers`` and
``use_sliding_window`` are read by nothing (``layer_types`` says which layers
are windowed); an expert's ``W1 | W3`` is one stored leaf, ``w13[e]``, gate
first; weights are random (``make_params``, ``spreads``).

``routing=`` holds the experts to given ids (the program's): the weights are
still this reference's own, from its own probabilities at those ids, and
``routed=`` receives how far the given ids lie from this reference's own
choice (reference/lfm2_moe.py says why), in units of the router's LOGITS: the
softmax is monotone in the logit, so the top-k of ``s`` is the top-k of the
logits, and a probability among 64 is too small a unit to measure a shortfall
in.  ``quant`` rounds the operands of every contraction but the router's to a
narrower type; ``fault`` plants one of ``FAULTS``.  ``"ninth_for_best"`` is a
wrong choice that the weights cannot show: every token's best expert dropped
and its ``k + 1``-th taken, so each layer's choice is ranks 2 to ``k + 1``.  Held
to that routing the reference weighs the same experts and its logits agree;
only the routing's own distance from the reference's choice tells it (one pair
in ``k`` a little under the ``k``-th best: ``routing_near_share``).
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

from benchmark.reference.afmoe import attention, pairs_seen, seen_by
from benchmark.reference.falcon_h1 import HIGHEST, _dot, _draw, _norm, _rounder, key_of
# Zipf(1) ids through a seeded permutation; how far given experts lie from the
# reference's own choice; an expert's rows in whole buckets.
from benchmark.reference.lfm2_moe import EXPERT_GATED_RMS, ROW_BUCKET, _held_against_own, _rms, _widened
from benchmark.reference.lfm2_moe import make_tokens  # noqa: F401

FAULTS = ("no_yarn", "no_attention_factor", "yarn_on_sliding", "no_window", "band_one_chunk_lower",
          "weights_not_normalised", "expert_zeroed", "sigmoid_router", "ninth_for_best")
#: The expert that ``fault="expert_zeroed"`` silences in every layer.
ZEROED_EXPERT = 0
#: Queries of rms 2 on keys of rms 1.5 (carried by the spreads of ``W_q`` and ``W_k``): scores of
#: spread 3 in a sliding layer, 3 x 1.2773^2 = 4.9 in a full one.
Q_RMS, K_RMS = 2.0, 1.5
#: rms of the attention's output before ``W_o`` with those scores and values of rms 1, at 32,768
#: positions: 0.36 over a band of 1,024, 0.50 over the triangle (512 rows drawn by hand, numpy):
#: ``W_o``'s spread takes their middle, so the two kinds of layer add terms of rms 0.87 and 1.19.
ATTENTION_RMS = 0.42
#: Router logits of spread 1.5; rms of the softmax-weighted sum of top-8 of 64 unit terms under them
#: (the root of the sum of the chosen weights' squares, 20,000 draws).
ROUTER_SPREAD, COMBINED_RMS = 1.5, 0.448


def sizes(model: dict) -> dict:
    types = model["layer_types"]
    return {"q": model["num_attention_heads"] * model["head_dim"],
            "kv": model["num_key_value_heads"] * model["head_dim"],
            "attention_layers": model["num_hidden_layers"],
            "sliding_layers": sum(kind == "sliding_attention" for kind in types),
            "full_layers": sum(kind == "full_attention" for kind in types),
            "expert_layers": model["num_hidden_layers"],
            "held": model["num_experts"], "router_experts": model["num_experts"]}


def leaf_shapes(model: dict) -> dict:
    """{name: shape} of every weight; a matrix is ``[in, out]``."""
    d, s, f, e = model["hidden_size"], sizes(model), model["moe_intermediate_size"], model["num_experts"]
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    if set(model.get("mlp_layer_types") or ["sparse"]) != {"sparse"}:
        raise ValueError("every layer routed: mlp_layer_types all sparse")
    shapes = {"embed": (model["vocab_size"], d), "norm_f": (d,), "head": (d, model["vocab_size"])}
    for i in range(model["num_hidden_layers"]):
        layer = {"norm_in": (d,), "norm_post": (d,),
                 "attn.wq": (d, s["q"]), "attn.wk": (d, s["kv"]), "attn.wv": (d, s["kv"]), "attn.wo": (s["q"], d),
                 "moe.router": (d, e), "moe.w13": (e, d, 2 * f), "moe.w2": (e, f, d)}
        shapes.update({f"layers.{i}.{name}": shape for name, shape in layer.items()})
    return shapes


def spreads(model: dict) -> dict:
    """{leaf's name within a layer, or top-level name: ("normal", mean, spread)}.

    By Falcon's rule: every branch adds a term of rms about 1 to a residual
    that starts at rms 1 (an embedding of spread 1: the head is untied).  Every
    branch reads the residual through a norm (weights 1 +- 0.1), so nothing
    else scales with it.  Queries and keys carry the scores' spread
    (``Q_RMS``, ``K_RMS``), values have rms 1 and ``W_o`` brings the
    attention's output (``ATTENTION_RMS``) to rms 1.  The routed term: router
    logits of spread ``ROUTER_SPREAD``, gate and up of spread 1.25 each, and
    ``W2`` bringing the weighted sum of eight experts (``COMBINED_RMS``) to rms
    1.  Logits of spread 2.5."""
    d, s, f = model["hidden_size"], sizes(model), model["moe_intermediate_size"]
    fan = 1.0 / math.sqrt(d)
    unit = ("normal", 1.0, 0.1)
    return {
        "embed": ("normal", 0.0, 1.0), "norm_f": unit, "norm_in": unit, "norm_post": unit,
        "head": ("normal", 0.0, 2.5 * fan),
        "attn.wq": ("normal", 0.0, Q_RMS * fan), "attn.wk": ("normal", 0.0, K_RMS * fan),
        "attn.wv": ("normal", 0.0, fan), "attn.wo": ("normal", 0.0, 1.0 / (math.sqrt(s["q"]) * ATTENTION_RMS)),
        "moe.router": ("normal", 0.0, ROUTER_SPREAD * fan),
        # Gate and up are one leaf, so one spread: 1.25 each.
        "moe.w13": ("normal", 0.0, 1.25 * fan),
        "moe.w2": ("normal", 0.0, 1.0 / (math.sqrt(f) * EXPERT_GATED_RMS * COMBINED_RMS)),
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


# -- positions ----------------------------------------------------------------

def yarn_frequencies(section: dict, dim: int) -> np.ndarray:
    """``float64[dim // 2]``: yarn's turn a position of each pair, written out
    from the formula in the module's docstring."""
    base, factor = float(section["rope_theta"]), float(section["factor"])
    original = float(section["original_max_position_embeddings"])
    plain = np.array([base ** (-2.0 * j / dim) for j in range(dim // 2)])

    def b(turns):
        return dim * math.log(original / (2 * math.pi * turns)) / (2 * math.log(base))

    low, high = max(math.floor(b(section["beta_fast"])), 0), min(math.ceil(b(section["beta_slow"])), dim - 1)
    r = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - r) + plain / factor * r


def rope_tables(model: dict, kind: str, tokens: int, fault=None):
    """(cos, sin) ``float32[T, head_dim]`` of a layer of ``kind``, rotate-half
    layout (pair ``j`` at lanes ``j`` and ``j + head_dim / 2``), angles in
    float64."""
    hd = model["head_dim"]
    full = model["rope_parameters"]["full_attention"]
    yarn = (kind == "full_attention" and fault != "no_yarn") or fault == "yarn_on_sliding"
    section = full if yarn else model["rope_parameters"][kind]
    if yarn:
        freq = yarn_frequencies(section, hd)
        scale = 1.0 if fault == "no_attention_factor" else float(section["attention_factor"])
    else:
        freq, scale = np.array([float(section["rope_theta"]) ** (-2.0 * j / hd) for j in range(hd // 2)]), 1.0
    angle = np.arange(tokens, dtype=np.float64)[:, None] * freq[None, :]
    cos, sin = (np.concatenate([fn(angle)] * 2, axis=-1) * scale for fn in (np.cos, np.sin))
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def _turned(x, cos, sin):
    """``x`` ``[T, heads, D]``: ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    return x * cos[:, None, :] + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin[:, None, :]


# -- the forward pass -------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _compiled(model_json: str, quant, fault):
    model = json.loads(model_json)
    q_, eps, k = _rounder(quant), model["rms_norm_eps"], model["num_experts_per_tok"]
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    dot = _dot(q_)

    def project(p, h, cos, sin):
        """``norm_in(h)``, and of it ``q``, ``k`` (turned), ``v``."""
        p = _widened(p)
        u = _norm(h, p["norm_in"], eps)
        t = u.shape[0]
        q = _turned(dot(u, p["attn.wq"]).reshape(t, heads, hd), cos, sin)
        key = _turned(dot(u, p["attn.wk"]).reshape(t, kv, hd), cos, sin)
        return q, key, dot(u, p["attn.wv"]).reshape(t, kv, hd)

    def finish(p, h, out):
        """``h + out W_o``, its ``norm_post`` for the routed layer, and the rms of the term."""
        p = _widened(p)
        added = dot(out.reshape(out.shape[0], heads * hd), p["attn.wo"])
        h = h + added
        return h, _norm(h, p["norm_post"], eps), _rms(added)

    def logits(router, x):
        """The router's logits: never rounded."""
        return jnp.dot(x, router.astype(jnp.float32), precision=HIGHEST)

    def weights(l, sel):
        s = jax.nn.sigmoid(l) if fault == "sigmoid_router" else jax.nn.softmax(l, axis=-1)
        picked = jnp.take_along_axis(s, sel, axis=-1)
        return picked if fault == "weights_not_normalised" else picked / jnp.sum(picked, axis=-1, keepdims=True)

    def expert(out, x, w13, w2, rows, weight):
        """``out`` with one expert's weighted output added at ``rows`` (padding
        rows carry the weight 0)."""
        f = w2.shape[0]
        both = dot(x[rows], w13.astype(jnp.float32))
        y = dot(jax.nn.silu(both[:, :f]) * both[:, f:], w2.astype(jnp.float32))
        return out.at[rows].add(y * weight[:, None])

    def close(h, m):
        return h + m, _rms(m)

    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    def head(norm_f, table, h_last):
        return dot(_norm(h_last, norm_f.astype(jnp.float32), eps), table.astype(jnp.float32))

    fns = {name: jax.jit(fn) for name, fn in dict(
        project=project, finish=finish, logits=logits, weights=weights, expert=expert, close=close,
        embed=embed, head=head).items()}
    if fault == "ninth_for_best":
        fns["top_k"] = jax.jit(lambda l: lax.top_k(l, k + 1)[1][:, 1:])
    else:
        fns["top_k"] = jax.jit(lambda l: lax.top_k(l, k)[1])
    return fns


def routed_ff(fns, p, x, model, fault=None, given=None, delta=0.0):
    """The routed layer's term on one record, ``x`` ``[T, d]``; ``given``:
    ``[T, k]`` expert ids or None.  Returns (term, experts used ``[T, k]``, what
    ``_held_against_own`` says of ``given``, in logit units)."""
    k = model["num_experts_per_tok"]
    l = fns["logits"](p["moe.router"], x)
    seen = None
    if given is None:
        sel = np.asarray(fns["top_k"](l))
    else:
        sel = np.asarray(given).astype(np.int32)
        seen = _held_against_own(l, sel, k, delta)
    weight = np.asarray(fns["weights"](l, jnp.asarray(sel)))
    out = jnp.zeros_like(x)
    for e in range(model["num_experts"]):
        rows, slot = np.nonzero(sel == e)
        if not len(rows) or (fault == "expert_zeroed" and e == ZEROED_EXPERT):
            continue
        padded = -len(rows) % ROW_BUCKET
        out = fns["expert"](out, x, p["moe.w13"][e], p["moe.w2"][e],
                            np.pad(rows, (0, padded)), np.pad(weight[rows, slot], (0, padded)))
    return out, sel, seen


def forward(params: dict, tokens, model: dict, *, quant=None, fault=None, rms=None,
            routing=None, routed=None, chosen=None, routing_delta=0.0):
    """Logits ``float32[N, vocab]`` after the last position of each of the
    ``N`` sequences of ``tokens`` (``int[N, T]``).

    ``routing`` (``int[N, T, layers, k]``) holds the experts to the given ids;
    ``routed``, a list, then receives for each record ``{"pairs", "wrong",
    "near", "gap_max"}`` over its (token, layer, slot) pairs
    (:func:`_held_against_own`, with ``routing_delta`` in logit units).
    ``chosen``, a list, receives each record's experts as used (``int16[T,
    layers, k]``).  ``rms``, a list, receives for each record and layer the
    rms of the residual and of the two terms added to it."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    fns = _compiled(json.dumps(model, sort_keys=True), quant, fault)
    tokens = np.asarray(tokens)
    tables = {kind: rope_tables(model, kind, tokens.shape[1], fault) for kind in set(model["layer_types"])}
    out = []
    for n, row in enumerate(tokens):
        h = fns["embed"](params["embed"], row)
        used, seen = [], []
        for i, kind in enumerate(model["layer_types"]):
            prefix = f"layers.{i}."
            p = {name[len(prefix):]: w for name, w in params.items() if name.startswith(prefix)}
            moe = {name: w for name, w in p.items() if name.startswith("moe.")}
            op = {name: w for name, w in p.items() if name not in moe}
            q, key, v = fns["project"](op, h, *tables[kind])
            window = model["sliding_window"] if kind == "sliding_attention" else None
            attended = attention(q, key, v, seen_by(window, fault), quant)
            del q, key, v
            h, x, op_rms = fns["finish"](op, h, attended)
            del attended
            given = None if routing is None else np.asarray(routing)[n, :, i]
            added, sel, held = routed_ff(fns, moe, x, model, fault, given, routing_delta)
            used.append(sel)
            seen.append(held)
            h, ff_rms = fns["close"](h, added)
            if rms is not None:
                rms.append({"residual": float(_rms(h)), "op": float(op_rms), "ff": float(ff_rms)})
        out.append(fns["head"](params["norm_f"], params["head"], h[-1]))
        if chosen is not None:
            chosen.append(np.stack(used, axis=1).astype(np.int16))
        if routed is not None and routing is not None:
            routed.append({"pairs": sum(s.size for s in used), "wrong": sum(s[0] for s in seen),
                           "near": sum(s[1] for s in seen), "gap_max": max(s[2] for s in seen)})
    return jnp.stack(out)


# -- work from shapes ---------------------------------------------------------

def forward_flops(model: dict, tokens: int) -> int:
    """Operations of one record's forward pass over ``tokens`` positions, two a
    multiply-add: every matrix product (of the experts the ``k`` chosen a
    token, not all), the routers, attention on the band in a sliding layer and
    on the lower triangle in a full one, the head on one position.  Not
    counted: norms, RoPE, the softmaxes, the sort."""
    d, s = model["hidden_size"], sizes(model)
    per_layer = (d * (2 * s["q"] + 2 * s["kv"]) + d * model["num_experts"]
                 + model["num_experts_per_tok"] * 3 * d * model["moe_intermediate_size"])
    attention_ = sum(model["num_attention_heads"] * 2 * model["head_dim"]
                     * pairs_seen(tokens, model["sliding_window"] if kind == "sliding_attention" else None)
                     for kind in model["layer_types"])
    return int(2 * (tokens * s["attention_layers"] * per_layer + attention_ + d * model["vocab_size"]))


def attention_kernel_cost(model: dict, tokens: int, batch: int, window=None):
    """(operations, bytes) of one call of the grouped-query attention kernel
    over ``batch`` sequences of ``tokens`` positions: Q K^T and P V on the lower
    triangle, or on the band of ``window``, two operations a multiply-add; q
    read and the output written once, each key/value head read once, in
    bfloat16."""
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    flops = 2 * 2 * batch * heads * hd * pairs_seen(tokens, window)
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
