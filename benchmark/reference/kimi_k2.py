"""Kimi-K2 (``model_type: kimi_k2``; DeepSeek-V3's layer, key for key) as plain
``jax.numpy``: float32 at ``Precision.HIGHEST``, one record and one layer at a
time, no kernel, no sort, no batching; attention by plain softmax over blocks
of heads; an expert's rows are picked on the host, one expert at a time.
Imports nothing of the program.  Source: the model's public ``config.json``
(its keys are this module's ``model`` dict) and
``transformers/models/deepseek_v3/modeling_deepseek_v3.py`` (4.57.6), which
has every equation below.

Layer ``i``: ``x += attn_i(norm_in(x)); x += ff_i(norm_post(x))``; ``norm`` an
RMS norm with a weight and ``rms_norm_eps``.

- ``attn_i``, ``u`` ``[T, d]``: ``c_q = norm(u W_qa)`` (weight ``q_a_norm``);
  ``q = c_q W_qb``, heads of ``q_nope | q_pe``.  ``u W_kva`` = ``c | k_pe``;
  ``c_kv = norm(c)`` (weight ``kv_a_norm``); ``k_pe`` ONE head for all query
  heads.  ``c_kv W_kvb``: heads of ``k_nope | v``.  RoPE on ``q_pe`` and ``k_pe``
  as published: the adjacent pairs ``(x[2j], x[2j+1])`` are first regrouped
  into halves, on ``q`` and ``k`` alike, then rotate-half; every dot product is
  that of rotating the pairs in place.  Frequencies: yarn (``_yarn_inv_freq``);
  cos and sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
  Scores ``(q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 * m^2``, ``m =
  0.1 mscale_all_dim ln(factor) + 1``; causal softmax; ``P v``; ``W_o``.
- ``ff_i``, ``i < first_k_dense_replace``: ``w2(silu(w1 x) * w3 x)``.
- ``ff_i`` after that: ``routed(x) + shared(x)``.  A token: ``s = sigmoid(x
  W_r)``; ``sel = top_k(s + b)``, ties to the lower index; ``w = s[sel] / (sum(
  s[sel]) + 1e-20) * routed_scaling_factor``; ``sum_j w_j E_{sel_j}(x)``, ``E_e``
  a gated MLP.  ``b`` chooses and never weighs.  ``shared`` is a gated MLP of
  width ``moe_intermediate_size x n_shared_experts`` that every token takes.
- after the last layer ``norm_f``, then the untied head on the last position.

**The share.**  ``n_routed_experts`` counts the experts held, ``[first_expert,
first_expert + n_routed_experts)`` of the ``router_experts`` the router
chooses among (both left out: every expert is held, the uncut model).  The
sum over ``j`` then runs over the ``sel_j`` that are held; what the absent
experts would add is left out, and that partial result goes on to the next
layer.  The router, the weights ``w`` and the shared expert are whole.

Departures from the published implementation, all of them: the head runs on
the last position alone; an expert's ``W1 | W3`` is one stored leaf,
``w13[e]``, gate first; ``n_group`` and ``topk_group`` are 1 as published, so the
group-limited step of the top-k is the identity and is not written; weights
are random (``make_params``, ``spreads``); no tower (the source's config holds
no tower's sizes).

``routing=`` holds the experts to given ids (the program's): the weights are
still this reference's own, from its own scores at those ids, and ``routed=``
receives how far the given ids lie from this reference's own choice
(reference/lfm2_moe.py says why).  ``quant`` rounds the operands of every
contraction but the router's to a narrower type; ``fault`` plants one of
``FAULTS``.
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

from benchmark.reference.falcon_h1 import HIGHEST, _dot, _draw, _norm, _rounder, key_of
# Zipf(1) ids through a seeded permutation, over the vocabulary held; how far given
# experts lie from the reference's own choice; an expert's rows in whole buckets.
from benchmark.reference.lfm2_moe import ROW_BUCKET, _held_against_own, _rms, _widened, make_tokens  # noqa: F401

FAULTS = ("no_selection_bias", "no_routed_scaling", "no_shared_expert", "expert_zeroed",
          "over_capacity_dropped", "k_pe_not_rotated", "no_mscale", "no_latent_norms")
#: ``fault="over_capacity_dropped"``: a layer keeps the first of its held pairs,
#: sorted by expert and then by token, up to this share of what an even routing
#: sends the held experts, and drops the rest: a layer that takes one pass over
#: a capacity some pairs exceed.
FAULT_CAPACITY_SHARE = 0.5
#: Heads whose ``[T, T]`` scores are held at once.
HEAD_BLOCK = 4
#: rms of ``silu(g) * u`` for normal ``g``, ``u`` of spreads (1.5, 1) and
#: (1.25, 1.25) (a draw of two million).
GATED_RMS, EXPERT_GATED_RMS = 0.959, 0.970
#: rms of the attention's output over that of the values, with scores of spread
#: 3 over 4,096 causal positions, read off this reference at the published
#: widths on the chip (PERF.md section 6, PR 37: at 0.55 the attention term
#: read 0.74-0.82 in every layer on two seeds).  A draw of independent normal
#: scores reads 0.354: Zipfian ids repeat, a repeated id's values are alike in
#: the first layers, and a sum over alike values averages less away.
ATTENTION_RMS = 0.43
#: Queries of rms 1.412 on keys of rms 1.059 over a head of 192, times the
#: scale 0.1447: scores of spread 3 (192^0.5 x 1.412 x 1.059 x 0.1447).
Q_RMS, K_RMS = 1.412, 1.059


def sizes(model: dict) -> dict:
    heads = model["num_attention_heads"]
    held = model["n_routed_experts"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return {"qk_head_dim": qk, "q": heads * qk,
            "kv": heads * (model["qk_nope_head_dim"] + model["v_head_dim"]),
            "o": heads * model["v_head_dim"],
            "attention_layers": model["num_hidden_layers"],
            "dense_layers": model["first_k_dense_replace"],
            "expert_layers": model["num_hidden_layers"] - model["first_k_dense_replace"],
            "held": held, "router_experts": model.get("router_experts") or held,
            "first_expert": model.get("first_expert", 0),
            "shared_width": model["moe_intermediate_size"] * model["n_shared_experts"]}


def leaf_shapes(model: dict) -> dict:
    """{name: shape} of every weight; a matrix is ``[in, out]``."""
    d, s = model["hidden_size"], sizes(model)
    inter, f = model["intermediate_size"], model["moe_intermediate_size"]
    q_rank, kv_rank, rope = model["q_lora_rank"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    if not 0 <= s["first_expert"] <= s["router_experts"] - s["held"]:
        raise ValueError(f"experts [{s['first_expert']}, {s['first_expert'] + s['held']}) are not "
                         f"among the router's {s['router_experts']}")
    shapes = {"embed": (model["vocab_size"], d), "norm_f": (d,), "head": (d, model["vocab_size"])}
    for i in range(model["num_hidden_layers"]):
        layer = {"norm_in": (d,), "norm_post": (d,),
                 "attn.q_a": (d, q_rank), "attn.q_a_norm": (q_rank,), "attn.q_b": (q_rank, s["q"]),
                 "attn.kv_a": (d, kv_rank + rope), "attn.kv_a_norm": (kv_rank,),
                 "attn.kv_b": (kv_rank, s["kv"]), "attn.o": (s["o"], d)}
        if i < s["dense_layers"]:
            layer.update({"mlp.w1": (d, inter), "mlp.w3": (d, inter), "mlp.w2": (inter, d)})
        else:
            layer.update({"moe.router": (d, s["router_experts"]), "moe.bias": (s["router_experts"],),
                          "moe.w13": (s["held"], d, 2 * f), "moe.w2": (s["held"], f, d),
                          "shared.w1": (d, s["shared_width"]), "shared.w3": (d, s["shared_width"]),
                          "shared.w2": (s["shared_width"], d)})
        shapes.update({f"layers.{i}.{name}": shape for name, shape in layer.items()})
    return shapes


def spreads(model: dict) -> dict:
    """{leaf's name within a layer, or top-level name: ("normal", mean,
    spread)}.  By the rule of the two files beside this one: attention, the
    dense MLP and the shared expert each add a term of rms 1 to a residual that
    starts at rms 1 (an embedding of spread 1: the head is untied); every branch
    reads the residual through a norm, so nothing else scales with it.

    Scores of spread 3 once the whole scale is spent: ``c_q`` is unit after its
    norm, so ``q_a_norm``'s weights (1.412 +- 0.14) carry the queries' rms
    through a ``W_qb`` of fan-in spread; ``c_kv`` is unit after its norm
    (``kv_a_norm`` 1 +- 0.1) and ``W_kvb``'s spread (1.059 x fan-in) carries
    ``k_nope``'s rms, and the values' with it, which ``W_o`` divides out again;
    ``k_pe`` has no norm, so ``W_kva``'s spread carries its 1.059 (``c`` is normed
    and loses it).

    The routed share's term has rms 1 over the tokens it reaches: one held
    expert under the weight ``routed_scaling_factor / k`` that a pair gets when
    the chosen scores are alike.  With every expert held that is ``k`` such
    terms a token."""
    d, s = model["hidden_size"], sizes(model)
    fan = 1.0 / math.sqrt(d)
    pair_weight = model["routed_scaling_factor"] / model["num_experts_per_tok"]
    return {
        "embed": ("normal", 0.0, 1.0),
        "norm_f": ("normal", 1.0, 0.1), "norm_in": ("normal", 1.0, 0.1), "norm_post": ("normal", 1.0, 0.1),
        # Logits of spread 2.5 over the vocabulary held.
        "head": ("normal", 0.0, 2.5 * fan),
        "attn.q_a": ("normal", 0.0, fan), "attn.q_a_norm": ("normal", Q_RMS, 0.1 * Q_RMS),
        "attn.q_b": ("normal", 0.0, 1.0 / math.sqrt(model["q_lora_rank"])),
        "attn.kv_a": ("normal", 0.0, K_RMS * fan), "attn.kv_a_norm": ("normal", 1.0, 0.1),
        "attn.kv_b": ("normal", 0.0, K_RMS / math.sqrt(model["kv_lora_rank"])),
        "attn.o": ("normal", 0.0, 1.0 / (math.sqrt(s["o"]) * ATTENTION_RMS * K_RMS)),
        "mlp.w1": ("normal", 0.0, 1.5 * fan), "mlp.w3": ("normal", 0.0, fan),
        "mlp.w2": ("normal", 0.0, 1.0 / (math.sqrt(model["intermediate_size"]) * GATED_RMS)),
        "shared.w1": ("normal", 0.0, 1.5 * fan), "shared.w3": ("normal", 0.0, fan),
        "shared.w2": ("normal", 0.0, 1.0 / (math.sqrt(s["shared_width"]) * GATED_RMS)),
        # Router scores of spread 1.5 before the sigmoid; a selection bias of spread 0.05.
        "moe.router": ("normal", 0.0, 1.5 * fan), "moe.bias": ("normal", 0.0, 0.05),
        # Gate and up are one leaf, so one spread: 1.25 each.
        "moe.w13": ("normal", 0.0, 1.25 * fan),
        "moe.w2": ("normal", 0.0, 1.0 / (math.sqrt(model["moe_intermediate_size"])
                                         * EXPERT_GATED_RMS * pair_weight)),
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


# -- the forward pass -------------------------------------------------------

def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_inv_freq(model: dict) -> np.ndarray:
    """``float64[rope / 2]`` as ``transformers``' ``_compute_yarn_parameters``:
    ``f_j = theta^(-2j/dim)``; ``dim(r) = dim ln(original / (2 pi r)) / (2 ln
    theta)``; ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``,
    clipped to ``[0, dim - 1]``; ``ramp_j = clip((j - low) / (high - low), 0, 1)``;
    ``inv_freq_j = (f_j / factor) ramp_j + f_j (1 - ramp_j)``."""
    dim, base, y = model["qk_rope_head_dim"], float(model["rope_theta"]), model["rope_scaling"]
    f = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    where = lambda turns: dim * math.log(y["original_max_position_embeddings"]  # noqa: E731
                                         / (turns * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(where(y["beta_fast"])), 0), min(math.ceil(where(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return f / y["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(model: dict, fault=None) -> float:
    y = model["rope_scaling"]
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    if fault != "no_mscale" and y.get("mscale_all_dim"):
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope_as_published(x, model):
    """``x`` ``[T, heads, rope]``: pairs regrouped into halves, then rotate-half."""
    t, heads, dim = x.shape
    y = model["rope_scaling"]
    angle = np.arange(t, dtype=np.float64)[:, None] * _yarn_inv_freq(model)[None, :]
    stretch = _yarn_mscale(y["factor"], y["mscale"]) / _yarn_mscale(y["factor"], y["mscale_all_dim"])
    cos, sin = (jnp.asarray(np.concatenate([fn(angle)] * 2, axis=-1) * stretch, jnp.float32)[:, None, :]
                for fn in (np.cos, np.sin))
    x = x.reshape(t, heads, dim // 2, 2).transpose(0, 1, 3, 2).reshape(t, heads, dim)
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * cos + half * sin


def _attention(p, u, model, q_, fault):
    t, dot = u.shape[0], _dot(q_)
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    nope, rope, dv, rank = (model[key] for key in ("qk_nope_head_dim", "qk_rope_head_dim",
                                                   "v_head_dim", "kv_lora_rank"))
    c_q, kv = dot(u, p["attn.q_a"]), dot(u, p["attn.kv_a"])
    c_kv, k_pe = kv[:, :rank], kv[:, rank:]
    if fault != "no_latent_norms":
        c_q, c_kv = _norm(c_q, p["attn.q_a_norm"], eps), _norm(c_kv, p["attn.kv_a_norm"], eps)
    q = dot(c_q, p["attn.q_b"]).reshape(t, heads, nope + rope)
    k_v = dot(c_kv, p["attn.kv_b"]).reshape(t, heads, nope + dv)
    q_nope, q_pe = q[..., :nope], _rope_as_published(q[..., nope:], model)
    k_nope, v = k_v[..., :nope], k_v[..., nope:]
    k_pe = k_pe[:, None, :]
    if fault != "k_pe_not_rotated":
        k_pe = _rope_as_published(k_pe, model)
    else:  # regrouped as the queries are, and left where it stands
        k_pe = k_pe.reshape(t, 1, rope // 2, 2).transpose(0, 1, 3, 2).reshape(t, 1, rope)
    k_pe = k_pe[:, 0]
    scale = softmax_scale(model, fault)
    causal = jnp.tril(jnp.ones((t, t), bool))
    block = math.gcd(heads, HEAD_BLOCK)

    def some_heads(args):
        qn, qp, kn, vg = args  # [T, block, nope], [T, block, rope], [T, block, nope], [T, block, dv]
        scores = (jnp.einsum("tgd,sgd->gts", q_(qn), q_(kn), precision=HIGHEST)
                  + jnp.einsum("tgd,sd->gts", q_(qp), q_(k_pe), precision=HIGHEST)) * scale
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sgd->tgd", q_(w), q_(vg), precision=HIGHEST)

    blocks = lambda a: a.reshape(t, heads // block, block, a.shape[-1]).transpose(1, 0, 2, 3)  # noqa: E731
    out = lax.map(some_heads, (blocks(q_nope), blocks(q_pe), blocks(k_nope), blocks(v)))
    return dot(out.transpose(1, 0, 2, 3).reshape(t, heads * dv), p["attn.o"])


@functools.lru_cache(maxsize=32)
def _compiled(model_json: str, quant, fault):
    model = json.loads(model_json)
    q_, eps, k = _rounder(quant), model["rms_norm_eps"], model["num_experts_per_tok"]
    dot = _dot(q_)

    def gated(x, w1, w3, w2):
        return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)

    def operator(p, h):
        """``h + attn(norm_in(h))`` and its normed form for the feed-forward."""
        p = _widened(p)
        added = _attention(p, _norm(h, p["norm_in"], eps), model, q_, fault)
        h = h + added
        return h, _norm(h, p["norm_post"], eps), _rms(added)

    def dense_ff(p, h, x):
        p = _widened(p)
        added = gated(x, p["mlp.w1"], p["mlp.w3"], p["mlp.w2"])
        return h + added, _rms(added)

    def shared_ff(p, x):
        p = _widened(p)
        return gated(x, p["shared.w1"], p["shared.w3"], p["shared.w2"])

    def scores(router, bias, x):
        """The router's scores and what it selects by: never rounded."""
        s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32), precision=HIGHEST))
        by = s if fault == "no_selection_bias" else s + bias.astype(jnp.float32)
        return s, by, lax.top_k(by, k)[1]

    def weights(s, sel):
        picked = jnp.take_along_axis(s, sel, axis=-1)
        w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        return w if fault == "no_routed_scaling" else w * model["routed_scaling_factor"]

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
        return dot(_norm(h_last, norm_f.astype(jnp.float32), eps), table.astype(jnp.float32))

    return {name: jax.jit(fn) for name, fn in dict(
        operator=operator, dense_ff=dense_ff, shared_ff=shared_ff, scores=scores, weights=weights,
        expert=expert, embed=embed, head=head).items()}


def routed_ff(fns, p, x, model, fault=None, given=None, delta=0.0):
    """The routed layer's own term on one record, ``x`` ``[T, d]`` (the shared
    expert is not in it); ``given``: ``[T, k]`` expert ids or None.  Returns
    (term, experts used ``[T, k]``, what ``_held_against_own`` says of ``given``)."""
    k, s_ = model["num_experts_per_tok"], sizes(model)
    first, held = s_["first_expert"], s_["held"]
    s, by, own = fns["scores"](p["moe.router"], p["moe.bias"], x)
    seen = None
    if given is None:
        sel = np.asarray(own)
    else:
        sel = np.asarray(given).astype(np.int32)
        seen = _held_against_own(by, sel, k, delta)
    weight = np.asarray(fns["weights"](s, jnp.asarray(sel)))
    kept = (sel >= first) & (sel < first + held)
    if fault == "over_capacity_dropped":
        # Pairs sorted by expert, then token; those past the capacity are dropped.
        capacity = int(FAULT_CAPACITY_SHARE * x.shape[0] * k * held / s_["router_experts"])
        rows, slots = np.nonzero(kept)
        order = np.lexsort((rows, sel[rows, slots]))[capacity:]
        kept[rows[order], slots[order]] = False
    out = jnp.zeros_like(x)
    for e in range(first, first + held):
        rows, slot = np.nonzero((sel == e) & kept)
        if not len(rows) or (fault == "expert_zeroed" and e == first):
            continue
        padded = -len(rows) % ROW_BUCKET
        out = fns["expert"](out, x, p["moe.w13"][e - first], p["moe.w2"][e - first],
                            np.pad(rows, (0, padded)), np.pad(weight[rows, slot], (0, padded)))
    return out, sel, seen


def forward(params: dict, tokens, model: dict, *, quant=None, fault=None, rms=None,
            routing=None, routed=None, chosen=None, routing_delta=0.0):
    """Logits ``float32[N, vocab]`` after the last position of each of the
    ``N`` sequences of ``tokens`` (``int[N, T]``).

    ``routing`` (``int[N, T, expert layers, k]``) holds the experts to the
    given ids; ``routed``, a list, then receives for each record ``{"pairs",
    "wrong", "near", "gap_max"}`` over its (token, layer, slot) pairs
    (:func:`_held_against_own`, with ``routing_delta``).  ``chosen``, a list,
    receives each record's experts as used (``int16[T, expert layers, k]``).
    ``rms``, a list, receives for each record and layer the rms of the
    residual and of the two terms added to it."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    fns = _compiled(json.dumps(model, sort_keys=True), quant, fault)
    dense = model["first_k_dense_replace"]
    out = []
    for n, row in enumerate(np.asarray(tokens)):
        h = fns["embed"](params["embed"], row)
        used, seen = [], []
        for i in range(model["num_hidden_layers"]):
            prefix = f"layers.{i}."
            p = {name[len(prefix):]: w for name, w in params.items() if name.startswith(prefix)}
            ff = {name: w for name, w in p.items() if name.startswith(("mlp.", "moe.", "shared."))}
            h, x, op_rms = fns["operator"]({name: w for name, w in p.items() if name not in ff}, h)
            if i < dense:
                h, ff_rms = fns["dense_ff"](ff, h, x)
            else:
                given = None if routing is None else np.asarray(routing)[n, :, i - dense]
                added, sel, held = routed_ff(fns, ff, x, model, fault, given, routing_delta)
                if fault != "no_shared_expert":
                    added = added + fns["shared_ff"](
                        {name: w for name, w in ff.items() if name.startswith("shared.")}, x)
                h, ff_rms = h + added, _rms(added)
                used.append(sel)
                seen.append(held)
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

def _attention_macs(model: dict) -> int:
    """Multiply-adds a token of one layer's five projections."""
    d, s = model["hidden_size"], sizes(model)
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    return (d * q_rank + q_rank * s["q"] + d * (kv_rank + model["qk_rope_head_dim"])
            + kv_rank * s["kv"] + s["o"] * d)


def forward_flops(model: dict, tokens: int) -> int:
    """Operations of one record's forward pass over ``tokens`` positions, two a
    multiply-add: every matrix product (of the routed experts the share held
    here at the even share of pairs, ``k x held / router_experts`` a token a
    layer, not all and not ``k``), the shared expert, the routers, causal
    attention on the lower triangle at ``nope + rope`` and ``v``, the head on
    one position.  Not counted: norms, RoPE, the sort."""
    d, s = model["hidden_size"], sizes(model)
    f = model["moe_intermediate_size"]
    pairs_here = model["num_experts_per_tok"] * s["held"] / s["router_experts"]
    per_token = (s["attention_layers"] * _attention_macs(model)
                 + s["dense_layers"] * 3 * d * model["intermediate_size"]
                 + s["expert_layers"] * (3 * d * s["shared_width"] + d * s["router_experts"]
                                         + pairs_here * 3 * d * f))
    attention = (s["attention_layers"] * model["num_attention_heads"]
                 * (s["qk_head_dim"] + model["v_head_dim"]) * (tokens * (tokens + 1) // 2))
    return int(2 * (tokens * per_token + attention + d * model["vocab_size"]))


def attention_kernel_cost(model: dict, tokens: int, batch: int):
    """(operations, bytes) of one call of a causal latent-attention kernel
    over ``batch`` sequences of ``tokens`` positions: Q K^T at ``nope + rope``
    and P V at ``v`` on the lower triangle, two operations a multiply-add; ``q``
    read and the output written once, ``k_nope`` and ``v`` of each head read
    once, ``k_pe`` (one head for all) once, in bfloat16."""
    heads, s = model["num_attention_heads"], sizes(model)
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    flops = 2 * batch * heads * (s["qk_head_dim"] + dv) * (tokens * (tokens + 1) // 2)
    moved = 2 * batch * tokens * (heads * (s["qk_head_dim"] + dv) + heads * (nope + dv) + rope)
    return flops, moved


def expert_kernel_cost(model: dict, tokens: int, batch: int):
    """(operations, bytes) of ONE routed layer's two grouped products over
    ``batch`` sequences of ``tokens`` positions on the experts held: the even
    share of rows, ``tokens x batch x k x held / router_experts``, through
    ``W1 | W3`` and ``W2``, two operations a multiply-add; the held experts'
    weights read once, those rows read once and written once, in bfloat16
    (what passes between the two products need never leave the chip).  The
    rows are the expectation under an even routing, not a count: what a run
    drew is in ``expert_rows``.  Where few experts of many are held the weights
    are nearly all of the bytes (95% at 12 of 384, top-8, 8,192 tokens), so a
    share that draws half as many rows or half again as many moves this floor
    by under 3%."""
    d, f, s = model["hidden_size"], model["moe_intermediate_size"], sizes(model)
    rows = tokens * batch * model["num_experts_per_tok"] * s["held"] / s["router_experts"]
    flops = 2 * rows * 3 * d * f
    moved = 2 * (s["held"] * 3 * d * f + 2 * rows * d)
    return int(flops), int(moved)
