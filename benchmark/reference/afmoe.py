"""AFMoE (``model_type: afmoe``, Arcee's Trinity) as plain ``jax.numpy``:
float32 at ``Precision.HIGHEST``, one record and one layer at a time, no
kernel, no sort, no batching; attention by plain softmax over blocks of query
rows, each against the keys its band reaches, masked from positions; an
expert's rows are picked on the host, one expert at a time.  Imports nothing of
the program.  Source: the model's public ``config.json`` (its keys are this
module's ``model`` dict) and ``transformers/models/afmoe/modeling_afmoe.py``,
which has every equation below.

Layer ``i``, ``norm`` an RMS norm with a weight and ``rms_norm_eps``:
``h += norm_post_attn(attn_i(norm_in(h)))``; ``h += norm_post_mlp(ff_i(
norm_pre_mlp(h)))`` (the sandwich: four norms a layer).

- The embedding: ``h0 = E[ids] sqrt(hidden_size)`` (``mup_enabled``).
- ``attn_i``, ``u`` ``[T, d]``: ``q = u W_q`` (48 heads of 128), ``k = u W_k``,
  ``v = u W_v`` (8 heads each; query head ``n`` reads key/value head ``n // 6``),
  ``g = u W_g`` (``[T, 48 x 128]``).  ``q`` and ``k`` rms-normed a head at a time
  (weights ``[128]``).  ``layer_types[i] == "sliding_attention"``: rotate-half
  RoPE at ``rope_theta`` on ``q`` and ``k``, and key ``j`` is seen by query ``i``
  iff ``0 <= i - j < sliding_window``; ``"full_attention"``: no positions (NoPE)
  and iff ``j <= i``.  Scores times ``head_dim^-0.5``; softmax; ``P v``; then
  ``(attn * sigmoid(g)) W_o``.
- ``ff_i``, ``i < num_dense_layers``: ``w2(silu(w1 x) * w3 x)``, in blocks of
  rows (the same sums row by row).
- ``ff_i`` after that: ``routed(x) + shared(x)``.  A token: ``s = sigmoid(x
  W_r)``; ``sel = top_k(s + b)``, ties to the lower index; ``w = s[sel] / (sum(
  s[sel]) + 1e-20) * route_scale``; ``sum_j w_j E_{sel_j}(x)``, ``E_e`` a gated
  MLP.  ``b`` chooses and never weighs.  ``shared`` is a gated MLP of width
  ``moe_intermediate_size x num_shared_experts`` that every token takes.
- after the last layer ``norm_f``, then the untied head on the last position.

**The share.**  ``num_experts`` counts the experts held, ``[first_expert,
first_expert + num_experts)`` of the ``router_experts`` the router chooses
among (both left out: every expert is held, the uncut model).  The sum over
``j`` runs over the ``sel_j`` that are held; what the absent experts would add
is left out, and that partial result goes on to the next layer.  The router,
the weights ``w``, attention and the shared expert are whole.

Departures from the published implementation, all of them: RoPE on the sliding
layers only and none on the full ones, and the embedding's ``sqrt(hidden_size)``,
are read from the source's code (``modeling_afmoe.py``), not from its config,
which has ``rope_theta`` 10,000 and no scaling beside a context of 262,144, and
``mup_enabled``; the head runs on the last position alone; an expert's ``W1 |
W3`` is one stored leaf, ``w13[e]``, gate first; ``n_group`` and ``topk_group``
are 1 as published, so the group-limited step of the top-k is the identity and is
not written; weights are random (``make_params``, ``spreads``); the auxiliary
load-balancing term (``load_balance_coeff``) is training's and is not here.

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

from benchmark.reference.falcon_h1 import HIGHEST, _dot, _draw, _norm, _rope, _rounder, key_of
# Zipf(1) ids through a seeded permutation, over the vocabulary held; how far given
# experts lie from the reference's own choice; an expert's rows in whole buckets.
from benchmark.reference.lfm2_moe import ROW_BUCKET, _held_against_own, _rms, _widened, make_tokens  # noqa: F401

FAULTS = ("no_window", "band_one_chunk_lower", "rope_on_full_layer", "no_output_gate", "no_post_norms",
          "no_route_scale", "no_selection_bias")
#: ``fault="band_one_chunk_lower"``: the band's lower edge this many keys lower (a kernel that
#: skipped tiles below the band but masked nothing at its edge would see up to a chunk more).
CHUNK = 512
#: Query rows of one block of attention; each block is scored against the keys its band reaches.
QUERY_BLOCK = 1024
#: A full layer's query blocks see keys in whole spans of this many (4 shapes compile at 32,768
#: positions where a span ending at each block's last row would compile 32).
KEY_SPAN = 8192
#: Rows of one block of the dense MLP: its ``[rows, 12,288]`` float32 products are held at once.
MLP_ROWS = 4096
#: Queries and keys of rms sqrt(3) each after their norms (carried by the weights of ``q_norm``
#: and ``k_norm``), times the scale 128^-0.5: scores of spread 3.
QK_RMS = math.sqrt(3.0)


def sizes(model: dict) -> dict:
    held, types = model["num_experts"], model["layer_types"]
    return {"q": model["num_attention_heads"] * model["head_dim"],
            "kv": model["num_key_value_heads"] * model["head_dim"],
            "attention_layers": model["num_hidden_layers"],
            "sliding_layers": sum(kind == "sliding_attention" for kind in types),
            "full_layers": sum(kind == "full_attention" for kind in types),
            "dense_layers": model["num_dense_layers"],
            "expert_layers": model["num_hidden_layers"] - model["num_dense_layers"],
            "held": held, "router_experts": model.get("router_experts") or held,
            "first_expert": model.get("first_expert", 0),
            "shared_width": model["moe_intermediate_size"] * model["num_shared_experts"]}


def leaf_shapes(model: dict) -> dict:
    """{name: shape} of every weight; a matrix is ``[in, out]``."""
    d, s, hd = model["hidden_size"], sizes(model), model["head_dim"]
    inter, f = model["intermediate_size"], model["moe_intermediate_size"]
    if not 0 <= s["first_expert"] <= s["router_experts"] - s["held"]:
        raise ValueError(f"experts [{s['first_expert']}, {s['first_expert'] + s['held']}) are not "
                         f"among the router's {s['router_experts']}")
    shapes = {"embed": (model["vocab_size"], d), "norm_f": (d,), "head": (d, model["vocab_size"])}
    for i in range(model["num_hidden_layers"]):
        layer = {"norm_in": (d,), "norm_post_attn": (d,), "norm_pre_mlp": (d,), "norm_post_mlp": (d,),
                 "attn.wq": (d, s["q"]), "attn.wk": (d, s["kv"]), "attn.wv": (d, s["kv"]),
                 "attn.wg": (d, s["q"]), "attn.wo": (s["q"], d), "attn.q_norm": (hd,), "attn.k_norm": (hd,)}
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
    """{leaf's name within a layer, or top-level name: ("normal", mean, spread)}.

    By Kimi's rule (reference/kimi_k2.py), each branch reaches the residual at
    rms about 1, and here the post-norms see to it: a branch's term is normed,
    times weights of 1 +- 0.1, whatever its raw rms.  The raw terms are left at
    fan-in spreads, where they come out well under 1 (the attention's some 0.2:
    values of rms 1 averaged by the softmax, halved by the gate), so that a
    program that left the post-norms out would add terms of another size.  The
    embedding has spread ``1 / sqrt(d)``, which the muP scale brings to 1.

    Scores of spread 3: ``q`` and ``k`` are unit after their norms and
    ``q_norm``'s and ``k_norm``'s weights (``sqrt 3 +- 10%``) carry them, so that
    ``q . k / sqrt(128)`` has spread 3.  The gate's input ``u W_g`` has rms 1.

    The routed share's term: an expert's output of rms about 1 under the weight
    ``route_scale / k`` that a pair gets when the chosen scores are alike, beside
    the shared expert's of rms 1."""
    d = model["hidden_size"]
    fan = 1.0 / math.sqrt(d)
    unit = ("normal", 1.0, 0.1)
    return {
        "embed": ("normal", 0.0, fan),
        "norm_f": unit, "norm_in": unit, "norm_post_attn": unit, "norm_pre_mlp": unit, "norm_post_mlp": unit,
        # Logits of spread 2.5 over the vocabulary held.
        "head": ("normal", 0.0, 2.5 * fan),
        "attn.wq": ("normal", 0.0, fan), "attn.wk": ("normal", 0.0, fan), "attn.wv": ("normal", 0.0, fan),
        "attn.wg": ("normal", 0.0, fan), "attn.wo": ("normal", 0.0, 1.0 / math.sqrt(sizes(model)["q"])),
        "attn.q_norm": ("normal", QK_RMS, 0.1 * QK_RMS), "attn.k_norm": ("normal", QK_RMS, 0.1 * QK_RMS),
        "mlp.w1": ("normal", 0.0, 1.5 * fan), "mlp.w3": ("normal", 0.0, fan),
        "mlp.w2": ("normal", 0.0, 1.0 / math.sqrt(model["intermediate_size"])),
        "shared.w1": ("normal", 0.0, 1.5 * fan), "shared.w3": ("normal", 0.0, fan),
        "shared.w2": ("normal", 0.0, 1.0 / math.sqrt(sizes(model)["shared_width"])),
        # Router scores of spread 1.5 before the sigmoid; a selection bias of spread 0.05.
        "moe.router": ("normal", 0.0, 1.5 * fan), "moe.bias": ("normal", 0.0, 0.05),
        # Gate and up are one leaf, so one spread: 1.25 each.
        "moe.w13": ("normal", 0.0, 1.25 * fan),
        "moe.w2": ("normal", 0.0, 1.0 / math.sqrt(model["moe_intermediate_size"])),
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

def seen_by(window, fault=None):
    """How far back a query of a layer with ``window`` (None: a full layer) sees."""
    if window is None:
        return None
    if fault == "no_window":
        return None
    return window + CHUNK if fault == "band_one_chunk_lower" else window


@functools.partial(jax.jit, static_argnames=("window", "quant"))
def _attend(q, k, v, first_row, first_key, *, window, quant):
    """One block of query rows, ``q`` ``[R, H, D]``, against the keys ``[S, Hkv, D]``
    from position ``first_key``: ``0 <= i - j < window`` (``window`` None: ``j <=
    i``), from positions alone.  One key/value head and its query heads at a time."""
    q_ = _rounder(quant)
    rows, heads, hd = q.shape
    keys, kv = k.shape[0], k.shape[1]
    i = first_row + jnp.arange(rows)[:, None]
    j = first_key + jnp.arange(keys)[None, :]
    seen = (i - j >= 0) if window is None else (i - j >= 0) & (i - j < window)

    def group(args):  # [R, heads / kv, D], [S, D], [S, D]
        qg, kg, vg = args
        s = jnp.einsum("rgd,sd->grs", q_(qg), q_(kg), precision=HIGHEST) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grs,sd->rgd", q_(w), q_(vg), precision=HIGHEST)

    out = lax.map(group, (q.reshape(rows, kv, heads // kv, hd).transpose(1, 0, 2, 3),
                          k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(rows, heads, hd)


def attention(q, k, v, window, quant=None):
    """``q`` ``[T, H, D]``, ``k``, ``v`` ``[T, Hkv, D]`` -> ``[T, H, D]``: blocks of
    ``QUERY_BLOCK`` rows, each against the keys from ``max(0, first_row - window +
    1)`` (0 without a window) to its last row, within a span of keys of one of
    few lengths, so that few shapes compile: ``window - 1 + QUERY_BLOCK`` keys for
    every block of a band, whole ``KEY_SPAN``s up to the block's last row for a
    full layer.  What the span holds beyond those keys is masked from positions
    in :func:`_attend`."""
    t, out = q.shape[0], []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        if window is None:
            first, span = 0, min(t, -(-hi // KEY_SPAN) * KEY_SPAN)
        else:
            span = min(t, window - 1 + QUERY_BLOCK)
            first = min(max(0, lo - window + 1), t - span)
        keys = slice(first, first + span)
        out.append(_attend(q[lo:hi], k[keys], v[keys], lo, first, window=window, quant=quant))
    return jnp.concatenate(out)


@functools.lru_cache(maxsize=32)
def _compiled(model_json: str, quant, fault):
    model = json.loads(model_json)
    q_, eps, k = _rounder(quant), model["rms_norm_eps"], model["num_experts_per_tok"]
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    dot = _dot(q_)
    post = (lambda a, w: a) if fault == "no_post_norms" else (lambda a, w: _norm(a, w, eps))  # noqa: E731

    def gated(x, w1, w3, w2):
        return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)

    def project(p, h, rotate):
        """``norm_in(h)``, and of it ``q``, ``k`` (normed a head at a time, turned
        where ``rotate``), ``v``."""
        p = _widened(p)
        u = _norm(h, p["norm_in"], eps)
        t = u.shape[0]
        q = _norm(dot(u, p["attn.wq"]).reshape(t, heads, hd), p["attn.q_norm"], eps)
        key = _norm(dot(u, p["attn.wk"]).reshape(t, kv, hd), p["attn.k_norm"], eps)
        if rotate:
            q, key = _rope(q, float(model["rope_theta"])), _rope(key, float(model["rope_theta"]))
        return u, q, key, dot(u, p["attn.wv"]).reshape(t, kv, hd)

    def finish(p, h, u, out):
        """``h + norm_post_attn((out * sigmoid(u W_g)) W_o)``, its normed form for
        the feed-forward, and the rms of the term."""
        p = _widened(p)
        out = out.reshape(out.shape[0], heads * hd)
        if fault != "no_output_gate":
            out = out * jax.nn.sigmoid(dot(u, p["attn.wg"]))
        added = post(dot(out, p["attn.wo"]), p["norm_post_attn"])
        h = h + added
        return h, _norm(h, p["norm_pre_mlp"], eps), _rms(added)

    def dense_ff(p, x):
        p = _widened(p)
        rows = min(MLP_ROWS, x.shape[0])
        return lax.map(lambda xb: gated(xb, p["mlp.w1"], p["mlp.w3"], p["mlp.w2"]),
                       x.reshape(-1, rows, x.shape[1])).reshape(x.shape)

    def shared_ff(p, x):
        p = _widened(p)
        return gated(x, p["shared.w1"], p["shared.w3"], p["shared.w2"])

    def close_ff(norm_post_mlp, h, m):
        added = post(m, norm_post_mlp.astype(jnp.float32))
        return h + added, _rms(added)

    def scores(router, bias, x):
        """The router's scores and what it selects by: never rounded."""
        s = jax.nn.sigmoid(jnp.dot(x, router.astype(jnp.float32), precision=HIGHEST))
        by = s if fault == "no_selection_bias" else s + bias.astype(jnp.float32)
        return s, by, lax.top_k(by, k)[1]

    def weights(s, sel):
        picked = jnp.take_along_axis(s, sel, axis=-1)
        w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        return w if fault == "no_route_scale" else w * model["route_scale"]

    def expert(out, x, w13, w2, rows, weight):
        """``out`` with one expert's weighted output added at ``rows`` (padding
        rows carry the weight 0)."""
        f = w2.shape[0]
        both = dot(x[rows], w13.astype(jnp.float32))
        y = dot(jax.nn.silu(both[:, :f]) * both[:, f:], w2.astype(jnp.float32))
        return out.at[rows].add(y * weight[:, None])

    def embed(table, tokens):
        h = table[tokens].astype(jnp.float32)
        return h * math.sqrt(model["hidden_size"]) if model.get("mup_enabled", True) else h

    def head(norm_f, table, h_last):
        return dot(_norm(h_last, norm_f.astype(jnp.float32), eps), table.astype(jnp.float32))

    fns = {name: jax.jit(fn) for name, fn in dict(
        finish=finish, dense_ff=dense_ff, shared_ff=shared_ff, close_ff=close_ff, scores=scores,
        weights=weights, expert=expert, embed=embed, head=head).items()}
    fns["project"] = jax.jit(project, static_argnames="rotate")
    return fns


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
    out = jnp.zeros_like(x)
    for e in range(first, first + held):
        rows, slot = np.nonzero((sel == e) & kept)
        if not len(rows):
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
    dense, window = model["num_dense_layers"], model["sliding_window"]
    out = []
    for n, row in enumerate(np.asarray(tokens)):
        h = fns["embed"](params["embed"], row)
        used, seen = [], []
        for i, kind in enumerate(model["layer_types"]):
            prefix = f"layers.{i}."
            p = {name[len(prefix):]: w for name, w in params.items() if name.startswith(prefix)}
            ff = {name: w for name, w in p.items() if name.startswith(("mlp.", "moe.", "shared."))}
            op = {name: w for name, w in p.items() if name not in ff}
            sliding = kind == "sliding_attention"
            u, q, key, v = fns["project"](op, h, rotate=sliding or fault == "rope_on_full_layer")
            attended = attention(q, key, v, seen_by(window if sliding else None, fault), quant)
            del q, key, v
            h, x, op_rms = fns["finish"](op, h, u, attended)
            del u, attended
            if i < dense:
                added = fns["dense_ff"](ff, x)
            else:
                given = None if routing is None else np.asarray(routing)[n, :, i - dense]
                added, sel, held = routed_ff(fns, ff, x, model, fault, given, routing_delta)
                added = added + fns["shared_ff"]({name: w for name, w in ff.items() if name.startswith("shared.")}, x)
                used.append(sel)
                seen.append(held)
            h, ff_rms = fns["close_ff"](p["norm_post_mlp"], h, added)
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

def pairs_seen(tokens: int, window=None) -> int:
    """(query, key) pairs of one head over ``tokens`` positions: the lower
    triangle, or the band ``0 <= i - j < window``."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def _attention_macs(model: dict) -> int:
    """Multiply-adds a token of one layer's five projections (q, k, v, the gate, o)."""
    d, s = model["hidden_size"], sizes(model)
    return d * (3 * s["q"] + 2 * s["kv"])


def forward_flops(model: dict, tokens: int) -> int:
    """Operations of one record's forward pass over ``tokens`` positions, two a
    multiply-add: every matrix product (of the routed experts the share held
    here at the even share of pairs, ``k x held / router_experts`` a token a
    layer, not all and not ``k``), the shared expert, the routers, attention on
    the band in a sliding layer and on the lower triangle in a full one, the head
    on one position.  Not counted: norms, RoPE, the gate's sigmoid, the sort."""
    d, s = model["hidden_size"], sizes(model)
    f = model["moe_intermediate_size"]
    pairs_here = model["num_experts_per_tok"] * s["held"] / s["router_experts"]
    per_token = (s["attention_layers"] * _attention_macs(model)
                 + s["dense_layers"] * 3 * d * model["intermediate_size"]
                 + s["expert_layers"] * (3 * d * s["shared_width"] + d * s["router_experts"]
                                         + pairs_here * 3 * d * f))
    attention = sum(model["num_attention_heads"] * 2 * model["head_dim"]
                    * pairs_seen(tokens, model["sliding_window"] if kind == "sliding_attention" else None)
                    for kind in model["layer_types"])
    return int(2 * (tokens * per_token + attention + d * model["vocab_size"]))


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
    ``batch`` sequences of ``tokens`` positions on the experts held: the even
    share of rows, ``tokens x batch x k x held / router_experts``, through
    ``W1 | W3`` and ``W2``, two operations a multiply-add; the held experts'
    weights read once, those rows read once and written once, in bfloat16.  The
    rows are the expectation under an even routing, not a count: what a run
    drew is in ``expert_rows``."""
    d, f, s = model["hidden_size"], model["moe_intermediate_size"], sizes(model)
    rows = tokens * batch * model["num_experts_per_tok"] * s["held"] / s["router_experts"]
    flops = 2 * rows * 3 * d * f
    moved = 2 * (s["held"] * 3 * d * f + 2 * rows * d)
    return int(flops), int(moved)
