"""Plain float32 reference of the ``keye_vl`` family (Keye-VL-2.0-30B-A3B's
language model), independent of ``models/keye_vl.py``: the full forward in
``jax.numpy``, dense scores under the selection's mask, a Python loop over
experts with dense masks. No kernel, no cache, no sort of rows, no bf16.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

``h`` is the residual, ``rms(x; g) = g x / sqrt(mean(x^2) + eps)``, no bias;
a token's position is three channels ``(p_t, p_h, p_w)``, equal for text.

1. ``x = rms(h; g_attn)``; ``q = x Wq`` -> H x hd, ``k = x Wk`` -> KV x hd,
   ``v = x Wv`` -> KV x hd.
2. ``rms`` over each head's ``hd`` lanes of q and of k (gains ``g_q``,
   ``g_k``) — ASSUMED, the Qwen3-MoE convention these shapes are those of.
3. mrope on q, k: ``hd / 2`` pairs (lane i with i + hd / 2),
   ``inv_freq_i = theta^(-2 i / hd)``; pair i takes its angle from ``p_t``,
   ``p_h`` or ``p_w`` by ``mrope_section``.
4. Indexer (ASSUMED from DeepSeek-V3.2's lightning indexer): ``qI = x WqI``
   -> HI x dI; ``kI = LayerNorm(x WkI)`` -> dI, one a token; ``w = (x Ww) *
   HI^-1/2 * dI^-1/2``; plain rotary from ``p_t`` on the leading ``dI / 2``
   lanes of qI and kI. ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for
   ``s <= t``; ``S_t`` = the ``topk`` rows of largest ``I[t, s]`` (all of them
   while ``t < topk``; ties to the lower ``s``).
5. ``o[t, head] = sum_{s in S_t} softmax_s(q[t, head] . k[s, head // group] /
   sqrt(hd)) v[s, head // group]``; ``h += o Wo``.
6. ``u = rms(h; g_ffn)``; ``r = softmax(u Wr)``; the k largest, their weights
   divided by their sum; ``h += sum_e w_e W2_e (silu(W1_e u) * W3_e u)`` over
   the chosen experts that are RESIDENT (``expert_first``,
   ``n_resident_experts``: all of them in the served cut).
7. Final ``rms``, then the untied head.

It reads the program's parameter tree (the weights under test are the
program's); ``model`` holds the published keys. Attention is computed
``q_chunk`` queries at a time so that a long sequence fits: the chunk is a
tile size, not semantics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG = -jnp.inf


def _f32(p):
    """A layer's weights in float32, but for the stacked experts (1.8 GB a
    layer at the published widths): ``routed_part`` casts one at a time."""
    return {name: x if name == "experts" else x.astype(jnp.float32)
            for name, x in p.items()}


def rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def text_positions(B: int, T: int):
    t = jnp.broadcast_to(jnp.arange(T), (B, T))
    return jnp.stack([t, t, t], axis=-1)


def _turn(x, ang):
    """x [..., n] by angles [..., n / 2]: lane i pairs with lane i + n / 2."""
    half = x.shape[-1] // 2
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def mrope(x, pos3, model: dict):
    """x [B, T, heads, hd], pos3 [B, T, 3]."""
    hd = x.shape[-1]
    sections = model["rope_scaling"]["mrope_section"]
    channel = np.concatenate([np.full(n, c) for c, n in enumerate(sections)])
    inv = model["rope_theta"] ** (-2.0 * np.arange(hd // 2) / hd)
    ang = pos3[..., channel].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    return _turn(x, ang[:, :, None, :])


def index_rotary(x, p_t, model: dict):
    """The leading half of x's lanes turned by the time channel; p_t has x's
    leading shape."""
    r = model["sa_config"]["indexer_head_dim"] // 2
    inv = model["rope_theta"] ** (-2.0 * np.arange(r // 2) / r)
    ang = p_t[..., None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    return jnp.concatenate([_turn(x[..., :r], ang), x[..., r:]], axis=-1)


def projections(p, x, pos3, model: dict):
    """Step 1-4's rows of normalised x [B, T, D]."""
    B, T, _ = x.shape
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    sa, eps = model["sa_config"], model["rms_norm_eps"]
    HI, dI = sa["indexer_num_heads"], sa["indexer_head_dim"]
    q = mrope(rms((x @ p["wq"]).reshape(B, T, H, hd), p["q_norm"], eps), pos3, model)
    k = mrope(rms((x @ p["wk"]).reshape(B, T, KV, hd), p["k_norm"], eps), pos3, model)
    v = (x @ p["wv"]).reshape(B, T, KV, hd)
    p_t = pos3[..., 0]
    qi = index_rotary((x @ p["wqi"]).reshape(B, T, HI, dI), p_t[..., None], model)
    ki = index_rotary(layer_norm(x @ p["wki"], p["ki_norm_g"], p["ki_norm_b"], eps),
                      p_t, model)
    w = (x @ p["wwi"]) * (HI ** -0.5 * dI ** -0.5)
    return q, k, v, qi, ki, w


def index_scores(qi, ki, w, q_pos):
    """``I`` [B, C, T] of queries at sequence positions ``q_pos`` [C]; -inf
    where ``s > t``."""
    dots = jnp.einsum("bqjd,bkd->bqjk", qi, ki)
    I = jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)
    return jnp.where(jnp.arange(ki.shape[1])[None, :] <= q_pos[:, None], I, NEG)


def select(I, topk: int):
    """bool [.., T]: the ``topk`` largest of every row of ``I``, all visible
    ones where there are no more; of rows that tie with the ``topk``-th value
    the lower ``s`` first, as many as there is room for."""
    T = I.shape[-1]
    if topk >= T:
        return I > NEG
    kth = jax.lax.top_k(I, topk)[0][..., -1]
    above, tied = I > kth[..., None], I == kth[..., None]
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (I > NEG) & (above | (tied & (jnp.cumsum(tied, axis=-1) <= room)))


def attend_parts(p, x, pos3, model: dict, q_chunk: int = 512):
    """Steps 1-5 on normalised x [B, T, D]: the attention's output [B, T, D],
    and what the check on the chip compares layer by layer: ``I`` [B, T, T],
    the selection [B, T, T], and the rows a cache would store: ``k``
    (rotated) and ``v`` [B, T, KV * hd], ``ki`` [B, T, dI]."""
    B, T, _ = x.shape
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    topk = model["sa_config"]["topk"]
    q, k, v, qi, ki, w = projections(p, x, pos3, model)
    C = min(q_chunk, T)
    n = -(-T // C)

    def chunks(a):  # [B, T, ...] -> [n, B, C, ...], zeros behind T
        a = jnp.pad(a, [(0, 0), (0, n * C - T)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B, n, C, *a.shape[2:]), 1, 0)

    def one(args):
        c, q_c, qi_c, w_c = args
        I = index_scores(qi_c, ki, w_c, c * C + jnp.arange(C))
        sel = select(I, topk)
        s = jnp.einsum("bqkgd,btkd->bkgqt", q_c.reshape(B, C, KV, H // KV, hd),
                       k) / hd ** 0.5
        a = jax.nn.softmax(jnp.where(sel[:, None, None], s, NEG), axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", a, v).reshape(B, C, H * hd)
        return o, I, sel

    parts = jax.lax.map(one, (jnp.arange(n), chunks(q), chunks(qi), chunks(w)))
    o, I, sel = (jnp.moveaxis(a, 0, 1).reshape(B, n * C, *a.shape[3:])[:, :T]
                 for a in parts)
    return {"out": o @ p["wo"], "I": I, "selected": sel,
            "k": k.reshape(B, T, -1), "v": v.reshape(B, T, -1), "ki": ki}


def attend_given(p, x, pos3, k, v, selected, model: dict):
    """Step 5 alone, of single queries under a GIVEN selection: normalised
    x [N, D] at positions pos3 [N, 3], the rows k, v [T, KV * hd] as
    ``attend_parts`` returns them (k rotated), selected bool [N, T] ->
    [N, H * hd], before ``Wo``. What a decode step's attention has to give
    for the rows it says it selected."""
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    N, T = selected.shape
    wq, g = p["wq"].astype(jnp.float32), p["q_norm"].astype(jnp.float32)
    q = mrope(rms((x @ wq).reshape(N, 1, H, hd), g, model["rms_norm_eps"]),
              pos3[:, None], model)
    s = jnp.einsum("nkgd,tkd->nkgt", q.reshape(N, KV, H // KV, hd),
                   k.reshape(T, KV, hd)) / hd ** 0.5
    a = jax.nn.softmax(jnp.where(selected[:, None, None], s, NEG), axis=-1)
    return jnp.einsum("nkgt,tkd->nkgd", a, v.reshape(T, KV, hd)).reshape(N, H * hd)


def routing(p, u, model: dict):
    """u [..., D] -> (chosen experts [..., k], their weights [..., k] divided
    by their sum (the published ``norm_topk_prob``), the softmax scores
    [..., num_experts])."""
    r = jax.nn.softmax(u @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(r, model["num_experts_per_tok"])
    return idx, top / jnp.sum(top, -1, keepdims=True), r


def swiglu(e, u):
    return (jax.nn.silu(u @ e["wg"]) * (u @ e["wu"])) @ e["wd"]


def routed_part(p, u, model: dict, idx, w):
    """``sum over chosen e of w_e E_e(u)`` over the RESIDENT experts, one
    after the other; ``p["experts"]`` stacks them on a leading axis (in any
    dtype: an expert is cast up when its turn comes)."""
    stacked, first = p["experts"], model.get("expert_first", 0)

    def add(i, out):
        expert = {name: x[i].astype(jnp.float32) for name, x in stacked.items()}
        w_e = jnp.sum(jnp.where(idx == first + i, w, 0.0), axis=-1)  # 0: not chosen
        return out + w_e[..., None] * swiglu(expert, u)

    return jax.lax.fori_loop(0, stacked["wg"].shape[0], add, jnp.zeros_like(u))


def block_parts(p, h, pos3, model: dict, q_chunk: int = 512):
    """One layer on h [B, T, D], taken apart: ``out`` and, for the check on
    the chip, the attention's input ``x`` and parts (``attend_parts``), the
    expert layer's input ``u``, the routing, the experts' part ``routed`` and
    ``boundary``, the gap between the k-th and the (k+1)-th router score."""
    p = _f32(p)
    eps, k = model["rms_norm_eps"], model["num_experts_per_tok"]
    x = rms(h, p["attn_norm"], eps)
    att = attend_parts(p, x, pos3, model, q_chunk)
    h = h + att["out"]
    u = rms(h, p["ffn_norm"], eps)
    idx, w, r = routing(p, u, model)
    routed = routed_part(p, u, model, idx, w)
    top = jax.lax.top_k(r, k + 1)[0]
    return {"out": h + routed, "x": x, "attention": att, "u": u, "idx": idx,
            "w": w, "routed": routed, "boundary": top[..., k - 1] - top[..., k]}


def block(p, h, pos3, model: dict, q_chunk: int = 512):
    return block_parts(p, h, pos3, model, q_chunk)["out"]


def embed(params, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def hidden(params, tokens, model: dict, pos3=None):
    """tokens [B, T] -> hidden states [B, T, D], float32."""
    pos3 = text_positions(*tokens.shape) if pos3 is None else pos3
    h = embed(params, tokens)
    for p in params["layers"]:
        h = block(p, h, pos3, model)
    return h


def logits(params, h, model: dict):
    g = params["final_norm"].astype(jnp.float32)
    return rms(h, g, model["rms_norm_eps"]) @ params["head"].astype(jnp.float32)
