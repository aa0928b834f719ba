"""Plain float32 reference of the ``kimi_k2`` family (Kimi-K2.5's language
model, the DeepSeek-V3 layout), independent of ``models/kimi_k2.py``: the full
forward in ``jax.numpy``, expanded attention, a Python loop over experts with
dense masks. No kernel, no cache, no sort, no bf16. Callers wrap it in
``jax.default_matmul_precision("highest")``.

``x`` is a token's residual; ``rms(x; g) = g x / sqrt(mean(x^2) + eps)``; no
bias anywhere.

- Block: ``h = x + Attn(rms(x; g1))``, ``y = h + F(rms(h; g2))``; ``F`` is
  the dense SwiGLU ``(silu(u Wg) * (u Wu)) Wd`` in the first
  ``first_k_dense_replace`` layers and the expert layer after. Final ``rms``,
  then the untied head.
- Latent attention (expanded): ``cq = rms(u Wdq; gq)``, ``q = cq Wuq`` ->
  heads x (nope | rope); ``[ckv | kr] = u Wdkv``, ``c = rms(ckv; gkv)``,
  ``k_nope = c Wuk``, ``v = c Wuv`` a head; ``kr`` is ONE rotary key shared by
  all heads. ``q_rope = R_t(q_rope)``, ``kr = R_t(kr)``, ``k = [k_nope | kr]``,
  scores ``q . k * s``, causal softmax, ``o = p v``, ``out = concat(o) Wo``.
  ``s = (nope + rope)^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  cos / sin carry ``yarn(mscale) / yarn(mscale_all_dim)``. ``R_t`` uses YaRN's
  blended frequencies and pairs lane ``i`` with lane ``i + rope/2``
  (half-split; the interleaved layout of a checkpoint is a fixed permutation
  of ``Wuq`` / ``Wdkv`` columns away).
- Expert layer: ``sc = sigmoid(u Wr)``; choice by ``sc + b``
  (``e_score_correction_bias``), top k; ``n_group = topk_group = 1``, so the
  group step is the identity; weights ``sc[chosen] / (sum sc[chosen] + 1e-20)
  * routed_scaling_factor`` — the UNBIASED scores, normalised over ALL
  chosen. ``F(u) = Shared(u) + sum over chosen e of w_e E_e(u)``; given the
  resident set (``expert_first``, ``n_resident_experts``) the sum runs over
  the chosen experts that are resident: what the absent ones would add is
  left out, as the program leaves it out.

It reads the program's parameter tree, because the weights under test are the
program's: ``wuk`` / ``wuv`` are ``Wukv = [Wuk | Wuv]`` a head, stored apart;
``experts[i]`` is expert ``expert_first + i``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(model: dict, T: int):
    """(cos, sin) [T, rope] for positions 0..T-1, the two halves alike."""
    rs, dim = model["rope_scaling"], model["qk_rope_head_dim"]
    theta, factor = model["rope_theta"], rs["factor"]
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / factor

    def dim_of(beta):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (beta * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = inter * (1.0 - mask) + extra * mask
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    m = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rot(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(p, u, model: dict):
    """u [B, T, D] (normalised) -> [B, T, D]."""
    B, T, _ = u.shape
    H, eps = model["num_attention_heads"], model["rms_norm_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank = model["kv_lora_rank"]
    cos, sin = rotary(model, T)
    q = (rms(u @ p["wdq"], p["q_norm"], eps) @ p["wuq"]).reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckv = u @ p["wdkv"]
    c, kr = rms(ckv[..., :rank], p["kv_norm"], eps), ckv[..., rank:]
    k_nope = jnp.einsum("btc,chn->bthn", c, p["wuk"])
    v = jnp.einsum("btc,chv->bthv", c, p["wuv"])
    q_rope = _rot(q_rope, cos[None, :, None], sin[None, :, None])
    kr = _rot(kr, cos[None], sin[None])
    m = _mscale(model["rope_scaling"]["factor"],
                model["rope_scaling"]["mscale_all_dim"])
    s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhr,bkr->bhqk", q_rope, kr)) * ((nope + rope) ** -0.5 * m * m)
    allowed = jnp.tril(jnp.ones((T, T), bool))[None, None]
    w = jax.nn.softmax(jnp.where(allowed, s, NEG), axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", w, v).reshape(B, T, -1)
    return o @ p["wo"]


def routing(p, u, model: dict):
    """u [..., D] -> (chosen experts [..., k], their weights [..., k], the
    biased scores [..., n_routed])."""
    k = model["num_experts_per_tok"]
    sc = jax.nn.sigmoid(u @ p["router"])
    biased = sc + p["router_bias"]
    _, idx = jax.lax.top_k(biased, k)
    chosen = jnp.take_along_axis(sc, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, w * model["routed_scaling_factor"], biased


def routed_part(p, u, model: dict, idx, w):
    """``sum over chosen e of w_e E_e(u)`` over the RESIDENT experts."""
    out = jnp.zeros_like(u)
    for i, expert in enumerate(p["experts"]):
        e = model.get("expert_first", 0) + i
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)  # 0 where not chosen
        out = out + w_e[..., None] * swiglu(expert, u)
    return out


def expert_layer(p, u, model: dict):
    """Shared expert + the resident experts' part of the routed sum."""
    idx, w, _ = routing(p, u, model)
    return swiglu(p["shared"], u) + routed_part(p, u, model, idx, w)


def attend(p, h, model: dict):
    """The residual after a block's attention; ``p`` float32."""
    return h + attention(p, rms(h, p["attn_norm"], model["rms_norm_eps"]), model)


def block(p, h, model: dict):
    """One layer on h [B, T, D]; ``p`` may be in any dtype."""
    p = _f32(p)
    h = attend(p, h, model)
    u = rms(h, p["ffn_norm"], model["rms_norm_eps"])
    return h + (swiglu(p["dense"], u) if "dense" in p else expert_layer(p, u, model))


def sparse_block_parts(p, h, model: dict):
    """A sparse layer, taken apart for the check on the chip: the layer's
    output, the expert layer's input ``u``, the routing (experts, weights),
    the resident experts' part of the output, and two margins a position:
    ``boundary``, the gap between the k-th and the (k+1)-th biased score
    (under it a rounding can change the chosen set), and ``resident``, how
    far the nearest RESIDENT expert is from changing sides of that boundary
    (under it a rounding can change what this chip computes)."""
    p = _f32(p)
    k = model["num_experts_per_tok"]
    h = attend(p, h, model)
    u = rms(h, p["ffn_norm"], model["rms_norm_eps"])
    idx, w, biased = routing(p, u, model)
    routed = routed_part(p, u, model, idx, w)
    top, _ = jax.lax.top_k(biased, k + 1)
    kth, nxt = top[..., k - 1], top[..., k]
    first = model.get("expert_first", 0)
    mine = biased[..., first:first + model["n_resident_experts"]]
    gap = jnp.where(mine >= kth[..., None], mine - nxt[..., None], kth[..., None] - mine)
    return {"out": h + swiglu(p["shared"], u) + routed, "u": u, "idx": idx,
            "w": w, "routed": routed, "boundary": kth - nxt,
            "resident": jnp.min(gap, axis=-1)}


def embed(params, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def hidden(params, tokens, model: dict):
    """tokens [B, T] -> hidden states [B, T, D], float32."""
    h = embed(params, tokens)
    for p in params["layers"]:
        h = block(p, h, model)
    return h


def logits(params, h, model: dict):
    g = params["final_norm"].astype(jnp.float32)
    return rms(h, g, model["rms_norm_eps"]) @ params["head"].astype(jnp.float32)
