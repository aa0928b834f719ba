"""Plain float32 reference of the ``trinity`` family (Arcee Trinity, ``afmoe``),
independent of ``models/trinity.py``: the full forward in ``jax.numpy``, dense
masks, queries in blocks so that a 9k sequence's scores fit, a Python loop
over experts. No kernel, no cache, no sort, no bf16. Callers wrap it in
``jax.default_matmul_precision("highest")``.

``rms(x; g) = g x / sqrt(mean(x^2) + eps)``; no bias anywhere; D hidden, H
query heads over G K/V heads of d lanes (query head h reads K/V head
``h // (H / G)``).

- ``h = E[token] * sqrt(D)`` (``mup_enabled``).
- Attention of layer l, of kind ``layer_types[l]``: ``x = rms(h; g_in)``; ``q
  = rms_d(x Wq; gq)``, ``k = rms_d(x Wk; gk)`` over each head's d lanes, ``v =
  x Wv``. On a ``sliding_attention`` layer q and k are rotated by ``R_t``
  (``rope_theta``, the whole head, pairs (i, i + d/2), no scaling) and query
  i sees key j iff ``j <= i`` and ``i - j < sliding_window``; on a
  ``full_attention`` layer they are not rotated and i sees every ``j <= i``.
  Scores over ``sqrt(d)``, softmax; ``a = (P v) * sigmoid(x Wgate)`` lane by
  lane; ``h <- h + rms(a Wo; g_post_attn)``.
- Feed-forward: ``u = rms(h; g_pre_mlp)``; a dense SwiGLU ``(silu(u Wg) * (u
  Wu)) Wd`` in the first ``num_dense_layers`` layers; after them ``s =
  sigmoid(u Wr)``, the ``num_experts_per_tok`` experts with the largest ``s +
  b``, weights ``s[chosen] / (sum s[chosen] + 1e-20) * route_scale``, ``F =
  swiglu_shared(u) + sum over chosen RESIDENT e of w_e swiglu_e(u)`` (what the
  absent experts would add is left out, as the program leaves it out); ``h <-
  h + rms(F; g_post_mlp)``.
- ``logits = rms(h; g_final) W_head``.

It reads the program's parameter tree, because the weights under test are the
program's: ``experts`` stacks the resident experts on a leading axis, expert
``expert_first + i`` at index i.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30
SLIDING = "sliding_attention"


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]


def rotate(x, model: dict):
    """``R_t`` on x [B, T, heads, d] at positions 0 .. T-1."""
    d = model["head_dim"]
    inv = model["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def qkv(p, x, model: dict, kind: str):
    """x [B, T, D] (normalised) -> q [B, T, H, d], k and v [B, T, G, d]."""
    B, T, _ = x.shape
    H, G, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q = rms((x @ p["wq"]).reshape(B, T, H, d), p["q_norm"], eps)
    k = rms((x @ p["wk"]).reshape(B, T, G, d), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(B, T, G, d)
    if kind == SLIDING:
        q, k = rotate(q, model), rotate(k, model)
    return q, k, v


def attend(q, k, v, window, q_block: int):
    """softmax(q k^T / sqrt(d)) v under the causal mask and, where ``window``
    is a number, ``i - j < window``: q [B, T, H, d], k / v [B, T, G, d] ->
    [B, T, H * d]; query head h reads K/V head ``h // (H / G)``. ``q_block``
    queries at a time (the last pass padded with zero queries, and cut): a
    tile size, not semantics."""
    B, T, H, d = q.shape
    G = k.shape[2]
    at = jnp.arange(T)

    def some(first, q_c):
        i = first + jnp.arange(q_c.shape[1])
        seen = at[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - at[None, :] < window
        q_c = q_c.reshape(B, -1, G, H // G, d)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_c, k) / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", w, v).reshape(B, -1, H * d)

    if T <= q_block:
        return some(0, q)
    n = -(-T // q_block)
    q = jnp.pad(q, ((0, 0), (0, n * q_block - T), (0, 0), (0, 0)))
    chunks = jnp.moveaxis(q.reshape(B, n, q_block, H, d), 1, 0)
    out = jax.lax.map(lambda a: some(a[0] * q_block, a[1]), (jnp.arange(n), chunks))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * q_block, H * d)[:, :T]


def attend_at(q, at, k, v, window):
    """Attention of single queries: q [S, H, d] at positions ``at`` [S] over
    the keys k / v [T, G, d] of one sequence -> [S, H * d]."""
    S, H, d = q.shape
    G = k.shape[1]
    j = jnp.arange(k.shape[0])
    seen = j[None, :] <= at[:, None]
    if window is not None:
        seen &= at[:, None] - j[None, :] < window
    s = jnp.einsum("sgrd,kgd->sgrk", q.reshape(S, G, H // G, d), k) / math.sqrt(d)
    w = jax.nn.softmax(jnp.where(seen[:, None, None], s, NEG), axis=-1)
    return jnp.einsum("sgrk,kgd->sgrd", w, v).reshape(S, H * d)


def attention_parts(p, h, model: dict, kind: str, q_block: int):
    """A block's attention, taken apart; ``p`` in any dtype (its attention
    weights are read). ``x`` the normalised input, q / k / v, ``pv``
    attention's output before the gate, ``gate``, ``branch`` what joins the
    residual, ``out`` the residual after it."""
    p = _f32({k: p[k] for k in ("attn_norm", "wq", "wk", "wv", "wgate", "q_norm",
                                "k_norm", "wo", "post_attn_norm")})
    eps = model["rms_norm_eps"]
    x = rms(h, p["attn_norm"], eps)
    q, k, v = qkv(p, x, model, kind)
    pv = attend(q, k, v, model["sliding_window"] if kind == SLIDING else None, q_block)
    gate = jax.nn.sigmoid(x @ p["wgate"])
    branch = rms((pv * gate) @ p["wo"], p["post_attn_norm"], eps)
    return {"x": x, "q": q, "k": k, "v": v, "pv": pv, "gate": gate,
            "branch": branch, "out": h + branch}


def routing(p, u, model: dict):
    """u [..., D] -> (chosen experts [..., k], their weights [..., k], the
    biased scores [..., n_routed])."""
    sc = jax.nn.sigmoid(u @ p["router"])
    biased = sc + p["router_bias"]
    _, idx = jax.lax.top_k(biased, model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(sc, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, w * model["route_scale"], biased


def routed_part(experts, u, model: dict, idx, w):
    """``sum over chosen e of w_e E_e(u)`` over the RESIDENT experts;
    ``experts`` in ANY dtype, stacked on a leading axis. A loop an expert, the
    expert cast up inside it: 32 of them in float32 at once are 3.6 GB, which
    a chip that also holds the served weights does not have."""
    first = model.get("expert_first", 0)

    def one(i, out):
        w_e = jnp.sum(jnp.where(idx == first + i, w, 0.0), axis=-1)  # 0 where not chosen
        expert = {name: jax.lax.dynamic_index_in_dim(x, i, keepdims=False).astype(jnp.float32)
                  for name, x in experts.items()}
        return out + w_e[..., None] * swiglu(expert, u)

    return jax.lax.fori_loop(0, experts["wg"].shape[0], one, jnp.zeros_like(u))


def _by_rows(fn, x, block: int):
    """``fn`` over ``block`` rows of x [B, T, D] at a time (a tile size)."""
    B, T, D = x.shape
    if T <= block:
        return fn(x)
    n = -(-T // block)
    x = jnp.pad(x, ((0, 0), (0, n * block - T), (0, 0)))
    out = jax.lax.map(fn, jnp.moveaxis(x.reshape(B, n, block, D), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * block, -1)[:, :T]


def ffn_parts(p, h, model: dict, row_block: int):
    """A block's feed-forward half on the residual ``h`` [B, T, D] (after
    attention), taken apart; ``p`` in any dtype. ``u`` the feed-forward's
    input, ``out`` the layer's output and, for an expert layer, the routing
    (``idx``, ``w``), the resident experts' part of the output (``routed``)
    and ``boundary``, the gap between the k-th and the (k+1)-th biased score
    (under it a rounding can change the chosen set). The dense and the shared
    SwiGLU go ``row_block`` rows at a time (a tile size)."""
    experts = p.get("experts")
    p = _f32({k: v for k, v in p.items() if k in (
        "ffn_norm", "post_ffn_norm", "dense", "shared", "router", "router_bias")})
    eps = model["rms_norm_eps"]
    u = rms(h, p["ffn_norm"], eps)
    if experts is None:
        f, more = _by_rows(lambda x: swiglu(p["dense"], x), u, row_block), {}
    else:
        k = model["num_experts_per_tok"]
        idx, w, biased = routing(p, u, model)
        routed = routed_part(experts, u, model, idx, w)
        top, _ = jax.lax.top_k(biased, k + 1)
        f = _by_rows(lambda x: swiglu(p["shared"], x), u, row_block) + routed
        more = {"idx": idx, "w": w, "routed": routed,
                "boundary": top[..., k - 1] - top[..., k]}
    return {"u": u, **more, "out": h + rms(f, p["post_ffn_norm"], eps)}


def block_parts(p, h, model: dict, kind: str, q_block: int):
    """One layer of ``kind`` on h [B, T, D], taken apart: ``attention``
    (``attention_parts``) and the keys of ``ffn_parts``."""
    att = attention_parts(p, h, model, kind, q_block)
    return {"attention": att, **ffn_parts(p, att["out"], model, q_block)}


def embed(params, tokens, model: dict):
    h = params["embed"].astype(jnp.float32)[tokens]
    return h * math.sqrt(model["hidden_size"]) if model.get("mup_enabled", True) else h


def hidden(params, tokens, model: dict, q_block: int = 256):
    """tokens [B, T] -> hidden states [B, T, D], float32."""
    h = embed(params, tokens, model)
    for l, p in enumerate(params["layers"]):
        h = block_parts(p, h, model, model["layer_types"][l], q_block)["out"]
    return h


def logits(params, h, model: dict):
    g = params["final_norm"].astype(jnp.float32)
    return rms(h, g, model["rms_norm_eps"]) @ params["head"].astype(jnp.float32)
