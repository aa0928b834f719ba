"""Plain float32 reference of the repo's one transformer, independent of
``models/transformer.py``: forward, MLM / causal-LM loss and (through
``jax.grad``) gradients in ``jax.numpy``, no kernel, no cache, no bf16.

It follows the published BERT / GPT-2 block with the departures the
configuration files list: post- or pre-norm by ``norm_position``, erf or tanh
GELU by ``gelu_approximate``, LayerNorm eps 1e-12 everywhere, an embedding
LayerNorm, segment row 0 added to every token, and the repo's head (dense,
GELU, LayerNorm, tied decoder plus bias) with no final ``ln_f``. It reads the
program's parameter tree, because the weights under test are the program's.
Callers wrap it in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs in bf16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30


def _ln(x, scale, bias, eps=1e-12):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def hidden(params, tokens, model: dict, pad_mask=None):
    """tokens [B,T] -> hidden states [B,T,D], float32."""
    p = _f32(params)
    B, T = tokens.shape
    H = model["n_heads"]
    D = model["d_model"]
    hd = D // H
    pre = model.get("norm_position", "pre") == "pre"
    causal = bool(model.get("causal", False))
    approx = bool(model.get("gelu_approximate", True))

    e = p["embed"]
    h = e["tok"][tokens] + e["pos"][:T][None] + e["seg"][0]
    h = _ln(h, e["ln_scale"], e["ln_bias"])

    allowed = jnp.ones((B, 1, T, T), bool)
    if causal:
        allowed = allowed & jnp.tril(jnp.ones((T, T), bool))[None, None]
    if pad_mask is not None:
        allowed = allowed & (pad_mask > 0)[:, None, None, :]

    def attn(x, b):
        qkv = x @ b["qkv_w"] + b["qkv_b"]
        q, k, v = (t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(allowed, s, NEG), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
        return o.transpose(0, 2, 1, 3).reshape(B, T, D) @ b["out_w"] + b["out_b"]

    def ffn(x, b):
        x = jax.nn.gelu(x @ b["ffn_w1"] + b["ffn_b1"], approximate=approx)
        return x @ b["ffn_w2"] + b["ffn_b2"]

    def block(h, b):
        if pre:
            h = h + attn(_ln(h, b["ln1_scale"], b["ln1_bias"]), b)
            h = h + ffn(_ln(h, b["ln2_scale"], b["ln2_bias"]), b)
        else:
            h = _ln(h + attn(h, b), b["ln1_scale"], b["ln1_bias"])
            h = _ln(h + ffn(h, b), b["ln2_scale"], b["ln2_bias"])
        return h, None

    # one scanned, rematerialised block, not a loop unrolled over the layers:
    # at "highest" precision the unrolled gradient program of 24 layers took
    # 90-100 s to compile and 0.64 GB of code on the chip (my chip runs, PR 26)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *p["blocks"])
    h, _ = jax.lax.scan(jax.checkpoint(block), h, stacked)
    return h


def logits(params, h, model: dict, positions=None):
    """The repo's head on hidden states [B,T,D] (or at ``positions`` [B,P])."""
    p = _f32({"mlm": params["mlm"], "tok": params["embed"]["tok"]})
    m = p["mlm"]
    if positions is not None:
        h = jnp.take_along_axis(h, positions[..., None], axis=1)
    x = jax.nn.gelu(h @ m["w"] + m["b"],
                    approximate=bool(model.get("gelu_approximate", True)))
    x = _ln(x, m["ln_scale"], m["ln_bias"])
    return x @ p["tok"].T + m["out_bias"]


def loss(params, batch, model: dict):
    """Weighted token cross-entropy, as the program's ``loss_fn`` defines it:
    at ``mlm_positions`` where the batch has them, else at every position."""
    h = hidden(params, batch["tokens"], model, batch.get("pad_mask"))
    lg = logits(params, h, model, batch.get("mlm_positions"))
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["labels"][..., None], axis=-1)[..., 0]
    w = batch["weights"]
    return jnp.sum((logz - gold) * w) / jnp.maximum(jnp.sum(w), 1.0)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))
