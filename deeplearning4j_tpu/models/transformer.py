"""Flagship transformer (BERT-base family) — TPU-first functional model.

Reference parity: the reference's BERT story is a TF-imported SameDiff graph
(SURVEY §3.3: TFGraphMapper → ~1.2k-node graph executed op-by-op, one JNI
round-trip per node). Here the model is a pure JAX function: the whole
forward+backward+updater step compiles to ONE XLA executable, and
parallelism is declared with a PartitionSpec tree over a
``jax.sharding.Mesh`` instead of the reference's Aeron parameter server
(SURVEY §2.10).

Mesh axes (any subset may be present):
- ``dp`` — data parallel (batch sharding; gradient allreduce over ICI)
- ``tp`` — tensor parallel (Megatron column/row splits on attention + MLP;
  which matrix splits which way is stated once, by role, in
  ``parallel.partition.SpecLayout``: ``Partitioner.spec_tree(params)``)
- ``sp`` — sequence/context parallel (ring attention over the ICI ring)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.attention import (dot_product_attention, head_axis, ring_attention,
                                 ulysses_attention)
from ..kernels.paged_attention import paged_decode_attention
from .paged_decode import (  # the pool's names are this module's too
    BlockAllocator,
    KvCacheLostError,
    NoFreeBlocksError,
    PagedDecodeSlotPool,
    _write_window,
)


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 30522          # BERT-base WordPiece vocab
    max_len: int = 512
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    type_vocab: int = 2              # segment ids (BERT)
    causal: bool = False             # False = BERT encoder, True = GPT-style LM
    dropout: float = 0.1
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16  # bf16 matmuls on the MXU, fp32 master params
    attn_impl: str = "auto"          # auto | xla | flash | ring | ulysses
    sequence_axis: Optional[str] = None  # mesh axis for ring attention ("sp")
    remat: bool = False              # jax.checkpoint each block (HBM for FLOPs)
    norm_position: str = "pre"       # "pre" (GPT-style, default) | "post" (original BERT)
    gelu_approximate: bool = True    # False = erf gelu (HF BERT parity)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def decode_family(self):
        """What the paged slot pool runs for this config's layers."""
        return TransformerDecodeFamily(self)

    @staticmethod
    def bert_base(**kw) -> "TransformerConfig":
        return TransformerConfig(**kw)

    @staticmethod
    def bert_large(**kw) -> "TransformerConfig":
        kw.setdefault("d_model", 1024)
        kw.setdefault("n_heads", 16)
        kw.setdefault("n_layers", 24)
        kw.setdefault("d_ff", 4096)
        return TransformerConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "TransformerConfig":
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("max_len", 128)
        kw.setdefault("d_model", 128)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_layers", 2)
        kw.setdefault("d_ff", 512)
        return TransformerConfig(**kw)


# ---------------------------------------------------------------------- init


def init_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    dt = cfg.param_dtype
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    std = 0.02

    def dense(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dt)

    keys = iter(jax.random.split(key, 6 + 8 * cfg.n_layers))
    params: Dict[str, Any] = {
        "embed": {
            "tok": dense(next(keys), (V, D)),
            "pos": dense(next(keys), (cfg.max_len, D)),
            "seg": dense(next(keys), (cfg.type_vocab, D)),
            "ln_scale": jnp.ones((D,), dt),
            "ln_bias": jnp.zeros((D,), dt),
        },
        "blocks": [],
        "mlm": {
            "w": dense(next(keys), (D, D)),
            "b": jnp.zeros((D,), dt),
            "ln_scale": jnp.ones((D,), dt),
            "ln_bias": jnp.zeros((D,), dt),
            "out_bias": jnp.zeros((V,), dt),
        },
    }
    for _ in range(cfg.n_layers):
        params["blocks"].append({
            "qkv_w": dense(next(keys), (D, 3 * D)),
            "qkv_b": jnp.zeros((3 * D,), dt),
            "out_w": dense(next(keys), (D, D)),
            "out_b": jnp.zeros((D,), dt),
            "ln1_scale": jnp.ones((D,), dt), "ln1_bias": jnp.zeros((D,), dt),
            "ffn_w1": dense(next(keys), (D, F)),
            "ffn_b1": jnp.zeros((F,), dt),
            "ffn_w2": dense(next(keys), (F, D)),
            "ffn_b2": jnp.zeros((D,), dt),
            "ln2_scale": jnp.ones((D,), dt), "ln2_bias": jnp.zeros((D,), dt),
        })
    return params


def batch_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """Input sharding: batch over dp, sequence over sp (if present)."""
    sp = cfg.sequence_axis
    tok = P("dp", sp)
    return {"tokens": tok, "segments": tok, "labels": tok, "weights": tok,
            "mlm_positions": P("dp", None), "pad_mask": tok}


# ------------------------------------------------------------------- forward


def _layer_norm(x, scale, bias, eps=1e-12):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def _attention(cfg: TransformerConfig, q, k, v, pad_mask):
    if cfg.attn_impl in ("ring", "ulysses") and cfg.sequence_axis:
        # sequence-sharded attention inside shard_map; head axis may be
        # tp-sharded at the same time — specs reference only present axes.
        # ring = ppermute pipeline (longest T); ulysses = 2 all-to-alls
        # swapping seq↔head sharding (lower latency at moderate T).
        kernel = ring_attention if cfg.attn_impl == "ring" else ulysses_attention
        mesh = jax.sharding.get_abstract_mesh()
        tp = "tp" if "tp" in mesh.axis_names else None
        dp = "dp" if "dp" in mesh.axis_names else None
        spec = P(dp, tp, cfg.sequence_axis, None)
        if pad_mask is not None:
            mspec = P(dp, cfg.sequence_axis)
            f = jax.shard_map(
                lambda a, b, c, m: kernel(
                    a, b, c, axis_name=cfg.sequence_axis, causal=cfg.causal, key_mask=m),
                mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec,
            )
            return f(q, k, v, pad_mask)
        f = jax.shard_map(
            functools.partial(kernel, axis_name=cfg.sequence_axis, causal=cfg.causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        return f(q, k, v)
    if cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} requires sequence_axis (a mesh axis "
            "name) — silently falling back to dense attention would fake "
            "sequence parallelism")
    impl = cfg.attn_impl if cfg.attn_impl in ("xla", "flash", "auto") else "auto"
    return dot_product_attention(q, k, v, pad_mask, causal=cfg.causal, impl=impl)


def _layer(cfg: TransformerConfig, p, h, attend, rng=None, train=False):
    """The GPT-2 / BERT block, written once: h [..., D] -> (h, kept).

    Owns the pre/post-norm residual wiring, the qkv projection and split, the
    out projection, the feed-forward, the dropout and the dtype discipline
    (float32 masters cast to the compute dtype at each use, float32 norms).
    Attention is the caller's: ``attend(q, k, v) -> (o, kept)`` on tensors
    shaped like ``h``; what it wants kept (a prefill's K and V, a decode
    step's arenas) comes back beside the new ``h``."""
    cd = cfg.compute_dtype

    def attn_sub(x):
        o, kept = attend(*_project_qkv(cfg, p, x))
        o = o @ p["out_w"].astype(cd) + p["out_b"].astype(cd)
        return _dropout(o, cfg, rng, 0, train), kept

    def ffn_sub(x):
        x = jax.nn.gelu(x @ p["ffn_w1"].astype(cd) + p["ffn_b1"].astype(cd),
                        approximate=cfg.gelu_approximate)
        x = x @ p["ffn_w2"].astype(cd) + p["ffn_b2"].astype(cd)
        return _dropout(x, cfg, rng, 1, train)

    if cfg.norm_position == "pre":  # GPT-style pre-LN: h + f(LN(h))
        a, kept = attn_sub(_layer_norm(h, p["ln1_scale"], p["ln1_bias"]).astype(cd))
        h = h + a.astype(h.dtype)
        h = h + ffn_sub(_layer_norm(h, p["ln2_scale"], p["ln2_bias"]).astype(cd)).astype(h.dtype)
        return h, kept
    # original-BERT post-LN: LN(h + f(h))  (required for faithful HF import)
    a, kept = attn_sub(h.astype(cd))
    h = _layer_norm(h + a.astype(h.dtype), p["ln1_scale"], p["ln1_bias"]).astype(h.dtype)
    h = _layer_norm(h + ffn_sub(h.astype(cd)).astype(h.dtype),
                    p["ln2_scale"], p["ln2_bias"]).astype(h.dtype)
    return h, kept


#: blocks traced with q, k and v split by heads in the weight (``_project_qkv``)
head_major_blocks = 0


def _project_qkv(cfg: TransformerConfig, p, x):
    """x [..., D] -> q, k, v, each [..., D].

    Where the ambient mesh splits heads over an axis (``head_axis``: the one
    flash attention's ``shard_map`` puts them on), ``qkv_w`` is viewed
    head-major, [D, 3, H, hd], and pinned split by heads over that axis, so
    the projection hands every device its own heads of q, k and v. The
    stored columns (q, then k, then v, split contiguously over ``tp``) then
    move as a weight, not as activations, and that weight is known before
    the block's input is. Elsewhere: one matmul and a split."""
    global head_major_blocks
    cd = cfg.compute_dtype
    ax = head_axis(cfg.n_heads)
    if ax is None:
        qkv = x @ p["qkv_w"].astype(cd) + p["qkv_b"].astype(cd)
        return jnp.split(qkv, 3, axis=-1)
    head_major_blocks += 1
    H, hd = cfg.n_heads, cfg.head_dim
    # cast first, so what crosses chips is the compute dtype
    w = jax.lax.with_sharding_constraint(p["qkv_w"].astype(cd).reshape(-1, 3, H, hd),
                                         P(P.UNCONSTRAINED, None, ax, None))
    b = jax.lax.with_sharding_constraint(p["qkv_b"].astype(cd).reshape(3, H, hd),
                                         P(None, ax, None))
    qkv = jnp.einsum("...d,dshe->...she", x, w) + b
    return [qkv[..., i, :, :].reshape(*x.shape[:-1], H * hd) for i in range(3)]


def _whole_sequences(cfg: TransformerConfig, pad_mask):
    """``attend`` of :func:`_layer` for whole sequences [B,T,D] (training,
    ``encode``, prefill): every position attends its own sequence through
    :func:`_attention`; keeps this layer's K and V, each [B,H,T,hd]."""
    H, hd = cfg.n_heads, cfg.head_dim

    def attend(q, k, v):
        B, T, D = q.shape
        # [B,T,D] -> [B,H,T,hd]
        q, k, v = (t.reshape(B, T, H, hd).transpose(0, 2, 1, 3) for t in (q, k, v))
        o = _attention(cfg, q, k, v, pad_mask)
        return o.transpose(0, 2, 1, 3).reshape(B, T, D), (k, v)

    return attend


def _paged_window(cfg: TransformerConfig, kc, vc, layer: int, tables, limits):
    """``attend`` of :func:`_layer` for a W-token decode window [S,W,D] over
    paged K/V: kc / vc [L, n_blocks, block_T, H*hd] are the WHOLE arenas,
    updated in place at ``layer`` and kept; tables [S, max_blocks] map
    logical to physical blocks; token w of slot s sits at position
    ``limits[s, w] - 1`` and attends its slot's first ``limits[s, w]`` keys
    (0: a dead slot)."""

    def attend(q, k, v):
        # write-before-read: this window's K/V land in their cells first, so
        # stale/garbage cells at <= attended positions never survive a step
        nkc = _write_window(kc, layer, tables, limits, k)
        nvc = _write_window(vc, layer, tables, limits, v)
        o = paged_decode_attention(q, nkc, nvc, tables, limits,
                                   layer=layer, n_heads=cfg.n_heads)
        return o, (nkc, nvc)

    return attend


def _block(cfg: TransformerConfig, p, h, pad_mask, rng, train):
    """One block over whole sequences (the name ``parallel/pipeline.py`` scans)."""
    return _layer(cfg, p, h, _whole_sequences(cfg, pad_mask), rng, train)[0]


def _dropout(x, cfg, rng, salt, train):
    if not train or cfg.dropout <= 0.0 or rng is None:
        return x
    keep = 1.0 - cfg.dropout
    mask = jax.random.bernoulli(jax.random.fold_in(rng, salt), keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def embed(params, tokens, cfg: TransformerConfig, *, segments=None,
          positions=None):
    """Embedding front-end: tokens [B,T] → block input [B,T,D] (compute dtype).
    ``positions`` (shaped like ``tokens``; a decode window's) default to
    0..T-1 of every row."""
    e = params["embed"]
    h = e["tok"][tokens] + (e["pos"][:tokens.shape[-1]][None] if positions is None
                            else e["pos"][positions])
    if segments is not None:
        h = h + e["seg"][segments]
    elif cfg.type_vocab > 0:
        h = h + e["seg"][0]  # BERT semantics: token_type defaults to segment 0
    return _layer_norm(h, e["ln_scale"], e["ln_bias"]).astype(cfg.compute_dtype)


def mlm_head(params, h, cfg: TransformerConfig, *, positions=None):
    """MLM head with tied output embedding: [B,T,D] → logits [B,T,V] fp32.

    ``positions``: optional int32 [B, P] — compute the head ONLY at those
    positions (TF-BERT's ``masked_lm_positions`` contract): at T=128 /
    ~20 masked tokens this cuts the dominant D×V tied-decoder projection
    ~6×. The projection runs with compute-dtype (bf16) operands and fp32
    MXU accumulation — v5e executes fp32 matmul many times slower than
    bf16, and this projection is the single largest matmul in the step
    (VERDICT r4 weak #3).
    """
    m = params["mlm"]
    cd = cfg.compute_dtype
    if positions is not None:
        h = jnp.take_along_axis(h, positions[..., None], axis=1)  # [B,P,D]
    x = jax.nn.gelu(h.astype(cd) @ m["w"].astype(cd) + m["b"].astype(cd),
                    approximate=cfg.gelu_approximate)
    x = _layer_norm(x, m["ln_scale"], m["ln_bias"])
    logits = jax.lax.dot_general(
        x.astype(cd), params["embed"]["tok"].astype(cd),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits + m["out_bias"].astype(jnp.float32)


def token_ce_loss(logits, labels, weights=None):
    """Weighted token cross-entropy (masked-LM and causal-LM alike)."""
    if weights is None:
        weights = jnp.ones(labels.shape, jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def forward(params, tokens, cfg: TransformerConfig, *, segments=None, pad_mask=None,
            rng=None, train: bool = False):
    """tokens [B,T] int32 → logits [B,T,V] (float32)."""
    return mlm_head(params, encode(params, tokens, cfg, segments=segments,
                                   pad_mask=pad_mask, rng=rng, train=train), cfg)


def loss_fn(params, batch, cfg: TransformerConfig, rng=None, train: bool = True):
    """Weighted token cross-entropy — serves masked-LM (weights = mask
    positions) and causal-LM (weights = all positions) alike.

    If ``batch["mlm_positions"]`` ([B, P] int32) is present, the head and
    loss run only at those positions — ``labels``/``weights`` must then be
    [B, P] (gathered to position space), the TF-BERT pretraining layout.
    """
    pos = batch.get("mlm_positions")
    h = encode(params, batch["tokens"], cfg, segments=batch.get("segments"),
               pad_mask=batch.get("pad_mask"), rng=rng, train=train)
    logits = mlm_head(params, h, cfg, positions=pos)
    return token_ce_loss(logits, batch["labels"], batch.get("weights"))


def layer_costs(cfg: TransformerConfig, batch: int, seq: int,
                mlm_positions: Optional[int] = None,
                train: bool = True) -> list:
    """Per-layer cost rows for the functional transformer, in the same
    ``{layer, kind, flops, param_bytes, activation_bytes}`` schema as
    ``monitoring.costmodel.layer_costs`` — the embedding front-end, every
    block, and the MLM head get a row each, so the cost table can say which
    block family (attention vs FFN vs decoder) owns the step. Flops use the
    same 2·MAC accounting as XLA's ``cost_analysis()``; ``train=True``
    applies the fwd+bwd 3× factor (embedding gathers scatter-add on the
    backward, counted as bytes, not flops)."""
    B, T = batch, seq
    D, F, V, H = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_heads
    P = mlm_positions if mlm_positions is not None else T
    pbytes = int(jnp.dtype(cfg.param_dtype).itemsize)
    abytes = int(jnp.dtype(cfg.compute_dtype).itemsize)
    factor = 3.0 if train else 1.0

    # elementwise expansions as XLA's cost model counts them (measured on
    # the CPU HLO pipeline): numerically-stable softmax ≈ 32 flops/score,
    # tanh-approximate gelu ≈ 28 flops/element, fp32 layernorm ≈ 15/element
    SOFTMAX, GELU, LN = 32.0, 28.0, 15.0
    rows = [{
        "layer": "embed", "kind": "Embedding",
        # gathers move bytes; the layernorm + segment/position adds compute
        "flops": (LN * T * D) * B * factor,
        "param_bytes": (V * D + cfg.max_len * D + cfg.type_vocab * D + 2 * D) * pbytes,
        "activation_bytes": B * T * D * abytes,
    }]
    per_block_fwd = (
        2.0 * T * D * 3 * D        # qkv projection
        + 2.0 * T * D * D          # attention output projection
        + 4.0 * T * T * D          # QK^T and AV contractions
        + SOFTMAX * H * T * T      # stable softmax over the scores
        + 2.0 * 2.0 * T * D * F    # the two FFN matmuls
        + GELU * T * F             # gelu over the FFN hidden
        + 2.0 * LN * T * D         # the two layernorms
        + 2.0 * T * D)             # residual adds
    block_params = (D * 3 * D + 3 * D + D * D + D
                    + D * F + F + F * D + D + 4 * D) * pbytes
    for i in range(cfg.n_layers):
        rows.append({
            "layer": f"block{i}", "kind": "TransformerBlock",
            "flops": per_block_fwd * B * factor,
            "param_bytes": block_params,
            "activation_bytes": B * T * D * abytes,
        })
    rows.append({
        "layer": "mlm_head", "kind": "MlmHead",
        "flops": (2.0 * P * D * D       # dense projection
                  + GELU * P * D        # gelu on the projection
                  + LN * P * D          # layernorm
                  + 2.0 * P * D * V     # tied-decoder projection
                  + 8.0 * P * V         # token cross-entropy (logsumexp)
                  ) * B * factor,
        "param_bytes": (D * D + D + 2 * D + V) * pbytes,
        "activation_bytes": B * P * V * 4,  # fp32 logits
    })
    return rows


def make_train_step(cfg: TransformerConfig, updater):
    """One whole-graph XLA train step: loss+grads+updater+apply, donated state."""

    def step(params, opt_state, batch, iteration, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, rng, True)
        updates, new_opt = updater.apply(grads, opt_state, params, iteration, 0)
        new_params = jax.tree.map(lambda p, u: (p - u).astype(p.dtype), params, updates)
        return new_params, new_opt, loss

    return step


# ----------------------------------------------------- SQuAD fine-tune head
# (BASELINE configs[4]: "SameDiff BERT-base fine-tune (SQuAD)" — the
# reference's headline SameDiff training workload, SURVEY §6. The span
# head is the standard BertForQuestionAnswering shape: one dense [D,2]
# over the encoder output producing start/end logits.)


def encode(params, tokens, cfg: TransformerConfig, *, segments=None,
           pad_mask=None, rng=None, train: bool = False):
    """Encoder-only forward: tokens [B,T] → hidden states [B,T,D] (no head)."""
    h = embed(params, tokens, cfg, segments=segments)
    block = functools.partial(_block, cfg)
    if cfg.remat:
        block = jax.checkpoint(block, static_argnums=(4,))  # train: a Python bool
    for i, p in enumerate(params["blocks"]):
        sub = jax.random.fold_in(rng, i) if rng is not None else None
        h = block(p, h, pad_mask, sub, train)
    return h


def init_qa_head(key, cfg: TransformerConfig):
    """Span head params: {'w': [D,2], 'b': [2]}."""
    import numpy as _np

    w = jax.random.normal(key, (cfg.d_model, 2), jnp.float32)
    return {"w": w * _np.float32(0.02), "b": jnp.zeros((2,), jnp.float32)}


def qa_forward(params, qa_params, tokens, cfg: TransformerConfig, *,
               segments=None, pad_mask=None, rng=None, train: bool = False):
    """→ (start_logits [B,T], end_logits [B,T]) fp32."""
    h = encode(params, tokens, cfg, segments=segments, pad_mask=pad_mask,
               rng=rng, train=train)
    logits = h.astype(jnp.float32) @ qa_params["w"] + qa_params["b"]
    return logits[..., 0], logits[..., 1]


def qa_loss_fn(params, qa_params, batch, cfg: TransformerConfig, rng=None,
               train: bool = True):
    """Mean of start/end-position cross-entropies (BertForQuestionAnswering
    objective). batch: tokens, segments (question=0/context=1),
    start_positions [B], end_positions [B], optional pad_mask."""
    s_logits, e_logits = qa_forward(params, qa_params, batch["tokens"], cfg,
                                    segments=batch.get("segments"),
                                    pad_mask=batch.get("pad_mask"),
                                    rng=rng, train=train)

    def ce(logits, pos):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, pos[:, None], axis=1)[:, 0]
        return jnp.mean(logz - gold)

    return 0.5 * (ce(s_logits, batch["start_positions"])
                  + ce(e_logits, batch["end_positions"]))


# ------------------------------------------------------- autoregressive decode
# Served through ``paged_decode.PagedDecodeSlotPool``; this file holds what the
# pool runs for a causal config: the family below, over the one ``_layer``.


def prefill_forward(params, tokens, cfg: TransformerConfig, *, segments=None,
                    pad_mask=None):
    """Causal encoder forward that also returns per-layer K/V.

    tokens [B,T] → (hidden [B,T,D], k [L,B,H,T,hd], v [L,B,H,T,hd]) — the
    same math as :func:`encode` (inference, no dropout), with each block's
    projected keys/values captured for the KV cache."""
    h = embed(params, tokens, cfg, segments=segments)
    attend = _whole_sequences(cfg, pad_mask)
    ks, vs = [], []
    for p in params["blocks"]:
        h, (k, v) = _layer(cfg, p, h, attend)
        ks.append(k)
        vs.append(v)
    return h, jnp.stack(ks), jnp.stack(vs)


# a block's leaves that ``_layer`` casts to the compute dtype at every use
_BLOCK_MATMUL_LEAVES = ("qkv_w", "qkv_b", "out_w", "out_b",
                        "ffn_w1", "ffn_b1", "ffn_w2", "ffn_b2")


def _in_dtype(x, dtype):
    """``x`` in ``dtype``: itself when it already is, a shape for a shape."""
    if x.dtype == dtype:
        return x
    if isinstance(x, jax.ShapeDtypeStruct):
        return x.update(dtype=dtype)
    return jnp.asarray(x, dtype)


class TransformerDecodeFamily:
    """This file's layers for the slot pool (the protocol is in
    ``paged_decode``'s docstring): every head has its own K and V, so a token
    stores ``H*hd`` values in each of two arenas."""

    speculative = True   # ``decode_window`` takes W = spec_tokens + 1 tokens
    shares_prefix = True  # every block lives as long as its request
    stat_names = ()      # a step counts nothing of its own
    name = "transformer"

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.n_layers = cfg.n_layers
        self.cache_widths = (cfg.n_heads * cfg.head_dim,) * 2
        self.cache_dtype = cfg.compute_dtype

    def resident(self, params):
        """The tree this family's programs read, made once from the caller's
        (float32 master) params: every leaf that each use site casts to the
        compute dtype before use, cast now, so a step reads half the bytes and
        converts nothing. ``embed`` passes through (the lookup reads float32
        rows); the tied table as the HEAD reads it is a second, compute-dtype
        leaf under ``head``. A leaf already in its dtype is the same array."""
        cd = self.cfg.compute_dtype

        def cast(p, names):
            return {k: _in_dtype(v, cd) if k in names else v for k, v in p.items()}

        return {**params,
                "blocks": [cast(p, _BLOCK_MATMUL_LEAVES) for p in params["blocks"]],
                "mlm": cast(params["mlm"], ("w", "b")),
                "head": {"tok": _in_dtype(params["embed"]["tok"], cd)}}

    def prefill(self, params, tokens, length):
        """tokens [1, Tb] -> (hidden state at ``length - 1`` [D], the rows to
        store: K and V, each [L, Tb, H*hd])."""
        h, ks, vs = prefill_forward(params, tokens, self.cfg)

        def rows(x):  # [L, 1, H, Tb, hd] -> [L, Tb, H*hd]
            x = jnp.transpose(x[:, 0], (0, 2, 1, 3))
            return x.reshape(*x.shape[:2], -1)

        return h[0, length - 1], (rows(ks), rows(vs))

    def head(self, params, h):
        # the head's view of the resident tree: the table in the compute dtype
        return mlm_head({"mlm": params["mlm"], "embed": params["head"]}, h,
                        self.cfg)

    def decode_window(self, params, tokens, positions, arenas, tables):
        """tokens / positions [S, W] -> (logits [S, W, V] fp32, arenas, None).
        The K and V arenas are written in place layer by layer. A slot is
        live iff its logical block 0 is mapped (a released slot's table row
        is all trash)."""
        cfg = self.cfg
        kc, vc = arenas
        h = embed(params, tokens, cfg, positions=positions)
        limits = jnp.where(tables[:, :1] > 0, positions + 1, 0)
        for l, p in enumerate(params["blocks"]):
            h, (kc, vc) = _layer(cfg, p, h,
                                 _paged_window(cfg, kc, vc, l, tables, limits))
        return self.head(params, h), (kc, vc), None

    def cumulative_stats(self, sums, steps) -> Dict[str, int]:
        return {}


def generate(params, prompts, max_new_tokens: int,
             cfg: TransformerConfig, *, slots: Optional[int] = None,
             eos_id: Optional[int] = None, max_len: Optional[int] = None,
             pool=None, draft_params=None, draft_cfg=None,
             spec_tokens: int = 4):
    """Greedy batch generation through a decode pool (offline API).

    ``prompts``: sequence of 1-D int token sequences (ragged ok). Returns a
    list of generated-token lists, one per prompt, each ending at
    ``eos_id`` (inclusive) or ``max_new_tokens``. Admission is continuous:
    a finished sequence's slot is refilled immediately, so a batch of
    mixed-length generations never pads to its slowest member.

    When no ``pool`` is passed the driver builds a block-paged
    :class:`PagedDecodeSlotPool` with room for ``slots`` sequences of
    ``max_len``; pass ``draft_params``/``draft_cfg`` to decode speculatively
    — the output is token-identical to plain greedy by construction. A
    ``pool=`` may answer a step with ``{slot: tok}`` or ``{slot: [toks...]}``."""
    from collections import deque

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    prompts = list(prompts)
    if not prompts:
        return []
    if pool is None:
        T = max_len or cfg.max_len
        # the largest power-of-two block size (<= 16) that divides max_len,
        # so any model's positional range pages cleanly
        block_T = 16
        while T % block_T:
            block_T //= 2
        pool = PagedDecodeSlotPool(params, cfg,
                                   slots=slots or min(8, len(prompts)),
                                   eos_id=eos_id, max_len=max_len,
                                   block_T=block_T, draft_params=draft_params,
                                   draft_cfg=draft_cfg, spec_tokens=spec_tokens)
    eos = eos_id if eos_id is not None else pool.eos_id
    pending = deque(enumerate(prompts))
    live: Dict[int, list] = {}  # slot -> [prompt index, generated tokens]
    results: Dict[int, list] = {}
    while pending or live:
        while pending and pool.free_slots:
            idx, prompt = pending[0]
            try:
                slot, first = pool.admit(prompt, max_new_tokens)
            except Exception as e:
                # paged pools can be slot-free but block-full; drain the
                # live sequences and retry (an empty pool would admit, so
                # with nothing live this can never succeed — re-raise)
                if getattr(e, "retry_admission", False) and live:
                    break
                raise
            pending.popleft()
            if max_new_tokens == 1 or (eos is not None and first == eos):
                results[idx] = [first]
                pool.release(slot)
            else:
                live[slot] = [idx, [first]]
        if not live:
            continue
        for slot, step_toks in pool.step().items():
            if not isinstance(step_toks, (list, tuple)):
                step_toks = (step_toks,)
            idx, toks = live.get(slot, (None, None))
            if idx is None:
                continue
            for tok in step_toks:
                toks.append(tok)
                if len(toks) >= max_new_tokens or (eos is not None and tok == eos):
                    results[idx] = toks
                    pool.release(slot)
                    del live[slot]
                    break
    return [results[i] for i in range(len(prompts))]


def make_qa_train_step(cfg: TransformerConfig, updater):
    """Fine-tune step over (encoder params, qa head) jointly — the
    configs[4] workload. Shard the encoder as for pretraining
    (``Partitioner.spec_tree``); the head is replicated (2 columns shard
    nothing)."""

    def step(params, qa_params, opt_state, qa_opt_state, batch, iteration, rng):
        def lf(p, q):
            return qa_loss_fn(p, q, batch, cfg, rng, True)

        loss, (g_p, g_q) = jax.value_and_grad(lf, argnums=(0, 1))(params, qa_params)
        upd_p, new_opt = updater.apply(g_p, opt_state, params, iteration, 0)
        upd_q, new_qopt = updater.apply(g_q, qa_opt_state, qa_params, iteration, 0)
        new_params = jax.tree.map(lambda p, u: (p - u).astype(p.dtype), params, upd_p)
        new_qa = jax.tree.map(lambda p, u: p - u, qa_params, upd_q)
        return new_params, new_qa, new_opt, new_qopt, loss

    return step
