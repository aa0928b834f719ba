"""The ``kimi_k2`` layer family (the DeepSeek-V3 layout), as ONE chip of an
expert-parallel serving pool runs it.

A second family beside ``transformer.py``: RMSNorm, no bias anywhere, YaRN
rotary positions on a 64-wide slice of each head, SwiGLU feed-forwards,
latent (MLA) attention and routed experts with a shared expert. Weights are
held in ``param_dtype`` (bfloat16 as served: there are no float32 masters),
the residual stream and every softmax, norm and routing decision are
float32.

- **Latent attention** caches ONE row a token a layer, ``[c | kr]``: the
  normalised 512-wide latent and the rotated 64-wide key that all heads
  share. Prefill runs the *expanded* form (``k = [c Wuk | kr]``,
  ``v = c Wuv``, flash attention with 192-wide q/k); a decode step runs the
  *absorbed* form, in which the queries are taken into the latent space
  (``q_nope Wuk^T``) and attention is 64 heads over the one cached row
  (``kernels/paged_attention.py:paged_mla_decode_attention``). Same
  mathematics; ``tests/test_kimi_k2.py`` holds the two to each other.
- **Rotary pairing** is half-split: lane ``i`` of the 64 rotary lanes
  pairs with lane ``i + 32``. A checkpoint in the interleaved layout is
  brought to it by a fixed permutation of ``wuq`` / ``wdkv`` columns.
- **Expert layer.** The router is as wide as the PUBLISHED expert count and
  picks ``num_experts_per_tok`` by ``sigmoid + bias``; the weights are the
  unbiased scores normalised over all chosen, times
  ``routed_scaling_factor``. This chip is TOLD which experts it holds
  (``expert_first`` .. ``+ n_resident_experts``) and computes their part of
  the sum plus the shared expert; what absent experts would add is left out.
  No capacity and no dropped token: assignments are sorted by expert and
  each resident expert's rows go through its weights in tiles
  (``expert_tile``), under a loop whose trip count is the rows it really got, so
  an expert no live token chose is never read.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..kernels.attention import flash_attention, mha_reference
from ..kernels.paged_attention import paged_mla_decode_attention
from .paged_decode import _write_window

_PREFILL_BLOCK = 1024  # rows and keys of a flash-attention block in prefill

#: what one decode step counts of its routing, in this order
MOE_STATS = ("routed_tokens", "resident_assignments", "experts_touched",
             "load_max")


@dataclasses.dataclass
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 384      # the router's width: the published count
    expert_first: int = 0            # this chip holds experts
    n_resident_experts: int = 384    # [expert_first, expert_first + n_resident)
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 262144
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"          # prefill: auto | xla | flash
    moe_tile: int = 256              # rows of one expert matmul

    # what the slot pool asks of any config
    causal = True
    # what the expert layer (``route``) asks of any config: this family scores
    # a logit with a sigmoid
    scoring_func = "sigmoid"

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def compute_dtype(self):
        return self.param_dtype

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def cache_width(self) -> int:
        """Values one token stores a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def arena_width(self) -> int:
        """Lanes of a cached row: ``cache_width`` up to whole 128-lane tiles,
        the rest zeros. The TPU lays an array out in such tiles whatever its
        last dimension says, and its DMA engine moves whole ones only."""
        return -(-self.cache_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def decode_family(self):
        return LatentDecodeFamily(self)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: KimiK2Config):
    """YaRN's blended inverse frequencies of the ``rope/2`` lane pairs."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / cfg.rope_factor

    def correction(beta):
        return (dim * math.log(cfg.rope_original_max / (beta * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def _rotate(x, positions, cfg: KimiK2Config):
    """Half-split rotary on the last axis of float32 ``x``; ``positions``
    has x's leading shape, less any head axis given as a 1."""
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------- init


def is_sparse(cfg: KimiK2Config, layer: int) -> bool:
    return layer >= cfg.first_k_dense_replace


def init_params(key, cfg: KimiK2Config) -> Dict[str, Any]:
    """Random weights (normal, std 0.02) in ``param_dtype``; norm gains 1,
    the router and its correction bias float32. The bias is drawn non-zero
    so that the biased choice differs from the unbiased one where scores lie
    close, and SMALL (std 0.001, the size of one update of the aux-loss-free
    balancing rule): at the published widths the top 8 of 384 sigmoid scores
    lie within 0.03 of one another, so a bias of std 0.01 already moves an
    expert's popularity by half and one of 0.1 sends most tokens to the few
    experts it favours (PERF.md, PR 31). A trained bias balances the load;
    random routers are balanced to about a tenth without one."""
    D, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.param_dtype
    keys = iter(jax.random.split(key, 16 + cfg.num_hidden_layers * (
        16 + 3 * cfg.n_resident_experts)))

    def w(*shape, dtype=dt, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def swiglu(width):
        return {"wg": w(D, width), "wu": w(D, width), "wd": w(width, D)}

    def layer(l):
        p = {
            "attn_norm": jnp.ones((D,), jnp.float32),
            "wdq": w(D, cfg.q_lora_rank),
            "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
            "wuq": w(cfg.q_lora_rank,
                     H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
            "wdkv": w(D, cfg.cache_width),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
            "wuk": w(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim),
            "wuv": w(cfg.kv_lora_rank, H, cfg.v_head_dim),
            "wo": w(H * cfg.v_head_dim, D),
            "ffn_norm": jnp.ones((D,), jnp.float32),
        }
        if is_sparse(cfg, l):
            p["router"] = w(D, cfg.n_routed_experts, dtype=jnp.float32)
            p["router_bias"] = w(cfg.n_routed_experts, dtype=jnp.float32, std=0.001)
            p["shared"] = swiglu(cfg.moe_intermediate_size)
            # one buffer an expert: a step hands a whole buffer to the
            # matmul that reads it, never a slice of a stacked array
            p["experts"] = [swiglu(cfg.moe_intermediate_size)
                            for _ in range(cfg.n_resident_experts)]
        else:
            p["dense"] = swiglu(cfg.intermediate_size)
        return p

    return {"embed": w(cfg.vocab_size, D),
            "layers": [layer(l) for l in range(cfg.num_hidden_layers)],
            "final_norm": jnp.ones((D,), jnp.float32),
            "head": w(D, cfg.vocab_size)}


# ----------------------------------------------------------------- the layer


def _swiglu(p, u):
    """``(silu(u Wg) * (u Wu)) Wd``; u in the weights' dtype, float32 out."""
    a = jax.nn.silu(_mm(u, p["wg"])) * _mm(u, p["wu"])
    return _mm(a.astype(u.dtype), p["wd"])


def _scores(cfg, p, u):
    """The router's scores of u [N, D] in float32 -> [N, n_routed]:
    ``sigmoid(u Wr)`` a logit, or ``softmax(u Wr)`` over the router's width,
    by ``cfg.scoring_func``."""
    logits = jnp.dot(u.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.scoring_func == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


def route(cfg, p, u):
    """u [N, D] -> (chosen experts [N, k] int32, weights [N, k] float32).

    Scores are float32 (``_scores``); the choice is by score + the correction
    bias where the layer has one (``router_bias``), the weights are the
    UNBIASED scores of the chosen, normalised over all of them (resident or
    not), and scaled. ``n_group = topk_group = 1``: the group step of
    ``noaux_tc`` is the identity. A config of another family (``keye_vl``:
    softmax scores, no bias, scale 1) answers the same attributes."""
    sc = _scores(cfg, p, u)
    biased = sc + p["router_bias"] if "router_bias" in p else sc
    _, idx = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(sc, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def expert_tile(cfg, tokens: int) -> int:
    """Rows of one trip through an expert's weights, for a call of
    ``tokens`` rows: a quarter of them (an expert sees ``tokens * k /
    n_routed`` on average, so one trip holds all but the most uneven
    routing), at least a bf16 sublane tile of 16 and at most ``moe_tile``.
    A decode step's trips are short: streaming the weights bounds them."""
    return min(cfg.moe_tile, max(16, tokens // 4))


def resident_experts(cfg, p, u, idx, w, live):
    """The resident experts' part of ``sum_e w_e E_e(u)``: u [N, D] (weights'
    dtype), idx / w [N, k], live [N] bool (a dead slot or a padded position
    routes nowhere). Returns (out [N, D] float32, stats int32 [4] in
    ``MOE_STATS`` order). Every live assignment to a resident expert is
    computed: nothing is dropped, whatever the imbalance.

    ``p["experts"]`` is ONE tree whose leaves stack the experts on a leading
    axis (``keye_vl``: one loop over all the trips, the expert's index data;
    a loop an expert was 25 s a layer to compile at 128 experts, most of them
    loops that never run), or a list, one tree of buffers an expert and a
    loop each (``kimi_k2``, whose accepted check and reference index the
    list, and whose 6.3 GB of experts cannot be stacked beside it on the
    chip: PERF.md, PR 35). The rows' arithmetic (``rows``) is one."""
    N, k = idx.shape
    E, A = cfg.n_resident_experts, N * k
    tile = expert_tile(cfg, N)
    local = idx - cfg.expert_first
    mine = (local >= 0) & (local < E) & live[:, None]
    flat = jnp.where(mine, local, E).reshape(A)       # E: not computed here
    order = jnp.argsort(flat, stable=True)
    tok = (order // k).astype(jnp.int32)              # token of a sorted row
    w_sorted = w.reshape(A)[order]
    counts = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    token_ids = jnp.arange(N, dtype=jnp.int32)[:, None]

    def rows(expert, e, t, out):
        """Trip ``t`` through expert ``e``'s sorted rows, added to ``out``."""
        pos = starts[e] + t * tile + jnp.arange(tile, dtype=jnp.int32)
        valid = pos < starts[e] + counts[e]
        pos = jnp.minimum(pos, A - 1)
        who = tok[pos]
        y = _swiglu(expert, u[who]) * jnp.where(valid, w_sorted[pos], 0.0)[:, None]
        # back to token order as a matmul: rows of one expert are
        # distinct tokens, so this is a permutation, not a sum
        back = ((who[None, :] == token_ids) & valid[None, :]).astype(u.dtype)
        return out + _mm(back, y.astype(u.dtype))

    out = jnp.zeros((N, u.shape[-1]), jnp.float32)
    trips = -(-counts // tile)
    if isinstance(p["experts"], dict):
        stacked, done = p["experts"], jnp.cumsum(trips)
        # every trip's expert and its place among that expert's trips, worked
        # out for all trips at once: inside the loop a search and a handful
        # of scalar reads a trip cost as much as the trip's matmuls
        # (PERF.md, PR 35). No more trips than full tiles + one an expert.
        i = jnp.arange(A // tile + E, dtype=jnp.int32)
        of = jnp.minimum(jnp.sum(i[:, None] >= done[None, :], axis=1), E - 1).astype(jnp.int32)
        plan = jnp.stack([of, i - (done - trips)[of]], axis=1)

        def trip(i, out):
            # the expert's weights are read where they lie: the index goes
            # into the matmul, no slice is made
            e, t = plan[i, 0], plan[i, 1]
            expert = {name: x[e] for name, x in stacked.items()}
            return rows(expert, e, t, out)

        out = jax.lax.fori_loop(0, done[-1], trip, out)
    else:
        for e, expert in enumerate(p["experts"]):
            # zero trips for an expert nobody chose: its weights are not read
            out = jax.lax.fori_loop(
                0, trips[e], lambda t, out, e=e, expert=expert: rows(expert, e, t, out), out)

    stats = jnp.stack([jnp.sum(live), jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.max(counts)]).astype(jnp.int32)
    return out, stats


def _ffn(cfg: KimiK2Config, p, u32, live):
    """F of one block on normalised float32 rows [N, D]: (out, stats|None)."""
    u = u32.astype(cfg.param_dtype)
    if "dense" in p:
        return _swiglu(p["dense"], u), None
    idx, w = route(cfg, p, u32)
    routed, stats = resident_experts(cfg, p, u, idx, w, live)
    return _swiglu(p["shared"], u) + routed, stats


def _latent_rows(cfg: KimiK2Config, p, u32, positions):
    """Normalised rows [..., D] at ``positions`` [...] -> (q_nope
    [..., H, nope], q_rope [..., H, rope] rotated, both in the weights'
    dtype; ckr [..., 576]: what the cache stores, ``[rms(c) | R(kr)]``)."""
    dt, H = cfg.param_dtype, cfg.num_attention_heads
    u = u32.astype(dt)
    cq = _rms(_mm(u, p["wdq"]), p["q_norm"], cfg.rms_norm_eps).astype(dt)
    q = _mm(cq, p["wuq"]).reshape(*u.shape[:-1], H, -1)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    q_rope = _rotate(q_rope, positions[..., None], cfg)
    ckv = _mm(u, p["wdkv"])
    c, kr = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    ckr = jnp.concatenate([_rms(c, p["kv_norm"], cfg.rms_norm_eps),
                           _rotate(kr, positions, cfg)], axis=-1)
    return q_nope.astype(dt), q_rope.astype(dt), ckr.astype(dt)


def _expanded_attention(cfg: KimiK2Config, p, q_nope, q_rope, ckr):
    """Causal attention of whole sequences [B, T, ...] in the expanded form:
    keys and values are rebuilt from the cached rows."""
    dt = cfg.param_dtype
    B, T = ckr.shape[:2]
    c, kr = jnp.split(ckr, [cfg.kv_lora_rank], axis=-1)
    k_nope = jnp.einsum("btc,chn->bhtn", c, p["wuk"],
                        preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("btc,chv->bhtv", c, p["wuv"],
                   preferred_element_type=jnp.float32).astype(dt)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, None], (B, k_nope.shape[1], T, kr.shape[-1]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    if cfg.attn_impl == "flash" or (cfg.attn_impl == "auto" and T >= 128
                                    and jax.default_backend() == "tpu"):
        # the flash kernel wants one width for q, k and v: zero lanes add
        # nothing. Blocks of 128 x 128 leave it bound by its grid's overhead
        # at 192-wide heads (PERF.md, PR 31): ask for larger ones
        pad = q.shape[-1] - v.shape[-1]
        block = min(_PREFILL_BLOCK, T)
        o = flash_attention(
            q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad))), causal=True,
            scale=cfg.softmax_scale, block_q=block, block_k=block)[..., :v.shape[-1]]
    else:
        o = mha_reference(q, k, v, causal=True, scale=cfg.softmax_scale)
    return _mm(o.transpose(0, 2, 1, 3).reshape(B, T, -1).astype(dt), p["wo"])


def _prefill_layer(cfg: KimiK2Config, p, h, positions, live):
    """One block over whole sequences: h [B, T, D] float32 ->
    (h, ckr [B, T, 576])."""
    q_nope, q_rope, ckr = _latent_rows(
        cfg, p, _rms(h, p["attn_norm"], cfg.rms_norm_eps), positions)
    h = h + _expanded_attention(cfg, p, q_nope, q_rope, ckr)
    u = _rms(h, p["ffn_norm"], cfg.rms_norm_eps)
    f, _ = _ffn(cfg, p, u.reshape(-1, u.shape[-1]), live.reshape(-1))
    return h + f.reshape(h.shape), ckr


def _head(cfg: KimiK2Config, params, h):
    u = _rms(h, params["final_norm"], cfg.rms_norm_eps).astype(cfg.param_dtype)
    return _mm(u, params["head"])


def prefill_forward(params, tokens, cfg: KimiK2Config, *, lengths=None):
    """tokens [B, T] -> (hidden [B, T, D] float32, ckr [L, B, T, 576]).
    Positions at or past ``lengths`` [B] (padding of a bucket) route to no
    expert; causal attention keeps them from the positions before."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    live = (positions < lengths[:, None]) if lengths is not None else (positions >= 0)
    h = params["embed"][tokens].astype(jnp.float32)
    rows = []
    for p in params["layers"]:
        h, ckr = _prefill_layer(cfg, p, h, positions, live)
        rows.append(ckr)
    return h, jnp.stack(rows)


def forward(params, tokens, cfg: KimiK2Config):
    """Full causal forward: tokens [B, T] -> logits [B, T, V] float32."""
    h, _ = prefill_forward(params, tokens, cfg)
    return _head(cfg, params, h)


# ----------------------------------------------------- the slot pool's family


class LatentDecodeFamily:
    """What ``PagedDecodeSlotPool`` asks of a model family (the protocol is
    in ``paged_decode``'s docstring), for latent attention: ONE
    arena ``[L, n_blocks, block_T, 640]`` (576 values and 64 zero lanes a
    token), a decode step in the absorbed form, and the step's routing
    counters."""

    speculative = False           # a verify window is not built for this family
    shares_prefix = True          # every block lives as long as its request
    stat_names = MOE_STATS
    name = "kimi_k2"

    def __init__(self, cfg: KimiK2Config):
        self.cfg = cfg
        self.n_layers = cfg.num_hidden_layers
        self.cache_widths = (cfg.arena_width,)
        self.cache_dtype = cfg.param_dtype
        self.n_sparse_layers = sum(is_sparse(cfg, l) for l in range(self.n_layers))
        self.n_resident_experts = cfg.n_resident_experts

    def resident(self, params):
        """This family's weights are served in the dtype they come in
        (``param_dtype``, no masters): the resident tree IS the caller's."""
        return params

    def prefill(self, params, tokens, length):
        """tokens [1, Tb], length scalar -> (last live hidden [D], rows: one
        [L, Tb, width] an arena)."""
        h, rows = prefill_forward(params, tokens, self.cfg,
                                  lengths=jnp.reshape(length, (1,)))
        return h[0, length - 1], (self._padded(rows[:, 0]),)

    def _padded(self, x):
        """Zero lanes behind the last axis, up to the arena's width."""
        pad = self.cfg.arena_width - x.shape[-1]
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def head(self, params, h):
        return _head(self.cfg, params, h)

    def cumulative_stats(self, sums: Dict[str, int], steps: int) -> Dict[str, int]:
        """``block_stats()``'s expert counters from the running sums of
        ``MOE_STATS`` over ``steps`` decode steps (prefill is not counted)."""
        return {
            # live tokens x sparse layers, and the token-expert pairs of
            # them that landed on an expert held here
            "moe_routed_tokens": sums["routed_tokens"],
            "moe_resident_assignments": sums["resident_assignments"],
            # resident experts some live token chose, against resident
            # experts x sparse layers x steps: the share of the resident
            # expert weights that a step reads
            "moe_experts_touched": sums["experts_touched"],
            "moe_experts_resident": (self.n_resident_experts
                                     * self.n_sparse_layers * steps),
            # the busiest resident expert's tokens, summed over layers and
            # steps, beside all resident experts' tokens (max over mean)
            "moe_load_max": sums["load_max"],
            "moe_load_sum": sums["resident_assignments"],
        }

    def decode_window(self, params, tokens, positions, arenas, tables):
        """One decode step of every slot: tokens / positions [S, 1]. Returns
        (logits [S, 1, V], arenas, stats int32 [4])."""
        cfg = self.cfg
        if tokens.shape[1] != 1:
            raise ValueError("the kimi_k2 family decodes one token a step")
        (arena,) = arenas
        dt = cfg.param_dtype
        live = tables[:, 0] > 0
        limits = jnp.where(live[:, None], positions + 1, 0)
        h = params["embed"][tokens].astype(jnp.float32)            # [S, 1, D]
        stats = jnp.zeros((len(MOE_STATS),), jnp.int32)
        for l, p in enumerate(params["layers"]):
            q_nope, q_rope, ckr = _latent_rows(
                cfg, p, _rms(h, p["attn_norm"], cfg.rms_norm_eps), positions)
            arena = _write_window(arena, l, tables, limits, self._padded(ckr))
            q_lat = jnp.einsum("swhn,chn->swhc", q_nope, p["wuk"],
                               preferred_element_type=jnp.float32).astype(dt)
            o_lat = paged_mla_decode_attention(
                self._padded(jnp.concatenate([q_lat, q_rope], axis=-1)[:, 0]),
                arena, tables,
                limits[:, 0], layer=l, scale=cfg.softmax_scale,
                latent_width=cfg.kv_lora_rank)                      # [S, H, 512]
            o = jnp.einsum("shc,chv->shv", o_lat, p["wuv"],
                           preferred_element_type=jnp.float32).astype(dt)
            h = h + _mm(o.reshape(o.shape[0], 1, -1), p["wo"])
            u = _rms(h, p["ffn_norm"], cfg.rms_norm_eps)
            f, s = _ffn(cfg, p, u[:, 0], live)
            h = h + f[:, None]
            if s is not None:
                stats = stats + s
        return _head(cfg, params, h), (arena,), stats
