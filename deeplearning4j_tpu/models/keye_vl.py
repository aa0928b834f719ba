"""The ``keye_vl`` layer family (Keye-VL-2.0's language model), as ONE stage of
a pipeline serves it: grouped-query attention over a LEARNED top-k selection
of the cached rows, multimodal rotary positions, and softmax-routed experts
that are all resident.

A third family beside ``transformer.py`` and ``kimi_k2.py``: RMSNorm, no bias,
SwiGLU experts in every layer (no dense layer, no shared expert). Weights are
held in ``param_dtype`` (bfloat16 as served, no float32 masters); the residual
stream, every norm, the indexer's scores, the router and both softmaxes are
float32. The vision tower is not here: the traffic is token ids, and a token
carries three position channels that text sets equal.

- **Grouped-query heads.** ``num_attention_heads`` query heads share
  ``num_key_value_heads`` K/V heads (head ``h`` reads K/V head ``h // group``);
  q and k are RMS-normalised over each head's lanes before the rotation. The
  cache stores K and V at ``num_key_value_heads * head_dim`` lanes a token.
- **Multimodal rotary positions** (``mrope_section``): a head's ``head_dim / 2``
  rotary pairs (lane ``i`` with ``i + head_dim / 2``, half-split) take their
  angle from the time, height or width channel of the token's position, by
  section. Equal channels are plain rotary.
- **Learned sparse attention** (the lightning indexer of DeepSeek-V3.2, which
  the model's description names): every token stores ONE index key beside K
  and V (a third arena of the pool, ``index_head_dim`` values in 128 lanes);
  a query scores every cached row ``s <= t`` with ``I[t, s] = sum_j w[t, j] *
  relu(qI[t, j] . kI[s])`` in float32, keeps the ``index_topk`` largest (all
  rows while there are no more than that; ties to the lower ``s``) and
  attends to those rows only. Prefill selects for every query position in
  chunks of ``index_q_chunk`` queries and attends under the selection's mask;
  a decode step reads the index keys of a slot's live rows through its block
  table, takes the exact top-k and gathers K/V of the selected rows only.
  Nothing on the served path turns the selection off.
- **Experts.** The expert layer is ``kimi_k2``'s (``route``,
  ``resident_experts``), configured for softmax scores and top-k weights
  normalised over the chosen: sorted rows, ONE loop whose trip count is the
  tiles of rows the experts really got (the experts are stacked on a leading
  axis and a trip indexes its own), nothing dropped. All ``num_experts`` are resident
  in the served cut; ``expert_first`` / ``n_resident_experts`` name a share.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.sparse_attention import (decode_select_counted, kth_largest,
                                        selected_attention)
from .kimi_k2 import MOE_STATS, _mm, _rms, resident_experts, route
from .paged_decode import _write_window

_NEG = -jnp.inf

#: what one decode step counts, in this order: its routing (the expert
#: layer's counters) and, summed over layers and live slots, the cached rows
#: its indexer scored, the K/V rows its attention then read, the slots whose
#: selection a threshold decided (more live rows than ``index_topk``) and, of
#: those, the ones with more rows tied at the threshold than room for them
STEP_STATS = MOE_STATS + ("live_rows", "selected_rows", "thresholded", "tie_breaks")


@dataclasses.dataclass
class KeyeVLConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128           # the router's width: the published count
    expert_first: int = 0            # this chip holds experts
    n_resident_experts: int = 128    # [expert_first, expert_first + n_resident)
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_q_chunk: int = 512         # queries of one selection in prefill
    max_position_embeddings: int = 262144
    param_dtype: Any = jnp.bfloat16
    moe_tile: int = 256              # rows of one expert matmul
    moe_chunk: int = 2048            # tokens of one pass through the experts

    # what the slot pool asks of any config
    causal = True
    # the expert layer's configuration (``kimi_k2.route``): softmax over the
    # router's width, no correction bias, no scaling; the chosen weights are
    # divided by their sum, as the published ``norm_topk_prob`` says
    scoring_func = "softmax"
    routed_scaling_factor = 1.0

    def __post_init__(self):
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(f"mrope_section {self.mrope_section} must cover "
                             f"the {self.head_dim // 2} rotary pairs of a head")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide evenly over K/V heads")

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

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
    def kv_width(self) -> int:
        """Values a token stores a layer for K (and again for V)."""
        return self.num_key_value_heads * self.head_dim

    @property
    def index_rope_dim(self) -> int:
        """Leading lanes of an index query / key that are rotated."""
        return self.index_head_dim // 2

    @property
    def index_arena_width(self) -> int:
        """Lanes of a cached index key: up to whole 128-lane tiles, the rest
        zeros (the TPU moves whole tiles: ``kimi_k2.arena_width``)."""
        return -(-self.index_head_dim // 128) * 128

    def decode_family(self):
        return SparseGQADecodeFamily(self)


# ---------------------------------------------------------------------- init


def init_params(key, cfg: KeyeVLConfig) -> Dict[str, Any]:
    """Random weights (normal, std 0.02) in ``param_dtype``; norm gains 1 and
    the index key's LayerNorm bias 0, the router float32."""
    D, dt = cfg.hidden_size, cfg.param_dtype
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    HI, dI = cfg.index_n_heads, cfg.index_head_dim
    E = cfg.n_resident_experts
    keys = iter(jax.random.split(key, 4 + cfg.num_hidden_layers * 12))

    def w(*shape, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dtype)

    def layer():
        W = cfg.moe_intermediate_size
        return {
            "attn_norm": jnp.ones((D,), jnp.float32),
            "wq": w(D, H * hd), "wk": w(D, KV * hd), "wv": w(D, KV * hd),
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k_norm": jnp.ones((hd,), jnp.float32),
            "wo": w(H * hd, D),
            "wqi": w(D, HI * dI), "wki": w(D, dI), "wwi": w(D, HI),
            "ki_norm_g": jnp.ones((dI,), jnp.float32),
            "ki_norm_b": jnp.zeros((dI,), jnp.float32),
            "ffn_norm": jnp.ones((D,), jnp.float32),
            "router": w(D, cfg.num_experts, dtype=jnp.float32),
            # the experts stacked: ONE loop runs the trips of all of them
            # (``kimi_k2.resident_experts``), and a trip's matmuls read the
            # expert they index where it lies
            "experts": {"wg": w(E, D, W), "wu": w(E, D, W), "wd": w(E, W, D)},
        }

    return {"embed": w(cfg.vocab_size, D),
            "layers": [layer() for _ in range(cfg.num_hidden_layers)],
            "final_norm": jnp.ones((D,), jnp.float32),
            "head": w(D, cfg.vocab_size)}


# ------------------------------------------------------------------ positions


def text_positions(positions):
    """One position a token -> its three channels, equal: what text has."""
    return jnp.broadcast_to(positions[..., None], (*positions.shape, 3))


def _half_split(x, ang):
    """Rotate the pairs (lane i, lane i + n/2) of float32 ``x`` by ``ang``."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mrope(x, pos3, cfg: KeyeVLConfig):
    """Multimodal rotary on the ``head_dim`` lanes of float32 ``x``
    [..., heads, head_dim]; ``pos3`` [..., 3] (time, height, width). Pair
    ``i`` turns by ``pos3[channel(i)] * theta^(-2i / head_dim)``, the channel
    by ``mrope_section``."""
    half = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    channel = np.repeat(np.arange(3), cfg.mrope_section)
    ang = pos3.astype(jnp.float32)[..., channel] * inv           # [..., half]
    return _half_split(x, ang[..., None, :])


def index_rope(x, positions, cfg: KeyeVLConfig):
    """Plain rotary by the time channel on the leading ``index_rope_dim``
    lanes of float32 ``x`` [..., index_head_dim]; ``positions`` has x's
    leading shape, less any head axis given as a 1."""
    r = cfg.index_rope_dim
    inv = cfg.rope_theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([_half_split(x[..., :r], ang), x[..., r:]], axis=-1)


# -------------------------------------------------------------- the selection


def index_scores(qi, ki, wi):
    """``I[.., t, s] = sum_j wi[.., t, j] * relu(qi[.., t, j] . ki[.., s])`` in
    float32: qi [.., Tq, HI, dI] and ki [.., Tk, dI] in the cache's dtype,
    wi [.., Tq, HI] float32 -> [.., Tq, Tk]."""
    dots = jnp.einsum("...qjd,...kd->...qjk", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * wi[..., None], axis=-2)


def selected(scores, k: int):
    """The selection as a mask: True at the ``k`` largest ``scores`` of every
    row of the last axis, all finite ones where there are no more than ``k``,
    ties to the lower index. A score of ``-inf`` (a row the query may not
    see) is never selected."""
    n = scores.shape[-1]
    valid = scores > _NEG
    if k >= n:
        return valid
    kth = kth_largest(scores, k)
    above = scores > kth
    tied = scores == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    # the running count that breaks ties by the row is a pass of its own over
    # the scores: made only where some row has more ties than room for them
    tied = jax.lax.cond(
        jnp.any(jnp.sum(tied, axis=-1, keepdims=True) > room),
        lambda: tied & (jnp.cumsum(tied, axis=-1) <= room), lambda: tied)
    return valid & (above | tied)


def select_chunk(cfg: KeyeVLConfig, qi_c, ki, wi_c, first):
    """The selection of a chunk of queries at positions ``first ..``: qi_c
    [B, C, HI, dI], ki [B, T, dI], wi_c [B, C, HI] -> bool [B, C, T], a row
    True at the ``index_topk`` best ``s <= t``."""
    C, T = qi_c.shape[1], ki.shape[1]
    with jax.named_scope("dsa.index"):
        scores = index_scores(qi_c, ki, wi_c)
        seen = jnp.arange(T)[None, :] <= (first + jnp.arange(C))[:, None]
        scores = jnp.where(seen, scores, _NEG)
    with jax.named_scope("dsa.select"):
        return selected(scores, cfg.index_topk)


def attend_chunk(cfg: KeyeVLConfig, q_c, k, v, mask_c, first):
    """Grouped-query attention of a chunk of queries (the first at position
    ``first``) under its selection: q_c [B, C, H * hd], k / v [B, T, KV *
    hd] in the weights' dtype, mask_c bool [B, C, T] -> [B, C, H * hd]."""
    with jax.named_scope("dsa.attend"):
        return jnp.stack([selected_attention(
            q_c[b], k[b], v[b], mask_c[b], first, kv_heads=cfg.num_key_value_heads,
            scale=cfg.head_dim ** -0.5) for b in range(q_c.shape[0])])


def _by_chunks(cfg: KeyeVLConfig, fn, *xs):
    """``fn(first, *chunks)`` over ``index_q_chunk`` positions at a time of
    arrays [B, T, ...] (``first`` the chunk's first position), the results
    [B, C, ...] put back together as [B, T, ...]. T is padded up with zeros
    for the last chunk, and the padding cut."""
    B, T = xs[0].shape[:2]
    C = min(cfg.index_q_chunk, T)
    n = -(-T // C)

    def chunks(x):
        x = jnp.pad(x, [(0, 0), (0, n * C - T)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(B, n, C, *x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: fn(a[0] * C, *a[1:]),
                      (jnp.arange(n), *map(chunks, xs)))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * C, *out.shape[3:])[:, :T]


def selection_mask(cfg: KeyeVLConfig, qi, ki, wi):
    """Every query position's selection over whole sequences, bool
    [B, T, T]."""
    return _by_chunks(cfg, lambda first, qi_c, wi_c: select_chunk(
        cfg, qi_c, ki, wi_c, first), qi, wi)


def masked_attention(cfg: KeyeVLConfig, q, k, v, mask):
    """Attention of whole sequences under a GIVEN selection [B, T, T] (every
    row selects something, nothing past itself): q [B, T, H * hd], k / v
    [B, T, KV * hd] -> [B, T, H * hd]."""
    return _by_chunks(cfg, lambda first, q_c, mask_c: attend_chunk(
        cfg, q_c, k, v, mask_c, first), q, mask)


def sparse_attention(cfg: KeyeVLConfig, q, k, v, qi, ki, wi):
    """What prefill runs: a chunk of queries' selection and then their
    attention under it, so that no [T, T] array is ever whole.
    ``masked_attention(.., selection_mask(..))`` chunk by chunk."""
    return _by_chunks(cfg, lambda first, q_c, qi_c, wi_c: attend_chunk(
        cfg, q_c, k, v, select_chunk(cfg, qi_c, ki, wi_c, first), first), q, qi, wi)


# ----------------------------------------------------------------- the layer


def attention_rows(cfg: KeyeVLConfig, p, x32, pos3):
    """Normalised float32 rows [..., D] at positions ``pos3`` [..., 3] ->
    what attention and the cache need of them, in the weights' dtype but for
    ``wi``: q [..., H, hd] and k [..., KV, hd] (normalised a head, rotated),
    v [..., KV, hd], the indexer's queries qi [..., HI, dI], the ONE index
    key ki [..., dI] and the head weights wi [..., HI] float32."""
    dt, eps = cfg.param_dtype, cfg.rms_norm_eps
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    HI, dI = cfg.index_n_heads, cfg.index_head_dim
    x = x32.astype(dt)
    lead = x.shape[:-1]
    q = mrope(_rms(_mm(x, p["wq"]).reshape(*lead, H, hd), p["q_norm"], eps), pos3, cfg)
    k = mrope(_rms(_mm(x, p["wk"]).reshape(*lead, KV, hd), p["k_norm"], eps), pos3, cfg)
    v = _mm(x, p["wv"]).reshape(*lead, KV, hd)
    t = pos3[..., 0]
    qi = index_rope(_mm(x, p["wqi"]).reshape(*lead, HI, dI), t[..., None], cfg)
    ki = _mm(x, p["wki"])
    mean = jnp.mean(ki, -1, keepdims=True)
    ki = (ki - mean) * jax.lax.rsqrt(jnp.mean(jnp.square(ki - mean), -1, keepdims=True)
                                     + eps) * p["ki_norm_g"] + p["ki_norm_b"]
    ki = index_rope(ki, t, cfg)
    wi = _mm(x, p["wwi"]) * (HI ** -0.5 * dI ** -0.5)
    return (q.astype(dt), k.astype(dt), v.astype(dt), qi.astype(dt),
            ki.astype(dt), wi)


def ffn(cfg: KeyeVLConfig, p, u32, live, routing=None):
    """The expert layer on normalised float32 rows [N, D], ``moe_chunk`` rows
    a pass (the way back to token order costs rows x tile a trip): (out
    [N, D] float32, stats int32 [4] in ``MOE_STATS`` order). ``routing``
    (experts [N, k], weights [N, k]) replaces the layer's own: a check's way
    to hold the experts apart from the router."""
    N, D = u32.shape

    def one(args):
        u_c, live_c, given = args
        idx, w = route(cfg, p, u_c) if given is None else given
        return resident_experts(cfg, p, u_c.astype(cfg.param_dtype), idx, w, live_c)

    if N <= cfg.moe_chunk:
        return one((u32, live, routing))
    pad = -N % cfg.moe_chunk

    def passes(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
            -1, cfg.moe_chunk, *x.shape[1:])

    out, stats = jax.lax.map(one, (passes(u32), passes(live),
                                   None if routing is None else tuple(map(passes, routing))))
    # counts add over passes; the busiest expert's load is its largest pass
    stats = jnp.concatenate([jnp.sum(stats[:, :3], axis=0), jnp.max(stats[:, 3:], axis=0)])
    return out.reshape(-1, D)[:N], stats


def _prefill_layer(cfg: KeyeVLConfig, p, h, pos3, live):
    """One block over whole sequences: h [B, T, D] float32 -> (h, the rows
    the cache stores: k [B, T, KV*hd], v, ki [B, T, dI])."""
    B, T, _ = h.shape
    q, k, v, qi, ki, wi = attention_rows(
        cfg, p, _rms(h, p["attn_norm"], cfg.rms_norm_eps), pos3)
    q, k, v = (x.reshape(B, T, -1) for x in (q, k, v))   # heads side by side
    o = sparse_attention(cfg, q, k, v, qi, ki, wi)
    h = h + _mm(o, p["wo"])
    u = _rms(h, p["ffn_norm"], cfg.rms_norm_eps)
    f, _ = ffn(cfg, p, u.reshape(B * T, -1), live.reshape(-1))
    return h + f.reshape(h.shape), (k, v, ki)


def _head(cfg: KeyeVLConfig, params, h):
    u = _rms(h, params["final_norm"], cfg.rms_norm_eps).astype(cfg.param_dtype)
    return _mm(u, params["head"])


def prefill_forward(params, tokens, cfg: KeyeVLConfig, *, lengths=None,
                    positions=None):
    """tokens [B, T] -> (hidden [B, T, D] float32, the cache's rows: k, v
    [L, B, T, KV*hd] and ki [L, B, T, dI]). ``positions`` [B, T, 3] (default:
    text, 0..T-1 in all three channels). Positions at or past ``lengths`` [B]
    (padding of a bucket) route to no expert; causal selection keeps them
    from the positions before."""
    B, T = tokens.shape
    order = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    pos3 = text_positions(order) if positions is None else positions
    live = (order < lengths[:, None]) if lengths is not None else (order >= 0)
    h = params["embed"][tokens].astype(jnp.float32)
    rows = []
    for p in params["layers"]:
        h, stored = _prefill_layer(cfg, p, h, pos3, live)
        rows.append(stored)
    return h, tuple(jnp.stack(r) for r in zip(*rows))


def forward(params, tokens, cfg: KeyeVLConfig, *, positions=None):
    """Full causal forward: tokens [B, T] -> logits [B, T, V] float32."""
    h, _ = prefill_forward(params, tokens, cfg, positions=positions)
    return _head(cfg, params, h)


# ----------------------------------------------------- the slot pool's family


def _cell_of_row(tables, block_T: int):
    """Where every position of every slot lies in ONE layer of an arena,
    counted in rows: [S, max_blocks] tables -> int32 [S, max_len]."""
    within = jnp.arange(tables.shape[1] * block_T, dtype=jnp.int32) % block_T
    return jnp.repeat(tables, block_T, axis=1) * block_T + within


def _layer_rows(arena, layer: int, cells):
    """Rows ``cells`` (counted within a layer, any shape) of ``arena[layer]``
    -> [*cells.shape, width]. The arena is indexed as a flat list of rows:
    XLA's gather is twice as fast so as through three indices (0.375 against
    0.67 ms for 32,768 rows of 1 KB on a v5e: PERF.md, PR 35)."""
    per_layer = arena.shape[1] * arena.shape[2]
    return arena.reshape(-1, arena.shape[-1])[layer * per_layer + cells]


class SparseGQADecodeFamily:
    """What ``PagedDecodeSlotPool`` asks of a model family (the protocol is
    in ``paged_decode``'s docstring), for grouped-query attention over a
    learned selection: THREE arenas ``[L, n_blocks, block_T, width]`` — K and
    V of ``KV * hd`` lanes and the index key (64 values in 128 lanes) — a
    decode step that scores a slot's live rows, takes the exact top-k and
    gathers the selected K/V rows, and the step's routing and row counters.
    The pool hands text positions; the three channels are made equal here."""

    speculative = False           # a verify window is not built for this family
    shares_prefix = True          # every block lives as long as its request
    stat_names = STEP_STATS
    name = "keye_vl"

    def __init__(self, cfg: KeyeVLConfig):
        self.cfg = cfg
        self.n_layers = cfg.num_hidden_layers
        self.cache_widths = (cfg.kv_width, cfg.kv_width, cfg.index_arena_width)
        self.cache_dtype = cfg.param_dtype
        self.n_sparse_layers = cfg.num_hidden_layers
        self.n_resident_experts = cfg.n_resident_experts
        # while ``decode_window`` traces: every ``_attend``'s tie breaks
        self._tie_breaks = None

    def resident(self, params):
        """Served in the dtype the weights come in (``param_dtype``, no
        masters): the resident tree IS the caller's."""
        return params

    def prefill(self, params, tokens, length):
        """tokens [1, Tb], length scalar -> (last live hidden [D], rows: one
        [L, Tb, width] an arena)."""
        h, (k, v, ki) = prefill_forward(params, tokens, self.cfg,
                                        lengths=jnp.reshape(length, (1,)))
        return h[0, length - 1], (k[:, 0], v[:, 0], self._padded(ki[:, 0]))

    def _padded(self, x):
        """Zero lanes behind the last axis, up to the index arena's width."""
        pad = self.cfg.index_arena_width - x.shape[-1]
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def head(self, params, h):
        return _head(self.cfg, params, h)

    def cumulative_stats(self, sums: Dict[str, int], steps: int) -> Dict[str, int]:
        """``block_stats()``'s counters from the running sums of
        ``STEP_STATS`` over ``steps`` decode steps (prefill is not counted):
        the expert layer's, with ``kimi_k2``'s names and meaning, and the two
        row counts of the selection."""
        return {
            "moe_routed_tokens": sums["routed_tokens"],
            "moe_resident_assignments": sums["resident_assignments"],
            "moe_experts_touched": sums["experts_touched"],
            "moe_experts_resident": (self.n_resident_experts
                                     * self.n_sparse_layers * steps),
            "moe_load_max": sums["load_max"],
            "moe_load_sum": sums["resident_assignments"],
            # cached rows of live slots that the indexer scored (every one,
            # in every layer), and the K/V rows of them that attention then
            # read: the selection (a dead slot reads none)
            "dsa_live_rows": sums["live_rows"],
            "dsa_selected_rows": sums["selected_rows"],
            # live slot-layers whose selection the threshold decided, and of
            # those the ones where rows tied at it outnumbered the room left
            "dsa_thresholded": sums["thresholded"],
            "dsa_tie_breaks": sums["tie_breaks"],
        }

    def _attend(self, q, qi, wi, arenas, layer: int, tables, limits, cell_of_row,
                live_first):
        """One token a slot against its cache: q [S, H, hd], qi [S, HI, dI],
        wi [S, HI], limits [S] (live rows, the token's own among them; 0 for
        a dead slot); what every layer of the step shares: ``cell_of_row``
        (``_cell_of_row``) and ``live_first``, the slots in an order that
        puts the live ones first -> (o [S, H * hd], and the selection the
        step made: ``chosen`` bool [S, topk], True where the place holds a
        selected row, and ``cells`` int32 [S, topk], where that row lies in
        the arena's layer: the selected rows in ROW order from place 0, read
        from ``cell_of_row``; attention over a set reads no order)."""
        cfg = self.cfg
        k_arena, v_arena, i_arena = arenas
        S, H, hd = q.shape
        KV, dI = cfg.num_key_value_heads, cfg.index_head_dim
        max_len = tables.shape[1] * k_arena.shape[2]
        topk = min(cfg.index_topk, max_len)
        with jax.named_scope("dsa.index"):
            # every mapped block of every slot, in position order
            ki = i_arena[layer, tables].reshape(S, max_len, -1)[..., :dI]
            scores = index_scores(qi[:, None], ki, wi[:, None])[:, 0]   # [S, R]
            scores = jnp.where(jnp.arange(max_len)[None, :] < limits[:, None],
                               scores, _NEG)
        with jax.named_scope("dsa.select"):
            # exact, with no sort: the k-th score by bisection, ties to the
            # lower row, the chosen rows' cells compacted in row order, one
            # live slot a grid step (the stable sort of [16, 17408] it
            # replaced was a third of a step: PERF.md, PR 40)
            chosen, cells, ties = decode_select_counted(scores, cell_of_row, limits, topk)
            if self._tie_breaks is not None:
                self._tie_breaks.append(jnp.sum(ties).astype(jnp.int32))
        with jax.named_scope("dsa.attend"):
            # one live slot a trip: a dead slot gathers nothing (XLA's gather
            # costs by the row, 17 ns for 1 KB on a v5e, and batched over the
            # pool's slots it was half of a step at 3 live of 16: PERF.md,
            # PR 35)
            q = q.reshape(S, KV, H // KV, hd)

            def slot(i, out):
                at = live_first[i]
                picked = jax.lax.dynamic_index_in_dim(chosen, at, keepdims=False)
                mine = jnp.where(
                    picked, jax.lax.dynamic_index_in_dim(cells, at, keepdims=False), 0)
                # gathered ONCE: left to itself XLA gathers again for every
                # K/V head that reads a slice of the rows
                k, v = jax.lax.optimization_barrier((
                    _layer_rows(k_arena, layer, mine),            # [topk, KV*hd]
                    _layer_rows(v_arena, layer, mine)))
                q_at = jax.lax.dynamic_index_in_dim(q, at, keepdims=False)
                heads = []
                for g in range(KV):  # a K/V head's lanes are whole tiles: no relayout
                    lanes = slice(g * hd, (g + 1) * hd)
                    s = jnp.einsum("jd,td->jt", q_at[g], k[:, lanes],
                                   preferred_element_type=jnp.float32) * hd ** -0.5
                    p = jax.nn.softmax(jnp.where(picked, s, _NEG), axis=-1)
                    heads.append(jnp.einsum("jt,td->jd", p.astype(v.dtype), v[:, lanes],
                                            preferred_element_type=jnp.float32))
                o = jnp.stack(heads).reshape(1, H * hd).astype(out.dtype)
                return jax.lax.dynamic_update_slice_in_dim(out, o, at, axis=0)

            o = jax.lax.fori_loop(0, jnp.sum(limits > 0), slot,
                                  jnp.zeros((S, H * hd), k_arena.dtype))
        return o, chosen, cells

    def decode_window(self, params, tokens, positions, arenas, tables):
        """One decode step of every slot: tokens / positions [S, 1]. Returns
        (logits [S, 1, V], arenas, stats int32 [8] in ``STEP_STATS`` order)."""
        cfg = self.cfg
        if tokens.shape[1] != 1:
            raise ValueError("the keye_vl family decodes one token a step")
        k_arena, v_arena, i_arena = arenas
        live = tables[:, 0] > 0
        limits = jnp.where(live[:, None], positions + 1, 0)
        pos3 = text_positions(positions)
        cell_of_row = _cell_of_row(tables, k_arena.shape[2])
        live_first = jnp.argsort(~live, stable=True).astype(jnp.int32)
        h = params["embed"][tokens].astype(jnp.float32)            # [S, 1, D]
        moe = jnp.zeros((len(MOE_STATS),), jnp.int32)
        picked = jnp.zeros((), jnp.int32)
        self._tie_breaks = []
        for l, p in enumerate(params["layers"]):
            q, k, v, qi, ki, wi = attention_rows(
                cfg, p, _rms(h, p["attn_norm"], cfg.rms_norm_eps), pos3)
            S = k.shape[0]
            k_arena = _write_window(k_arena, l, tables, limits, k.reshape(S, 1, -1))
            v_arena = _write_window(v_arena, l, tables, limits, v.reshape(S, 1, -1))
            i_arena = _write_window(i_arena, l, tables, limits, self._padded(ki))
            o, chosen, _ = self._attend(q[:, 0], qi[:, 0], wi[:, 0],
                                        (k_arena, v_arena, i_arena), l, tables,
                                        limits[:, 0], cell_of_row, live_first)
            h = h + _mm(o[:, None], p["wo"])
            u = _rms(h, p["ffn_norm"], cfg.rms_norm_eps)
            f, s = ffn(cfg, p, u[:, 0], live)
            h = h + f[:, None]
            moe = moe + s
            picked = picked + jnp.sum(chosen).astype(jnp.int32)
        # an ``_attend`` that another family ran (a check's control) added none
        ties, self._tie_breaks = sum(self._tie_breaks, jnp.zeros((), jnp.int32)), None
        rows = (jnp.sum(limits) * self.n_layers).astype(jnp.int32)
        topk = min(cfg.index_topk, cell_of_row.shape[1])
        thresholded = (jnp.sum(limits > topk) * self.n_layers).astype(jnp.int32)
        return (_head(cfg, params, h), (k_arena, v_arena, i_arena),
                jnp.concatenate([moe, jnp.stack([rows, picked, thresholded, ties])]))
