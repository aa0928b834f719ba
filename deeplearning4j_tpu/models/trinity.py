"""The ``trinity`` layer family (Arcee's Trinity, ``model_type`` ``afmoe``), as
ONE chip of an expert-parallel serving stage runs it: gated grouped-query
attention, sliding-window layers mixed with full-attention layers, four norms
a block, and ``kimi_k2``'s sigmoid-routed experts with a shared expert.

A fourth family beside ``transformer.py``, ``kimi_k2.py`` and ``keye_vl.py``:
RMSNorm, no bias, SwiGLU. Weights are held in ``param_dtype`` (bfloat16 as
served, no float32 masters); the residual stream, every norm, the router and
the softmax are float32.

- **Embedding.** ``h = E[token] * sqrt(hidden)`` (``mup_enabled``).
- **Attention of layer l**, of kind ``layer_types[l]``: ``x = rms(h; g_in)``;
  ``q = rms_d(x Wq)``, ``k = rms_d(x Wk)`` (RMSNorm over each head's lanes),
  ``v = x Wv``; ``num_attention_heads`` query heads share
  ``num_key_value_heads`` K/V heads (head ``h`` reads K/V head ``h //
  group``). On a ``sliding_attention`` layer q and k are rotated (the whole
  head, half-split pairs, no scaling) and query ``i`` sees key ``j`` iff ``j
  <= i`` and ``i - j < sliding_window``; a ``full_attention`` layer has NO
  positional term and sees every ``j <= i``. The output is gated lane by
  lane, ``a = (P v) * sigmoid(x Wgate)``, and the branch is normalised before
  it joins the residual: ``h <- h + rms(a Wo; g_post_attn)``.
- **Feed-forward.** ``u = rms(h; g_pre_mlp)``; the first ``num_dense_layers``
  layers are a dense SwiGLU, the others ``swiglu_shared(u) + sum_e w_e
  swiglu_e(u)`` with ``kimi_k2.route`` (sigmoid scores, a selection bias,
  weights normalised over the chosen and scaled by ``route_scale``) and
  ``kimi_k2.resident_experts`` over the experts this chip holds
  (``expert_first`` .. ``+ n_resident_experts``, stacked on one axis); ``h <-
  h + rms(F; g_post_mlp)``.
- **The cache.** A token stores K and V of ``num_key_value_heads * head_dim``
  lanes a layer. The full-attention layers' rows live as long as the request;
  a sliding layer never reads a key more than ``sliding_window`` positions
  back, so its blocks are handed back as decoding leaves them behind: two
  cache groups of the paged pool (``paged_decode.CacheGroup``), four arenas.
  Prefill is the grouped / windowed flash forward (``kernels/attention.py``),
  a decode step ``paged_decode_attention`` with ``kv_heads`` and, on a
  sliding layer, ``starts``.

A long prompt's rows go through the projections and the feed-forward
``prefill_chunk`` at a time, so a 32k bucket's activations stay near 2 GB.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.attention import flash_attention, mha_reference
from ..kernels.paged_attention import paged_decode_attention
from .kimi_k2 import MOE_STATS, _mm, _rms, _swiglu, resident_experts, route
from .paged_decode import CacheGroup, _write_window

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_experts: int = 256           # the router's width: the published count
    expert_first: int = 0            # this chip holds experts
    n_resident_experts: int = 256    # [expert_first, expert_first + n_resident)
    num_experts_per_tok: int = 4
    route_scale: float = 2.448
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None   # default: the rule above
    mup_enabled: bool = True         # the embedding is scaled by sqrt(hidden)
    # three facts of the block, named so that a test or a check's control can
    # run the program WITHOUT one of them
    attention_gate: bool = True          # a = (P v) * sigmoid(x Wgate)
    rope_on_full_attention: bool = False  # full layers have no positional term
    sandwich_norm: bool = True           # each branch is normalised on its way out
    max_position_embeddings: int = 262144
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"          # prefill: auto | xla | flash
    moe_tile: int = 256              # rows of one expert matmul
    prefill_chunk: int = 4096        # rows of one pass through a block's matmuls

    # what the slot pool asks of any config
    causal = True
    # what the expert layer (``kimi_k2.route``) asks of any config
    scoring_func = "sigmoid"

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(FULL if (l + 1) % n == 0 else SLIDING
                                     for l in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(f"layer_types {self.layer_types} must name "
                             f"{self.num_hidden_layers} layers, each {SLIDING} or {FULL}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide evenly over K/V heads")

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

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

    def window_of(self, layer: int) -> Optional[int]:
        """Keys a query of ``layer`` sees, its own among them (None: all)."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else None

    def rotates(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING or self.rope_on_full_attention

    def decode_family(self):
        return WindowedGQADecodeFamily(self)


def is_sparse(cfg: TrinityConfig, layer: int) -> bool:
    return layer >= cfg.num_dense_layers


# ---------------------------------------------------------------------- init


def init_params(key, cfg: TrinityConfig) -> Dict[str, Any]:
    """Random weights (normal, std 0.02) in ``param_dtype``; norm gains 1, the
    router and its selection bias float32, the bias small (std 0.001:
    ``kimi_k2.init_params`` says why)."""
    D, dt = cfg.hidden_size, cfg.param_dtype
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    E, W = cfg.n_resident_experts, cfg.moe_intermediate_size
    keys = iter(jax.random.split(key, 4 + cfg.num_hidden_layers * 16))

    def w(*shape, dtype=dt, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def swiglu(width):
        return {"wg": w(D, width), "wu": w(D, width), "wd": w(width, D)}

    def layer(l):
        p = {
            "attn_norm": jnp.ones((D,), jnp.float32),
            "wq": w(D, H * hd), "wk": w(D, G * hd), "wv": w(D, G * hd),
            "wgate": w(D, H * hd),
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k_norm": jnp.ones((hd,), jnp.float32),
            "wo": w(H * hd, D),
            "post_attn_norm": jnp.ones((D,), jnp.float32),
            "ffn_norm": jnp.ones((D,), jnp.float32),
            "post_ffn_norm": jnp.ones((D,), jnp.float32),
        }
        if is_sparse(cfg, l):
            p["router"] = w(D, cfg.num_experts, dtype=jnp.float32)
            p["router_bias"] = w(cfg.num_experts, dtype=jnp.float32, std=0.001)
            p["shared"] = swiglu(W)
            # stacked: ONE loop runs the trips of all resident experts
            # (``kimi_k2.resident_experts``)
            p["experts"] = {"wg": w(E, D, W), "wu": w(E, D, W), "wd": w(E, W, D)}
        else:
            p["dense"] = swiglu(cfg.intermediate_size)
        return p

    return {"embed": w(cfg.vocab_size, D),
            "layers": [layer(l) for l in range(cfg.num_hidden_layers)],
            "final_norm": jnp.ones((D,), jnp.float32),
            "head": w(D, cfg.vocab_size)}


# ----------------------------------------------------------------- the layer


def rope(x, positions, cfg: TrinityConfig):
    """Plain rotary on the whole last axis of float32 ``x`` [..., heads,
    head_dim], half-split pairs (lane i with lane i + head_dim / 2);
    ``positions`` has x's leading shape less the head axis."""
    half = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_rows(cfg: TrinityConfig, p, layer: int, h, positions):
    """Residual rows h [..., D] float32 at ``positions`` [...] -> what
    attention and the cache need of them, in the weights' dtype: q
    [..., H, hd] and k [..., G, hd] (normalised a head, rotated on a sliding
    layer), v [..., G, hd]."""
    dt, eps = cfg.param_dtype, cfg.rms_norm_eps
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    x = _rms(h, p["attn_norm"], eps).astype(dt)
    lead = x.shape[:-1]
    q = _rms(_mm(x, p["wq"]).reshape(*lead, H, hd), p["q_norm"], eps)
    k = _rms(_mm(x, p["wk"]).reshape(*lead, G, hd), p["k_norm"], eps)
    if cfg.rotates(layer):
        q, k = rope(q, positions, cfg), rope(k, positions, cfg)
    v = _mm(x, p["wv"]).reshape(*lead, G, hd)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def ffn(cfg: TrinityConfig, p, u32, live, routing=None):
    """F of one block on normalised float32 rows [N, D]: (out [N, D] float32,
    stats int32 [4] in ``MOE_STATS`` order, or None for a dense layer).
    ``routing`` (experts [N, k], weights [N, k]) replaces the layer's own: a
    check's way to hold the experts apart from the router."""
    u = u32.astype(cfg.param_dtype)
    if "dense" in p:
        return _swiglu(p["dense"], u), None
    idx, w = route(cfg, p, u32) if routing is None else routing
    routed, stats = resident_experts(cfg, p, u, idx, w, live)
    return _swiglu(p["shared"], u) + routed, stats


def attention_branch(cfg: TrinityConfig, p, h, o):
    """What attention adds to the residual: the block's input h [..., D]
    float32 and attention's ``P v`` o [..., H * hd] -> ``(o * sigmoid(x
    Wgate)) Wo``, normalised. The gate is made HERE, from h again, not kept
    from ``attention_rows``: a long prompt's gate would lie in memory beside
    its q and its o for the length of the attention kernel."""
    a = o.astype(jnp.float32)
    if cfg.attention_gate:
        x = _rms(h, p["attn_norm"], cfg.rms_norm_eps).astype(cfg.param_dtype)
        a = a * jax.nn.sigmoid(_mm(x, p["wgate"]))
    branch = _mm(a.astype(cfg.param_dtype), p["wo"])
    return _rms(branch, p["post_attn_norm"], cfg.rms_norm_eps) if cfg.sandwich_norm else branch


def finish_rows(cfg: TrinityConfig, p, h, o, live):
    """The rest of a block after attention's ``P v``: h [N, D] float32 (the
    block's input), o [N, H * hd] in the weights' dtype, live [N] -> (h
    [N, D], stats)."""
    h = h + attention_branch(cfg, p, h, o)
    f, stats = ffn(cfg, p, _rms(h, p["ffn_norm"], cfg.rms_norm_eps), live)
    if cfg.sandwich_norm:
        f = _rms(f, p["post_ffn_norm"], cfg.rms_norm_eps)
    return h + f, stats


def _by_rows(chunk: int, fn, *xs):
    """``fn(*rows)`` over ``chunk`` rows at a time of arrays [N, ...], the
    results (a tuple of [chunk, ...] arrays) put back together as [N, ...].
    N is padded up with zeros for the last pass, and the padding cut."""
    N = xs[0].shape[0]
    if N <= chunk:
        return fn(*xs)
    pad = -N % chunk

    def passes(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
            -1, chunk, *x.shape[1:])

    out = jax.lax.map(lambda a: fn(*a), tuple(map(passes, xs)))
    return tuple(y.reshape(-1, *y.shape[2:])[:N] for y in out)


def attend(cfg: TrinityConfig, q, k, v, window: Optional[int]):
    """Causal grouped-query attention of whole sequences, q [B, T, H, hd], k /
    v [B, T, G, hd] -> [B, T, H * hd]; ``window``: keys a query sees."""
    B, T = q.shape[:2]
    if cfg.attn_impl == "flash" or (cfg.attn_impl == "auto" and T >= 128
                                    and jax.default_backend() == "tpu"):
        # heads side by side, as the projections wrote them: no transpose
        o = flash_attention(q, k, v, causal=True, window=window, layout="bthd")
    else:
        o = mha_reference(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True,
                          window=window).astype(q.dtype).transpose(0, 2, 1, 3)
    return o.reshape(B, T, -1)


def _prefill_layer(cfg: TrinityConfig, p, layer: int, h, positions, live):
    """One block over whole sequences: h [B, T, D] float32 -> (h, the rows the
    cache stores: k, v [B, T, G * hd])."""
    B, T, D = h.shape
    flat = lambda x: x.reshape(B * T, *x.shape[2:])  # noqa: E731
    q, k, v = _by_rows(
        cfg.prefill_chunk, lambda h_c, pos_c: attention_rows(cfg, p, layer, h_c, pos_c),
        flat(h), flat(positions))
    o = attend(cfg, q.reshape(B, T, *q.shape[1:]), k.reshape(B, T, *k.shape[1:]),
               v.reshape(B, T, *v.shape[1:]), cfg.window_of(layer))
    (h,) = _by_rows(
        cfg.prefill_chunk,
        lambda h_c, o_c, live_c: (finish_rows(cfg, p, h_c, o_c, live_c)[0],),
        flat(h), flat(o), flat(live))
    return h.reshape(B, T, D), (k.reshape(B, T, -1), v.reshape(B, T, -1))


def embed(cfg: TrinityConfig, params, tokens):
    h = params["embed"][tokens].astype(jnp.float32)
    return h * math.sqrt(cfg.hidden_size) if cfg.mup_enabled else h


def _head(cfg: TrinityConfig, params, h):
    u = _rms(h, params["final_norm"], cfg.rms_norm_eps).astype(cfg.param_dtype)
    return _mm(u, params["head"])


def prefill_forward(params, tokens, cfg: TrinityConfig, *, lengths=None):
    """tokens [B, T] -> (hidden [B, T, D] float32, the cache's rows a layer:
    a list of (k, v), each [B, T, G * hd]). Positions at or past ``lengths``
    [B] (padding of a bucket) route to no expert; the causal mask keeps them
    from the positions before."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    live = (positions < lengths[:, None]) if lengths is not None else (positions >= 0)
    h = embed(cfg, params, tokens)
    rows = []
    for l, p in enumerate(params["layers"]):
        h, stored = _prefill_layer(cfg, p, l, h, positions, live)
        rows.append(stored)
    return h, rows


def forward(params, tokens, cfg: TrinityConfig):
    """Full causal forward: tokens [B, T] -> logits [B, T, V] float32."""
    h, _ = prefill_forward(params, tokens, cfg)
    return _head(cfg, params, h)


# ----------------------------------------------------- the slot pool's family


class WindowedGQADecodeFamily:
    """What ``PagedDecodeSlotPool`` asks of a model family (the protocol is in
    ``paged_decode``'s docstring), for grouped-query attention with sliding
    and full layers: TWO cache groups — the full-attention layers, whose
    blocks live as long as the request, and the sliding layers with the
    window — and K and V arenas ``[L_group, n_blocks_group, block_T, G * hd]``
    for each; a decode step through ``paged_decode_attention`` (``kv_heads``,
    and ``starts`` on a sliding layer), and the step's routing counters."""

    speculative = False           # a verify window is not built for this family
    shares_prefix = False         # a block behind the window is handed back
    stat_names = MOE_STATS
    name = "trinity"

    def __init__(self, cfg: TrinityConfig):
        self.cfg = cfg
        self.n_layers = cfg.num_hidden_layers
        full = [l for l in range(self.n_layers) if cfg.layer_types[l] == FULL]
        sliding = [l for l in range(self.n_layers) if cfg.layer_types[l] == SLIDING]
        if not full:
            raise ValueError("the pool tells a live slot by a block that lives as "
                             "long as the request: the cut needs a full_attention layer")
        self.cache_groups = (CacheGroup(len(full)),) + (
            (CacheGroup(len(sliding), cfg.sliding_window),) if sliding else ())
        #: layer -> (its cache group, its place among the group's layers)
        self.place = {l: (0, i) for i, l in enumerate(full)}
        self.place.update({l: (1, i) for i, l in enumerate(sliding)})
        n = len(self.cache_groups)
        self.arena_groups = tuple(g for g in range(n) for _ in range(2))  # K, V a group
        self.cache_widths = (cfg.kv_width,) * (2 * n)
        self.cache_dtype = cfg.param_dtype
        self.n_sparse_layers = sum(is_sparse(cfg, l) for l in range(self.n_layers))
        self.n_resident_experts = cfg.n_resident_experts

    def resident(self, params):
        """Served in the dtype the weights come in (``param_dtype``, no
        masters): the resident tree IS the caller's."""
        return params

    def prefill(self, params, tokens, length):
        """tokens [1, Tb], length scalar -> (last live hidden [D], rows: K and
        V of each cache group's layers, [L_group, Tb, G * hd] an arena)."""
        h, rows = prefill_forward(params, tokens, self.cfg,
                                  lengths=jnp.reshape(length, (1,)))
        out = []
        for g in range(len(self.cache_groups)):
            mine = [rows[l] for l in sorted(self.place) if self.place[l][0] == g]
            out += [jnp.stack([k[0] for k, _ in mine]), jnp.stack([v[0] for _, v in mine])]
        return h[0, length - 1], tuple(out)

    def head(self, params, h):
        return _head(self.cfg, params, h)

    def cumulative_stats(self, sums: Dict[str, int], steps: int) -> Dict[str, int]:
        """``block_stats()``'s expert counters from the running sums of
        ``MOE_STATS`` over ``steps`` decode steps (prefill is not counted),
        with ``kimi_k2``'s names and meaning."""
        return {
            "moe_routed_tokens": sums["routed_tokens"],
            "moe_resident_assignments": sums["resident_assignments"],
            "moe_experts_touched": sums["experts_touched"],
            "moe_experts_resident": (self.n_resident_experts
                                     * self.n_sparse_layers * steps),
            "moe_load_max": sums["load_max"],
            "moe_load_sum": sums["resident_assignments"],
        }

    def attend_step(self, q, k_arena, v_arena, table, limits, layer: int):
        """One token a slot against its cache, through ``layer``'s group's
        table: q [S, 1, H * hd], limits [S, 1] (the keys below the query's
        limit exist; 0: a dead slot) -> [S, 1, H * hd]. On a sliding layer
        the first visible key is ``limit - window``."""
        cfg, window = self.cfg, self.cfg.window_of(layer)
        starts = None if window is None else jnp.maximum(limits - window, 0)
        return paged_decode_attention(
            q, k_arena, v_arena, table, limits, layer=self.place[layer][1],
            n_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            starts=starts)

    def decode_window(self, params, tokens, positions, arenas, tables):
        """One decode step of every slot: tokens / positions [S, 1]; arenas
        (K, V of the full group, K, V of the sliding group); tables one a
        group. Returns (logits [S, 1, V], arenas, stats int32 [4])."""
        cfg = self.cfg
        if tokens.shape[1] != 1:
            raise ValueError("the trinity family decodes one token a step")
        tables = tables if isinstance(tables, (tuple, list)) else (tables,)
        arenas = list(arenas)
        S = tokens.shape[0]
        live = tables[0][:, 0] > 0
        limits = jnp.where(live[:, None], positions + 1, 0)
        h = embed(cfg, params, tokens)                              # [S, 1, D]
        stats = jnp.zeros((len(MOE_STATS),), jnp.int32)
        for l, p in enumerate(params["layers"]):
            g, at = self.place[l]
            q, k, v = attention_rows(cfg, p, l, h, positions)
            arenas[2 * g] = _write_window(arenas[2 * g], at, tables[g], limits,
                                          k.reshape(S, 1, -1))
            arenas[2 * g + 1] = _write_window(arenas[2 * g + 1], at, tables[g], limits,
                                              v.reshape(S, 1, -1))
            o = self.attend_step(q.reshape(S, 1, -1), arenas[2 * g], arenas[2 * g + 1],
                                 tables[g], limits, l)
            h2, s = finish_rows(cfg, p, h[:, 0], o[:, 0], live)
            h = h2[:, None]
            if s is not None:
                stats = stats + s
        return _head(cfg, params, h), tuple(arenas), stats
