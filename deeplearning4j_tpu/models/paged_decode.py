"""Block-paged KV cache with CoW prefix sharing and speculative decoding:
the slot pool every served model decodes through, and the arena's format.

A dense ``[L, slots, maxT, ...]`` cache makes every slot pay worst-case HBM
whether its sequence is 12 tokens or 500.  Here the cache is a vLLM-shape
paged arena behind ONE decode-step signature: the step always runs over the
whole slot pool, whatever subset of slots is live, so membership churn
(continuous batching) never mints a new executable, and prompt lengths pad
to the common power-of-two bucket ladder (``common.bucketing``), so they
share a handful of prefill executables.

- **arena** — K/V live in ``[L, n_blocks, block_T, H*hd]`` (one block is a
  contiguous, lane-dense tile holding every head); block 0 is a scratch
  ("trash") block that absorbs a prefill's writes to positions that belong
  to a shared block or lie past the reservation, so the jitted prefill
  never branches on sharing (a decode step writes nothing of a dead slot).
  Every program that takes the arenas updates them IN PLACE: they are
  donated, each layer scatters its window's K/V into its cells of the
  buffer it was given, and the same buffer is the result;
- **block tables** — each slot owns a ``[max_blocks]`` int32 row mapping
  logical block -> physical block (0 = unmapped/trash).  The decode math
  reaches its keys through the tables in
  :func:`~..kernels.paged_attention.paged_decode_attention`, which copies
  only the blocks a slot's live length reaches: bytes moved follow live
  tokens, not ``slots x max_len``.  Tables and lengths change every step;
  shapes never do, so ``decode_traces`` pins to 1 under admit/retire/alloc
  churn;
- **copy-on-write prefix sharing** — an exact-match index (keyed on the
  literal prompt token bytes — no hash-collision wrongness) maps full
  prompt-prefix blocks and partial prompt tails to physical blocks.  An
  admission that matches takes a refcount instead of recomputing prefill
  for those blocks; a sharer that must WRITE into a joined partial block
  first copies it into a block reserved for exactly that purpose at
  admission time (so CoW can never fail mid-decode);
- **block-priced admission** — ``admit`` prices a request as
  ``ceil((prompt + max_new [+ spec slack]) / block_T)`` blocks minus what
  the prefix index already holds, and raises :class:`NoFreeBlocksError`
  (``retry_admission = True``) when the arena cannot hold it NOW — the
  serving executor re-queues instead of failing the request;
- **cache groups** — layers with a sliding window never read a key more than
  ``window`` positions back, so their blocks need not outlive it. A windowed
  group maps at most ``window / block_T + 2`` blocks a slot: prefill stores
  only the rows a later query can still see (the rest go to the trash
  block), and every ``step`` hands the blocks that fell behind the window
  back to the group's free list, on the host, and maps the next one from
  what the admission reserved. An admission is priced in every group (the
  whole span, or ``min`` of that and ``window / block_T + 2``) and waits when
  ANY group is short;
- **one step ahead** — a decode step is two calls: ``dispatch()`` launches
  it and returns at once, ``collect()`` reads the oldest uncollected step's
  tokens back (``step()`` is the one after the other). Greedy decoding needs
  nothing from the host between two steps: the next step's tokens ARE the
  last step's output, which stays on the device and is an operand of the one
  decode program beside the host's own tokens for the slots it knows better
  (just admitted, or already collected); positions advance by one and budgets
  are the host's. So a caller may dispatch step n+1 BEFORE it collects step n
  and do its host work (retirement, tables, uploads) while the device
  computes; a slot released meanwhile has its token in flight dropped;
- **speculative decoding** — with a small draft model from the same zoo, one
  jitted step drafts ``k`` greedy tokens (k+1 chained single-token passes
  over the draft's own paged arena, sharing the block tables) and verifies
  them in ONE batched target forward over the (k+1)-token window.  Greedy
  acceptance (``n_acc = 1 + cumprod(match).sum()``) makes the emitted
  stream token-identical to plain greedy decoding by construction; rejected
  positions hold stale K/V that the sequential write-before-read discipline
  overwrites before it is ever attended.

**Model families.** The pool does not know a layer, and this module imports
no model and no kernel. The config it is given answers ``decode_family()``
with an object of its model's file (``transformer.TransformerDecodeFamily``:
K and V arenas of ``H*hd``; ``kimi_k2.LatentDecodeFamily``: one latent arena;
``keye_vl.SparseGQADecodeFamily``: K, V and an index-key arena, and XLA's
gather of the rows its indexer selected in place of a kernel;
``trinity.WindowedGQADecodeFamily``: K and V of its full-attention layers in
one cache group and of its sliding-window layers in another),
which writes its rows with :func:`_write_window` and attends through the
tables with a kernel of ``kernels/paged_attention.py``. The families share the
allocator, the tables, the prefix index, copy-on-write, admit / step /
release, the counters and the donated in-place programs below. What a family
answers, stated here once (no base class: four implementations and this list):

- ``name``; ``speculative`` (whether ``decode_window`` takes W > 1 tokens a
  slot, which a verify window needs); ``n_layers``; ``cache_widths`` (what one
  token stores in a block: one arena ``[L, n_blocks, block_T, width]`` a
  width) and ``cache_dtype``; ``stat_names`` (the int32 counters a step
  returns, which come back in the one fetch that brings the tokens);
- ``cache_groups`` and ``arena_groups``: a family whose layers do not all
  keep a request's whole context answers a tuple of :class:`CacheGroup`
  (``n_layers`` layers with ONE ``window``; ``None``: a block lives as long
  as the request) and, an arena, the group it belongs to; an arena of group
  g is ``[g.n_layers, n_blocks_g, block_T, width]``. The pool keeps, a group,
  its own allocator and its own table ``[slots, max_blocks]`` (logical block
  -> physical; a block a windowed group handed back reads 0), and
  ``prefill`` / ``decode_window`` take and return the arenas in the family's
  order while ``tables`` is a TUPLE, one a group. A family that does not
  answer is one group of all its layers with ``window None``, and ``tables``
  is the one array it always was;
- ``shares_prefix``: whether the prefix index and copy-on-write apply. A
  family with a windowed group answers False (a block that is handed back
  behind the window cannot be a later request's prefix), and the pool then
  neither looks a prompt up nor registers it;
- ``resident(params) -> params``: what the family's programs read, made ONCE
  from what the caller holds when the pool is constructed, and kept as
  ``pool.params``: every leaf that a step would cast before use is cast here
  (a transformer's float32 master matmul weights into the compute dtype), a
  leaf already in its dtype is the SAME array, shapes map to shapes. A pool
  serves from this resident copy, so a caller who only serves may drop its
  masters after constructing the pool; ``params`` below is this tree;
- ``prefill(params, tokens [1, Tb], length) -> (hidden state at length - 1
  [D], rows: one [L, Tb, width] an arena)``;
- ``decode_window(params, tokens [S, W], positions [S, W], arenas, tables)
  -> (logits [S, W, V], arenas written in place, stats or None)``: a slot is
  live iff its logical block 0 is mapped (in a group whose blocks live as
  long as the request);
- ``head(params, h [N, D]) -> logits [N, V]``;
- ``cumulative_stats(sums, steps) -> dict``: what ``block_stats()`` shows of
  the running sums of ``stat_names``.

Single-owner object: the decode loop thread (or the offline ``generate``
driver) is the only caller — no internal locking.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..monitoring.trace import span


class KvCacheLostError(RuntimeError):
    """A donated prefill/decode call failed after its KV buffers were
    consumed: every in-flight sequence is lost. The pool has already reset
    itself (fresh zero cache, all slots free), so the NEXT admission works —
    one transient device fault must not poison the pool forever.
    ``all_sequences_lost`` is the duck-typed marker the serving executor
    keys on (sessions are duck-typed; it cannot import this class)."""

    all_sequences_lost = True


class NoFreeBlocksError(RuntimeError):
    """The paged arena cannot hold this admission RIGHT NOW (it would fit an
    empty arena — unsatisfiable-ever requests are a ``ValueError`` instead).
    ``retry_admission`` is the duck-typed marker the serving executor keys
    on to re-queue the request at the head of the line rather than fail it."""

    retry_admission = True


class BlockAllocator:
    """Refcounted free-list allocator over the arena's physical blocks.

    Block 0 (trash) is never handed out.  ``reserved`` blocks are held back
    from admission so an already-admitted sharer's copy-on-write can never
    fail; a reserve is consumed by decrementing ``reserved`` before
    ``alloc``.  The prefix index lives here too so that a block's index
    keys die with its last reference."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 usable + trash), got {n_blocks}")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(1, n_blocks))  # block 0 = trash
        self.refcount = np.zeros(n_blocks, np.int32)
        self.reserved = 0
        self._index: Dict[Any, int] = {}     # prefix key -> physical block
        self._keys_of: Dict[int, list] = {}  # physical block -> [keys]

    @property
    def free_blocks(self) -> int:
        """Blocks available to NEW admissions (CoW reserves held back)."""
        return len(self._free) - self.reserved

    def alloc(self, count: int) -> List[int]:
        if count > self.free_blocks:
            raise NoFreeBlocksError(
                f"{count} KV blocks needed, {self.free_blocks} free "
                f"({self.reserved} reserved for copy-on-write)")
        out = [self._free.pop(0) for _ in range(count)]
        for b in out:
            self.refcount[b] = 1
        return out

    def ref(self, block: int) -> None:
        self.refcount[block] += 1

    def unref(self, block: int) -> None:
        self.refcount[block] -= 1
        if self.refcount[block] <= 0:
            self.refcount[block] = 0
            for key in self._keys_of.pop(block, ()):
                if self._index.get(key) == block:
                    del self._index[key]
            self._free.append(block)

    def register(self, key, block: int) -> None:
        """Publish ``block`` under ``key`` in the prefix index (first
        registration wins — identical later prompts share instead)."""
        if key not in self._index:
            self._index[key] = block
            self._keys_of.setdefault(block, []).append(key)

    def lookup(self, key) -> Optional[int]:
        return self._index.get(key)


class CacheGroup(NamedTuple):
    """Layers of a family that keep the same span of a request's context:
    ``n_layers`` of them, each reading no key more than ``window`` positions
    back (``None``: the whole context, so a block lives as long as the
    request)."""

    n_layers: int
    window: Optional[int] = None


class _Group:
    """One cache group's share of the pool's host state: its allocator, its
    table and, a slot, the logical blocks it maps (``lo .. hi - 1``) and the
    blocks its admission reserved and has not mapped yet (``owed``)."""

    def __init__(self, spec: CacheGroup, *, slots: int, block_T: int,
                 max_blocks: int, n_blocks: Optional[int]):
        self.n_layers, self.window = spec.n_layers, spec.window
        #: most blocks a slot maps at once: a window's span laid over block
        #: boundaries and the block the next token opens
        self.cap = (max_blocks if self.window is None
                    else min(max_blocks, self.window // block_T + 2))
        self.n_blocks = n_blocks or 1 + slots * self.cap
        if self.n_blocks < 2:
            raise ValueError("n_blocks must be >= 2 (1 usable + trash)")
        self.alloc = BlockAllocator(self.n_blocks)
        self.tables = np.zeros((slots, max_blocks), np.int32)
        self.lo = np.zeros(slots, np.int32)
        self.hi = np.zeros(slots, np.int32)
        self.owed = np.zeros(slots, np.int32)

    def price(self, nblocks: int) -> int:
        """Blocks an admission of ``nblocks`` logical blocks holds at most."""
        return min(nblocks, self.cap)

    def first_needed(self, position: int, block_T: int) -> int:
        """First logical block a query at ``position`` still sees."""
        if self.window is None:
            return 0
        return max(0, position - self.window + 1) // block_T

    def reset(self) -> None:
        self.alloc = BlockAllocator(self.n_blocks)
        self.tables[:] = 0
        self.lo[:] = self.hi[:] = self.owed[:] = 0


class _Flight:
    """A dispatched decode step: the slots it steps (``riders``, one flag a
    slot: a slot released before the step is collected is struck out, and its
    token dropped), the program's results while they are on the device, and
    the step's ``{slot: [tokens]}`` answer once they were read back."""

    __slots__ = ("riders", "results", "answer")

    def __init__(self, riders: np.ndarray, results):
        self.riders, self.results = riders, results
        self.answer: Optional[Dict[int, List[int]]] = None


def _write_window(arena, layer: int, tables, limits, x):
    """``arena[layer, block, cell] = x[s, w]`` at position ``limits[s, w] - 1``
    of every live slot s, through its table, in place; nothing of a dead slot
    (limits 0) is written.  arena [L, n_blocks, block_T, D]; x [S, W, D]."""
    n_blocks, block_T = arena.shape[1], arena.shape[2]
    pos = limits - 1
    block = jnp.take_along_axis(tables, jnp.maximum(pos, 0) // block_T, axis=1)
    block = jnp.where(pos >= 0, block, n_blocks)  # out of range: dropped
    return arena.at[layer, block.reshape(-1), (pos % block_T).reshape(-1)].set(
        x.reshape(-1, x.shape[-1]).astype(arena.dtype), mode="drop")


def _write_blocks(arena, dest_blocks, x):
    """``arena[:, dest_blocks[j]] = x[:, j]`` for every j, in place:
    arena [L, n_blocks, block_T, D], x [L, nb, block_T, D]."""
    def put(j, a):
        blk = jax.lax.dynamic_slice_in_dim(x, j, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(a, blk.astype(a.dtype),
                                                   dest_blocks[j], axis=1)
    return jax.lax.fori_loop(0, x.shape[1], put, arena)


class PagedDecodeSlotPool:
    """``slots`` concurrent sequences over one paged arena: ``admit``
    prefills a prompt into a free slot, ``step`` advances every live
    sequence (``dispatch`` launches the step, ``collect`` reads the oldest
    uncollected one back: the serving loop keeps one step ahead with them),
    ``release`` frees a slot; ``free_slots``, ``prompt_bucket``,
    the trace counters and the :class:`KvCacheLostError` reset are what any
    session of the serving executor has. It discovers three more by
    ``getattr``:

    - ``can_admit``/``request_blocks``/``total_blocks`` — block-priced
      admission control (queue-head gating and at-the-door 400s);
    - ``block_stats()`` — occupancy, CoW sharing, speculative counters and
      the blocks attention read against the blocks mapped, for
      ``stats()``/telemetry;
    - multi-token steps: ``step()`` returns ``{slot: [tokens...]}`` (one
      token per step plain, up to ``spec_tokens + 1`` speculative), each
      list clamped to the slot's remaining ``max_new_tokens`` budget.

    ``n_blocks`` sizes the arena (default: what ``slots`` full-length
    requests hold at most, + the trash block): one number, or one a cache
    group for a family that names several, and ``pool.n_blocks`` reads back
    the same way.

    Pass ``draft_params``/``draft_cfg`` (a smaller config from the same
    zoo — same vocab, causal) to enable speculative decoding with
    ``spec_tokens`` drafted per target step.
    """

    def __init__(self, params, cfg, *, slots: int = 8, block_T: int = 16,
                 n_blocks: Union[int, Sequence[int], None] = None,
                 max_len: Optional[int] = None, eos_id: Optional[int] = None,
                 min_prompt_bucket: int = 16,
                 draft_params=None, draft_cfg=None, spec_tokens: int = 4):
        fam = cfg.decode_family()
        dfam = draft_cfg.decode_family() if draft_cfg is not None else None
        if draft_cfg is not None and not fam.speculative:
            raise ValueError(
                f"speculative decoding is not built for the {fam.name} "
                f"family: its decode step takes one token a slot")
        if not cfg.causal:
            raise ValueError(
                "autoregressive decode needs a causal config "
                "(TransformerConfig(causal=True)) — a bidirectional encoder "
                "cannot extend a sequence incrementally")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if block_T < 1 or (block_T & (block_T - 1)):
            raise ValueError(f"block_T must be a power of two, got {block_T}")
        self.max_len = max_len or cfg.max_len
        if self.max_len > cfg.max_len:
            raise ValueError(f"max_len {self.max_len} exceeds the model's "
                             f"positional range max_len={cfg.max_len}")
        if self.max_len % block_T:
            raise ValueError(f"max_len {self.max_len} must be a multiple of "
                             f"block_T {block_T}")
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("speculative decoding needs BOTH draft_params "
                             "and draft_cfg (or neither)")
        # the programs read a resident copy made once, in the dtypes a step
        # computes in: never the caller's masters, cast again every token
        self.params = fam.resident(params)
        self.cfg = cfg
        self.slots = slots
        self.block_T = block_T
        self.eos_id = eos_id
        self.max_blocks = self.max_len // block_T  # logical blocks per slot
        # the family's cache groups (one of all layers, for a family that
        # names none): an allocator, a table and ``n_blocks`` each, by default
        # what ``slots`` full-length requests hold at most
        specs = tuple(getattr(fam, "cache_groups", None)
                      or (CacheGroup(fam.n_layers),))
        self._arena_groups = tuple(getattr(fam, "arena_groups", None)
                                   or (0,) * len(fam.cache_widths))
        each = (tuple(n_blocks) if isinstance(n_blocks, (tuple, list))
                else (n_blocks,) * len(specs))
        if len(each) != len(specs) or len(self._arena_groups) != len(fam.cache_widths):
            raise ValueError(
                f"the {fam.name} family has {len(specs)} cache group(s) over "
                f"{len(fam.cache_widths)} arenas: got n_blocks {n_blocks!r}, "
                f"arena_groups {self._arena_groups!r}")
        self._groups = [
            _Group(spec, slots=slots, block_T=block_T, max_blocks=self.max_blocks,
                   n_blocks=each[g]) for g, spec in enumerate(specs)]
        self._windowed = any(g.window is not None for g in self._groups)
        self._shares_prefix = bool(getattr(fam, "shares_prefix", True))
        if len(specs) > 1 and (self._shares_prefix or draft_cfg is not None):
            raise ValueError(
                f"the {fam.name} family has {len(specs)} cache groups: the "
                f"prefix index, copy-on-write and a draft model's arenas go by "
                f"ONE table (shares_prefix must be False, and no draft)")
        if self._windowed and (self._shares_prefix or specs[0].window is not None):
            raise ValueError(
                f"the {fam.name} family has a windowed cache group: a block "
                f"handed back behind the window can be no prefix of a later "
                f"request (shares_prefix must be False), and group 0 says "
                f"which slots are live by its block 0 (its window must be None)")
        self.n_blocks = (self._groups[0].n_blocks if len(specs) == 1
                         else tuple(g.n_blocks for g in self._groups))
        # bucket sizes must stay block-aligned so prefill scatter is whole blocks
        self.min_prompt_bucket = max(1, min_prompt_bucket, block_T)

        self.draft_params = (dfam.resident(draft_params)
                             if draft_cfg is not None else None)
        self.draft_cfg = draft_cfg
        self.spec_tokens = int(spec_tokens) if draft_cfg is not None else 0
        if draft_cfg is not None:
            if self.spec_tokens < 1:
                raise ValueError(f"spec_tokens must be >= 1, got {spec_tokens}")
            if not draft_cfg.causal:
                raise ValueError("draft model must be causal")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} — greedy verify compares token ids")
            if draft_cfg.max_len < self.max_len:
                raise ValueError(
                    f"draft positional range {draft_cfg.max_len} < pool "
                    f"max_len {self.max_len}")

        self.family = fam
        # bytes of the distinct leaves the decode program is handed
        self._resident_weight_bytes = sum({
            id(x): math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves((self.params, self.draft_params))}.values())
        self._arenas = tuple(self._new_arena(cfg))
        self._draft_arenas = (tuple(self._new_arena(draft_cfg, draft=True))
                              if draft_cfg is not None else ())
        self._active = np.zeros(slots, bool)
        self._positions = np.zeros(slots, np.int32)
        self._tokens = np.zeros(slots, np.int32)
        self._budget = np.zeros(slots, np.int32)    # max_new_tokens per slot
        self._emitted = np.zeros(slots, np.int32)   # tokens handed to caller
        # tokens handed out once every dispatched step is collected: a slot
        # whose budget is dispatched is stepped no more, collected or not
        self._dispatched = np.zeros(slots, np.int32)
        self._span = np.zeros(slots, np.int32)      # reserved position span
        self._cow_reserve = np.zeros(slots, np.int32)
        self._joined: Dict[int, Dict[int, int]] = {}  # slot -> {logical: phys}
        # dispatched steps whose answers the caller has not collected, oldest
        # first, and the last dispatched step's output (tokens, then the
        # family's counters): the next step's tokens, where they stay
        self._flying: Deque[_Flight] = deque()
        self._carry = jnp.zeros(slots + len(fam.stat_names), jnp.int32)
        # cumulative: steps dispatched, and those dispatched while the step
        # before them was still uncollected (the host's work rode under it)
        self.kv_steps = 0
        self.kv_steps_overlapped = 0
        # cumulative speculative counters (0 forever on a plain pool)
        self.spec_proposed = 0
        self.spec_accepted = 0
        # cumulative blocks a step's attention is asked to visit (live slots,
        # up to their live length) against the blocks the tables map
        self.kv_blocks_read = 0
        self.kv_blocks_mapped = 0
        # a pool with a windowed group also counts, cumulatively: the blocks a
        # step's attention is asked to visit in a windowed layer, the blocks
        # handed back behind a window, and the cached rows a step's attention
        # reads summed over ALL layers beside what it would read without a
        # window
        self.kv_blocks_read_windowed = 0
        self.kv_window_blocks_freed = 0
        self.swa_rows_read = 0
        self.swa_rows_windowless = 0
        # what the family's steps counted (``stat_names``): the last step's
        # and the running sums; a family that counts nothing has neither
        self.last_step_stats: Dict[str, int] = {}
        self.family_stats = {name: 0 for name in fam.stat_names}
        self.family_steps = 0
        # python-side trace counters: incremented when jax TRACES (not runs)
        # the fns — tests pin "one decode signature under membership churn"
        self.decode_traces = 0
        self.prefill_traces = 0

        bT = self.block_T
        spec = draft_cfg is not None
        k = self.spec_tokens
        n = len(fam.cache_widths)                        # arenas of the target
        m = len(dfam.cache_widths) if spec else 0        # and of the draft
        ng = len(self._groups)                           # tables, dest lists
        of = self._arena_groups

        # every program takes its arenas as leading positional arguments
        # (after the parameters), donates them and returns them first

        def _decode(params, *args):
            self.decode_traces += 1
            arenas, tables = args[:n], args[n:n + ng]
            carry, fresh, positions = args[n + ng:]
            # a slot's token is the step before's output, where it lies, unless
            # the host knows it (``fresh`` >= 0: just admitted, or collected)
            tokens = jnp.where(fresh >= 0, fresh, carry[:slots])
            # one group: the table itself, as every family before groups
            logits, arenas, stats = fam.decode_window(
                params, tokens[:, None], positions[:, None], arenas,
                tables[0] if ng == 1 else tables)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            # the step's counters ride behind the tokens: one fetch brings both
            out = nxt if stats is None else jnp.concatenate([nxt, stats])
            return (*arenas, out)

        def _spec(params, dparams, *args):
            self.decode_traces += 1
            arenas, darenas = args[:n], args[n:n + m]
            tables, tokens, positions = args[n + m:]
            # --- draft phase: k+1 chained single-token passes.  Pass j
            # consumes window[j] at position p+j; passes 0..k-1 propose
            # d_1..d_k; pass k only WRITES draft K/V at p+k so a fully
            # accepted round leaves no hole in the draft cache.
            window = [tokens]
            for j in range(k + 1):
                pos_j = (positions + j)[:, None]
                logits, darenas, _ = dfam.decode_window(
                    dparams, window[j][:, None], pos_j, darenas, tables)
                if j < k:
                    window.append(
                        jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32))
            win = jnp.stack(window, axis=1)                      # [S, k+1]
            pos_w = positions[:, None] + jnp.arange(k + 1)[None, :]
            # --- verify phase: ONE batched target forward over the window
            logits, arenas, _ = fam.decode_window(params, win, pos_w, arenas,
                                                  tables)
            ver = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k+1]
            # greedy acceptance: d_i accepted while it matches the target's
            # own greedy continuation; emitted tokens are ver[:, :n_acc]
            acc = (win[:, 1:] == ver[:, :-1]).astype(jnp.int32)
            n_acc = 1 + jnp.cumprod(acc, axis=1).sum(axis=1)
            return (*arenas, *darenas, ver, n_acc.astype(jnp.int32))

        def _store(family, params, arenas, dests, tokens, length):
            """Prefill through ``family``: (arenas with the prompt's rows in
            their blocks, the last live hidden state). ``dests``: a
            destination list a cache group (a draft's arenas are all of the
            one group)."""
            last, rows = family.prefill(params, tokens, length)
            arenas = tuple(
                _write_blocks(a, dests[of[i] if len(dests) > 1 else 0], r.reshape(
                    r.shape[0], r.shape[1] // bT, bT, r.shape[2]))
                for i, (a, r) in enumerate(zip(arenas, rows)))
            return arenas, last

        def _prefill(params, *args):
            self.prefill_traces += 1
            arenas, last = _store(fam, params, args[:n], args[n:n + ng],
                                  *args[n + ng:])
            logits = fam.head(params, last[None])[0]
            return (*arenas, jnp.argmax(logits, axis=-1).astype(jnp.int32))

        def _prefill_spec(params, dparams, *args):
            self.prefill_traces += 1
            dest, rest = args[n + m:n + m + 1], args[n + m + 1:]
            arenas, last = _store(fam, params, args[:n], dest, *rest)
            darenas, _ = _store(dfam, dparams, args[n:n + m], dest, *rest)
            logits = fam.head(params, last[None])[0]
            return (*arenas, *darenas,
                    jnp.argmax(logits, axis=-1).astype(jnp.int32))

        def _copy(*args):
            """Block ``src`` to block ``dst`` in every arena, in place."""
            *arenas, src, dst = args

            def one(a):
                blk = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(a, blk, dst, axis=1)

            return tuple(one(a) for a in arenas)

        # arena buffers are donated and every program above returns the buffer
        # it was given, updated: a step moves the bytes it writes, not the arena
        if spec:
            donated = tuple(range(2, 2 + n + m))
            self._decode_fn = jax.jit(_spec, donate_argnums=donated)
            self._prefill_fn = jax.jit(_prefill_spec, donate_argnums=donated)
        else:
            donated = tuple(range(1, 1 + n))
            self._decode_fn = jax.jit(_decode, donate_argnums=donated)
            self._prefill_fn = jax.jit(_prefill, donate_argnums=donated)
        self._copy_fn = jax.jit(_copy, donate_argnums=tuple(range(n + m)))

    def _new_arena(self, cfg, draft: bool = False):
        """Zeroed arenas for ``cfg``'s family: one a cache width, each with
        its group's layers and blocks (a draft's arenas go by the one table
        of group 0)."""
        fam = cfg.decode_family()
        if draft:
            g = self._groups[0]
            return tuple(jnp.zeros((fam.n_layers, g.n_blocks, self.block_T, w),
                                   fam.cache_dtype) for w in fam.cache_widths)
        return tuple(
            jnp.zeros((self._groups[g].n_layers, self._groups[g].n_blocks,
                       self.block_T, w), fam.cache_dtype)
            for g, w in zip(self._arena_groups, fam.cache_widths))

    # one group's allocator and table under the names they had before groups
    @property
    def _alloc(self) -> BlockAllocator:
        return self._groups[0].alloc

    @property
    def _tables(self) -> np.ndarray:
        return self._groups[0].tables

    def _set_arenas(self, arenas) -> None:
        n = len(self._arenas)
        self._arenas, self._draft_arenas = tuple(arenas[:n]), tuple(arenas[n:])

    def _run(self, program, *args):
        """Call a donated program on the parameters and arenas the pool
        holds, keep the arenas it returns, return the rest of its results."""
        params = ((self.params,) if self.draft_cfg is None
                  else (self.params, self.draft_params))
        out = program(*params, *self._arenas, *self._draft_arenas, *args)
        held = len(self._arenas) + len(self._draft_arenas)
        self._set_arenas(out[:held])
        return out[held:]

    # -- capacity ----------------------------------------------------------

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    @property
    def free_slots(self) -> int:
        return int(self.slots - self._active.sum())

    @property
    def occupancy(self) -> int:
        return int(self._active.sum())

    @property
    def total_blocks(self) -> int:
        """Usable arena blocks (trash blocks excluded), summed over the cache
        groups — the capacity an admission's worst-case block price
        (``request_blocks``, summed likewise) is checked against at the door."""
        return sum(g.n_blocks - 1 for g in self._groups)

    @property
    def admit_overhead_tokens(self) -> int:
        """Extra positions every admission reserves beyond prompt+max_new
        (speculative lookahead scratch) — the executor adds this to its
        at-the-door max_len validation."""
        return self.spec_tokens

    def request_blocks(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case (no sharing) block price of a request: in every cache
        group, the blocks of its whole span or, windowed, no more than the
        group ever maps for a slot."""
        span = prompt_len + max_new_tokens + self.spec_tokens
        return sum(g.price(-(-span // self.block_T)) for g in self._groups)

    def prompt_bucket(self, n: int) -> int:
        from ..common.bucketing import bucket_size

        return min(self.max_len, bucket_size(n, min_bucket=self.min_prompt_bucket))

    def block_stats(self) -> Dict[str, int]:
        """Occupancy / sharing / speculation counters for ``stats()`` and
        the ``tdl_decode_blocks_*`` + ``tdl_decode_spec_*`` families, and
        two cumulative counts a step: ``kv_blocks_read`` (what the attention
        kernel is asked to visit: over live slots, the blocks up to the
        window's last position) and ``kv_blocks_mapped`` (``slots x
        max_blocks``: what a dense gather through the tables would visit).
        ``kv_steps`` counts the steps dispatched and ``kv_steps_overlapped``
        those dispatched while the step before them was still uncollected
        (the caller's host work ran under the device's).
        ``kv_cache_bytes_per_token`` is what one token stores over all layers
        and arenas; ``resident_weight_bytes`` the bytes of every leaf of the
        resident tree(s) the decode program is handed (the draft's too; a leaf
        two views share counts once). A family whose steps count
        (``stat_names``) adds what it makes of the running sums
        (``cumulative_stats``: the latent family's ``moe_*`` counters); for
        another family they are absent. A pool with a windowed cache group
        adds ``kv_blocks_read_windowed`` (``kv_blocks_read``'s count for ONE
        windowed layer: the blocks from the first a slot's query still sees),
        ``kv_window_blocks_freed`` (blocks handed back behind a window),
        ``swa_rows_read`` / ``swa_rows_windowless`` (cached rows a step's
        attention reads, summed over all layers, beside what it would read
        with no window) and ``blocks_total_g<i>`` / ``blocks_free_g<i>`` a
        group; ``blocks_total`` / ``blocks_free`` are sums over the groups."""
        rc = self._alloc.refcount[1:]  # trash block is bookkeeping, not capacity
        fam = self.family
        grouped = {}
        if self._windowed:
            grouped = {"kv_blocks_read_windowed": self.kv_blocks_read_windowed,
                       "kv_window_blocks_freed": self.kv_window_blocks_freed,
                       "swa_rows_read": self.swa_rows_read,
                       "swa_rows_windowless": self.swa_rows_windowless}
            for i, g in enumerate(self._groups):
                grouped[f"blocks_total_g{i}"] = g.n_blocks - 1
                grouped[f"blocks_free_g{i}"] = g.alloc.free_blocks
        return {
            **grouped,
            **fam.cumulative_stats(self.family_stats, self.family_steps),
            "kv_cache_bytes_per_token": int(
                sum(self._groups[g].n_layers * w
                    for g, w in zip(self._arena_groups, fam.cache_widths))
                * jnp.dtype(fam.cache_dtype).itemsize),
            "resident_weight_bytes": self._resident_weight_bytes,
            "blocks_total": self.total_blocks,
            "blocks_free": sum(g.alloc.free_blocks for g in self._groups),
            "cow_shared_blocks": int((rc > 1).sum()),
            "cow_saved_blocks": int(np.maximum(rc - 1, 0).sum()),
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "kv_blocks_read": self.kv_blocks_read,
            "kv_blocks_mapped": self.kv_blocks_mapped,
            "kv_steps": self.kv_steps,
            "kv_steps_overlapped": self.kv_steps_overlapped,
        }

    def cached_rows(self, slot: int, n: int):
        """What the arenas hold of ``slot``'s first ``n`` positions, through
        its group's block table: one [L_group, n, width] array an arena (a
        position whose block a windowed group handed back reads the trash
        block). For checks and tests (a copy; the arenas stay where they
        are)."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        pos = np.arange(n)
        return tuple(
            a[:, self._groups[g].tables[slot, pos // self.block_T], pos % self.block_T]
            for g, a in zip(self._arena_groups, self._arenas))

    def block_tables(self, slot: int):
        """``slot``'s table rows, one a cache group (logical block -> physical;
        0: unmapped). For checks and tests (copies)."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        return tuple(g.tables[slot].copy() for g in self._groups)

    # -- admission planning ------------------------------------------------

    def _plan(self, toks: np.ndarray, max_new_tokens: int):
        """Price an admission: (span, nblocks, shared_full, tail_block,
        new_needed, reserve_needed).  Raises ValueError for never-fits."""
        n = toks.shape[0]
        if n < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        span = n + max_new_tokens + self.spec_tokens
        if span > self.max_len:
            slack = (f" + {self.spec_tokens} speculative slack"
                     if self.spec_tokens else "")
            raise ValueError(
                f"prompt of {n} tokens + {max_new_tokens} new tokens{slack} "
                f"exceeds the {self.max_len}-position KV cache")
        bT = self.block_T
        nblocks = -(-span // bT)
        fb = n // bT if self._shares_prefix else 0   # no lookup: nothing shared
        shared_full: List[int] = []
        for i in range(fb):
            b = self._alloc.lookup(("full", toks[:(i + 1) * bT].tobytes()))
            if b is None:
                break
            shared_full.append(b)
        tail = None
        if self._shares_prefix and len(shared_full) == fb and n % bT:
            tail = self._alloc.lookup(("tail", toks.tobytes()))
        new_needed = nblocks - len(shared_full) - (0 if tail is None else 1)
        reserve = 0 if tail is None else 1
        return span, nblocks, shared_full, tail, new_needed, reserve

    def can_admit(self, prompt, max_new_tokens: int = 1) -> bool:
        """Dry-run admission check (slot + blocks, prefix sharing counted)
        — the executor's queue-head gate.  False means 'not NOW'; a
        never-fits request raises the same ValueError ``admit`` would."""
        toks = np.asarray(prompt, np.int32).reshape(-1)
        _, nblocks, _, _, new_needed, reserve = self._plan(toks, max_new_tokens)
        if not (~self._active).any():
            return False
        return not self._short_group(nblocks, new_needed + reserve)

    def _short_group(self, nblocks: int, first_needs: int) -> Optional[str]:
        """What the cache group that cannot hold an admission NOW lacks (None:
        every group can). The one group of a family that shares prefixes
        needs ``first_needs`` (sharing counted); a group of another family
        the price of ``nblocks`` logical blocks."""
        for i, g in enumerate(self._groups):
            need = first_needs if self._shares_prefix else g.price(nblocks)
            if g.alloc.free_blocks < need:
                return (f"{need} new KV blocks in cache group {i} "
                        f"(window {g.window}) but only {g.alloc.free_blocks} of "
                        f"{g.n_blocks - 1} are free")
        return None

    # -- lifecycle ---------------------------------------------------------

    def admit(self, prompt, max_new_tokens: int = 1):
        """Prefill ``prompt`` into a free slot, paying only for blocks the
        prefix index does not already hold.  Returns ``(slot, first_token)``.
        Raises ``ValueError`` (never fits), ``RuntimeError`` (no free slot),
        :class:`NoFreeBlocksError` (no blocks NOW — re-queueable), or
        ``KvCacheLostError`` (donated prefill failed; pool already reset)."""
        toks = np.asarray(prompt, np.int32).reshape(-1)
        n = toks.shape[0]
        n_span, nblocks, shared_full, tail, new_needed, reserve = \
            self._plan(toks, max_new_tokens)
        free = np.flatnonzero(~self._active)
        if free.size == 0:
            raise RuntimeError("no free decode slot")
        short = self._short_group(nblocks, new_needed + reserve)
        if short:
            raise NoFreeBlocksError(
                f"admission needs {short}"
                f"{f' (+{reserve} of them a CoW reserve)' if reserve else ''}")
        slot = int(free[0])
        bT = self.block_T
        fb = n // bT

        # every group but the first maps the blocks a LATER query still sees
        # (all of them where there is no window) and the one the first new
        # token opens; what else its price covers is reserved, and mapped as
        # decoding gets there
        rows_stored = [n]
        for g in self._groups[1:]:
            lo = g.first_needed(n, bT)
            hi = nblocks if g.window is None else min(nblocks, n // bT + 1)
            g.tables[slot, lo:hi] = g.alloc.alloc(hi - lo)  # a free slot's row is zeros
            g.lo[slot], g.hi[slot] = lo, hi
            g.owed[slot] = g.price(nblocks) - (hi - lo)
            g.alloc.reserved += int(g.owed[slot])
            rows_stored.append(n - lo * bT)

        new_blocks = self._alloc.alloc(new_needed)
        for b in shared_full:
            self._alloc.ref(b)
        row = np.zeros(self.max_blocks, np.int32)
        li = 0
        for b in shared_full:
            row[li] = b
            li += 1
        joined: Dict[int, int] = {}
        if tail is not None:
            self._alloc.ref(tail)
            self._alloc.reserved += 1
            self._cow_reserve[slot] = 1
            joined[li] = tail  # logical tail block: copy before first write
            row[li] = tail
            li += 1
        for b in new_blocks:
            row[li] = b
            li += 1

        bucket = self.prompt_bucket(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = toks
        # prefill scatters whole blocks; shared blocks (and the bucket's
        # padding overshoot past the reservation) are redirected to the
        # trash block 0 so a sharer's prefill can never clobber live K/V
        shared_set = set(shared_full) | ({tail} if tail is not None else set())
        dest = np.zeros(bucket // bT, np.int32)
        for j in range(bucket // bT):
            if j < nblocks and row[j] not in shared_set:
                dest[j] = row[j]
        # another group's list: its mapped blocks; rows behind its window go
        # to the trash block with the bucket's padding
        dests = [dest] + [g.tables[slot, :bucket // bT].copy()
                          for g in self._groups[1:]]
        stored = ({f"rows_stored_g{i}": r for i, r in enumerate(rows_stored)}
                  if len(self._groups) > 1 else {})
        try:
            with span("kv.prefill", bucket=bucket, shared_blocks=len(shared_set),
                      new_blocks=len(new_blocks), **stored):
                first = self._run(self._prefill_fn, *dests, padded, np.int32(n))[0]
                with span("kv.prefill.fetch"):
                    first = int(first)  # the host waits for the prefill here
        except Exception as e:
            raise self._lost("prefill", e) from e

        # publish this prompt's freshly WRITTEN blocks for future sharers
        for i in range(fb if self._shares_prefix else 0):
            if i >= len(shared_full):
                self._alloc.register(("full", toks[:(i + 1) * bT].tobytes()),
                                     int(row[i]))
        if self._shares_prefix and n % bT and tail is None:
            self._alloc.register(("tail", toks.tobytes()), int(row[fb]))

        self._tables[slot] = row
        self._groups[0].lo[slot], self._groups[0].hi[slot] = 0, nblocks
        self._active[slot] = True
        self._positions[slot] = n
        self._tokens[slot] = first
        self._budget[slot] = max_new_tokens
        self._emitted[slot] = self._dispatched[slot] = 1
        self._span[slot] = n_span
        self._joined[slot] = joined
        return slot, first

    def _cow_before_write(self, slot: int, p_lo: int, p_hi: int) -> None:
        """Copy any JOINED shared block this step will write into (positions
        p_lo..p_hi inclusive) into the block reserved at admission.  The
        original registrant keeps writing in place — safe, because every
        sharer of a tail block has the identical prompt, masks positions
        >= its length, and copies before its own first write."""
        bT = self.block_T
        joined = self._joined.get(slot)
        if not joined:
            return
        for lb in range(p_lo // bT, p_hi // bT + 1):
            old = joined.pop(lb, None)
            if old is None:
                continue
            if self._cow_reserve[slot] > 0:
                self._cow_reserve[slot] -= 1
                self._alloc.reserved -= 1
            new = self._alloc.alloc(1)[0]
            try:
                self._set_arenas(self._copy_fn(
                    *self._arenas, *self._draft_arenas, np.int32(old),
                    np.int32(new)))
            except Exception as e:
                raise self._lost("copy-on-write", e) from e
            self._tables[slot, lb] = new
            self._alloc.unref(old)

    def step(self) -> Dict[int, List[int]]:
        """Advance EVERY live slot through ONE fixed-signature XLA call and
        wait for it: ``dispatch()`` then ``collect()``.

        Returns ``{slot: [tokens...]}`` — one token plain, up to
        ``spec_tokens + 1`` speculative, clamped to the slot's remaining
        ``max_new_tokens`` budget.  The caller decides retirement (EOS /
        budget / deadline) and calls :meth:`release`.  (A caller that left a
        step uncollected gets THAT step's answer: answers come oldest first.)"""
        self.dispatch()
        return self.collect() or {}

    def dispatch(self) -> bool:
        """Launch one decode step of every slot with budget left and return
        without waiting for it: its tokens stay on the device, where the next
        step reads them, so a caller may ``dispatch()`` step n+1 BEFORE it
        ``collect()``s step n and do its host work under the device's.

        The host's side of a step is settled here (``kv.step.prepare``:
        copy-on-write, the windows slid, the tokens the host knows, the step's
        counters), then the uploads and the launch; positions advance. A slot
        whose budget is dispatched is sent as a dead slot from then on (zero
        table row, position 0), released or not, so no slot is stepped past
        its budget. Returns True when a step is left RUNNING; False when there
        is nothing to wait for: no slot had budget left or, in a pool with a
        draft, the step was read back here (its next positions wait for the
        accepted count) and ``collect()`` hands the answer over."""
        spec = self.draft_cfg is not None
        window = self.spec_tokens + 1 if spec else 1
        with span("kv.step.prepare"):
            stepping = self._active & (self._dispatched < self._budget)
            live = np.flatnonzero(stepping)
            if live.size == 0:
                return False
            if (self._positions[live] + window > self._span[live]).any():
                raise RuntimeError(
                    "a live slot is at the end of its reserved block span — "
                    "the caller must retire sequences at their token budget")
            for s in live:
                s = int(s)
                self._cow_before_write(s, int(self._positions[s]),
                                       int(self._positions[s]) + window - 1)
            grouped = self._slide_windows(live) if self._windowed else {}
            # the step before, where it is still running: its riders' tokens
            # are its output on the device; every other token the host knows
            ahead = self._flying[-1] if self._flying else None
            running = ahead is not None and ahead.answer is None
            fresh = np.where(stepping, self._tokens, 0)
            if running:
                fresh[ahead.riders & stepping] = -1
            live_blocks = int(
                (-(-(self._positions[live] + window) // self.block_T)).sum())
            mapped_blocks = self.slots * self.max_blocks
            self.kv_blocks_read += live_blocks
            self.kv_blocks_mapped += mapped_blocks
            self.kv_steps += 1
            self.kv_steps_overlapped += running
        with span("kv.step.upload"):
            # private copies: the host edits its tables while the step runs
            tables = [jnp.asarray(np.where(stepping[:, None], g.tables, 0))
                      for g in self._groups]
            toks = jnp.asarray(fresh)
            pos = jnp.asarray(np.where(stepping, self._positions, 0))
        try:
            # a step's own routing is known when its tokens come back: the
            # span carries the counters of the step fetched last
            with span("kv.step.dispatch", live_blocks=live_blocks,
                      mapped_blocks=mapped_blocks, **grouped,
                      **self.last_step_stats):
                operands = (toks, pos) if spec else (self._carry, toks, pos)
                results = self._run(self._decode_fn, *tables, *operands)
        except Exception as e:
            raise self._lost("decode step", e) from e
        flight = _Flight(stepping, results)
        self._flying.append(flight)
        if spec:
            self._land(flight)
            return False
        self._carry = results[0]
        self._positions[live] += 1
        self._dispatched[live] += 1
        return True

    def collect(self) -> Optional[Dict[int, List[int]]]:
        """The answer of the OLDEST uncollected step, ``{slot: [tokens...]}``,
        read back now if it was not yet (``kv.step.fetch``: the host waits
        here for the device); None when no step is uncollected. A slot
        released since its step was dispatched (EOS, deadline) has that token
        dropped: it is never credited to the slot's next tenant."""
        if not self._flying:
            return None
        flight = self._flying[0]
        if flight.answer is None:
            self._land(flight)  # a failure resets the pool: nothing is left
        self._flying.popleft()
        return flight.answer

    def _land(self, flight: _Flight) -> None:
        """Read ``flight``'s results back (the one host round trip a step,
        S4: ``kv.step.fetch``, which carries ``ready``, whether the result was
        there before the read) and credit them (``kv.step.land``): the
        family's counters, and for every rider still in its slot its
        token(s), ``_tokens`` and ``_emitted``."""
        # ``ready`` is asked BEFORE the blocking read: a result that is not
        # there yet means the device was still running when the host came
        # back for it (the device paced this step); one that is, the host did
        try:
            ready = int(flight.results[0].is_ready())
            with span("kv.step.fetch", ready=ready):
                fetched = [np.asarray(r) for r in flight.results]
        except Exception as e:
            raise self._lost("decode step", e) from e
        with span("kv.step.land"):
            flight.results = None   # the device's buffers go here
            out: Dict[int, List[int]] = {}
            riders = [int(s) for s in np.flatnonzero(flight.riders)]
            if self.draft_cfg is None:
                nxt = fetched[0]
                if self.family.stat_names:
                    nxt, counted = nxt[:self.slots], nxt[self.slots:]
                    self.last_step_stats = {
                        name: int(v) for name, v in zip(self.family.stat_names,
                                                        counted)}
                    for name, v in self.last_step_stats.items():
                        self.family_stats[name] += v
                    self.family_steps += 1
                for slot in riders:
                    out[slot] = [int(nxt[slot])]
                    self._tokens[slot] = nxt[slot]
                    self._emitted[slot] += 1
            else:
                ver, n_acc = fetched
                for slot in riders:
                    na = int(n_acc[slot])
                    self.spec_proposed += self.spec_tokens
                    self.spec_accepted += na - 1
                    remaining = int(self._budget[slot] - self._emitted[slot])
                    take = min(na, max(remaining, 0))
                    out[slot] = [int(t) for t in ver[slot, :take]]
                    self._positions[slot] += na
                    self._tokens[slot] = int(ver[slot, na - 1])
                    self._emitted[slot] += take
                    self._dispatched[slot] = self._emitted[slot]
        flight.answer = out

    def _slide_windows(self, live) -> Dict[str, int]:
        """Before a step, on the host, for every windowed cache group and live
        slot: hand the blocks that fell wholly behind the window of the
        step's query back to the group's free list (the table entry reads 0
        from now on: the kernel never looks there again) and map the block
        the step writes into, from what the admission reserved. Counts the
        step's rows and blocks; returns what ``kv.step.dispatch`` carries of
        them."""
        bT = self.block_T
        at = self._positions[live].astype(np.int64)
        freed, carried = 0, {}
        self.swa_rows_windowless += int((at + 1).sum()) * self.family.n_layers
        for i, g in enumerate(self._groups):
            if g.window is None:
                self.swa_rows_read += int((at + 1).sum()) * g.n_layers
                carried[f"live_blocks_g{i}"] = int((at // bT + 1).sum())
                continue
            for s in live:
                s, p = int(s), int(self._positions[s])
                first = g.first_needed(p, bT)
                while g.lo[s] < min(first, g.hi[s]):
                    g.alloc.unref(int(g.tables[s, g.lo[s]]))
                    g.tables[s, g.lo[s]] = 0
                    g.lo[s] += 1
                    g.owed[s] += 1
                    g.alloc.reserved += 1
                    freed += 1
                while g.hi[s] <= p // bT:
                    g.owed[s] -= 1
                    g.alloc.reserved -= 1
                    g.tables[s, g.hi[s]] = g.alloc.alloc(1)[0]
                    g.hi[s] += 1
            seen = int((at // bT - np.maximum(at - g.window + 1, 0) // bT + 1).sum())
            self.kv_blocks_read_windowed += seen
            self.swa_rows_read += int(np.minimum(at + 1, g.window).sum()) * g.n_layers
            carried[f"live_blocks_g{i}"] = seen
        self.kv_window_blocks_freed += freed
        carried["window_blocks_freed"] = freed
        return carried

    def release(self, slot: int) -> None:
        """Free a slot: drop its block references (shared blocks survive
        while other sequences or the prefix index's last holder need them),
        return any unused CoW reserve and what a windowed group still held
        reserved for it, and clear the table rows. A token of the slot that
        is still in flight is dropped when its step is collected."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        for g in self._groups:
            for lb in range(int(g.lo[slot]), int(g.hi[slot])):
                g.alloc.unref(int(g.tables[slot, lb]))
            g.alloc.reserved -= int(g.owed[slot])
            g.tables[slot] = 0
            g.lo[slot] = g.hi[slot] = g.owed[slot] = 0
        self._alloc.reserved -= int(self._cow_reserve[slot])
        self._cow_reserve[slot] = 0
        self._active[slot] = False
        self._positions[slot] = 0
        self._tokens[slot] = 0
        self._budget[slot] = 0
        self._emitted[slot] = 0
        self._dispatched[slot] = 0
        self._span[slot] = 0
        self._joined.pop(slot, None)
        for flight in self._flying:  # its token in flight belongs to nobody
            flight.riders[slot] = False

    def _lost(self, what: str, e: BaseException) -> KvCacheLostError:
        """Reset after a donated call failed; the error to raise from it."""
        self._reset_after_failure()
        return KvCacheLostError(
            f"{what} failed after its KV buffers were donated "
            f"({type(e).__name__}: {e}); cache reset, in-flight sequences lost")

    def _reset_after_failure(self) -> None:
        """Recover from a failed donated call: fresh zero arenas, fresh
        allocator (the prefix index dies with the K/V it pointed at), all
        slots free.  In-flight sequences are lost (the caller tells their
        riders); the pool itself keeps serving."""
        self._arenas = tuple(self._new_arena(self.cfg))
        if self.draft_cfg is not None:
            self._draft_arenas = tuple(self._new_arena(self.draft_cfg, draft=True))
        for g in self._groups:
            g.reset()
        self._active[:] = False
        self._positions[:] = 0
        self._tokens[:] = 0
        self._budget[:] = 0
        self._emitted[:] = 0
        self._dispatched[:] = 0
        self._span[:] = 0
        self._cow_reserve[:] = 0
        self._joined.clear()
        self._flying.clear()
        self._carry = jnp.zeros_like(self._carry)
