"""Persistent Pallas block-size autotuner (ISSUE 12 tentpole layer 3).

The flash-attention kernels' block sizes are a table in this file, measured
on a v5e at the benchmark's training shapes (PERF.md section 6, PR 32) and
keyed by what a call shows: T_q, T_k, D, causal or not, and which of the
three kernels. CUDA-L1 (PAPERS.md 2507.14111) and the GPU↔CPU transpilation work
(2207.00257) both land on the same lesson: kernel parameters must be
*measured per (op, shape, dtype)*, not assumed — and the measurements must
persist, or every process pays the search again.

Three pieces:

- :func:`resolve_blocks` — what ``flash_attention`` consults before its
  static defaults: a persisted measured entry for this (op, shape-bucket,
  dtype) wins; otherwise the measured static table
  (:func:`static_flash_blocks`) answers. Shape buckets reuse
  ``common.bucketing`` so nearby shapes share one entry, exactly like they
  share one XLA executable.
- :class:`AutotuneTable` — the JSON table persisting winners under
  ``TDL_AUTOTUNE_DIR``, keyed per backend so a TPU table never leaks onto
  GPU. With the variable unset there is no default table: block sizes come
  from the static table in this file, so no state outside git steers the
  kernel.
- :func:`autotune_flash_attention` — the measured search: timed best-of-N
  per candidate with warmup discard, fwd+bwd (training is the workload that
  matters), and a regression guard — a "winner" that measures slower than
  the call with no block argument (the static table's choice) is
  discarded, so the tuned table is ≥ the table in source at every point
  by construction. On CPU / interpret
  mode, timing the Pallas interpreter would be noise, so the search takes a
  deterministic fallback: it times nothing and records a row with no block
  (``measured: false``), so lookups keep answering what the static table
  answers — tier-1 stays green and byte-stable.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..common.bucketing import bucket_size

log = logging.getLogger(__name__)

ENV_DIR = "TDL_AUTOTUNE_DIR"

#: the three Pallas kernels of flash attention, in the order
#: ``flash_attention`` carries their blocks
FLASH_KERNELS: Tuple[str, ...] = ("fwd", "dkv", "dq")

#: candidate (block_q, block_k) search grid — multiples of the 128-lane MXU
#: tile (see /opt guide tiling constraints); every block the static table
#: answers is a member, so exact-match against it is always reachable.
FLASH_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 128), (128, 256), (256, 256), (256, 512),          # block-ok: candidate grid
    (512, 512), (512, 1024), (1024, 512), (1024, 1024),      # block-ok: candidate grid
)

#: the VMEM a kernel is given unless it asks for more (v5e: 16 MiB of 128)
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def block_vmem_bytes(block_q: int, block_k: int, D: int, *,
                     backward: bool, itemsize: int = 2) -> int:
    """What one grid step holds in VMEM: its q-side and k-side blocks,
    double-buffered (forward q, o and k, v; backward q, dO and k, v, dK, dV),
    its float32 accumulators, and two score-shaped float32 blocks (forward S
    and P; backward S / P and dP / dS, each pair in place). Held against
    Mosaic ahead of time for a v5e (PR 32): at 1024 x 1024 every kernel
    compiles up to D 256, where this reads 13-16 MB, and none at D 512."""
    if backward:
        sides = (2 * block_q + 4 * block_k) * D * itemsize * 2
        accs = 2 * block_k * D * 4
    else:
        sides = (2 * block_q + 2 * block_k) * D * itemsize * 2
        accs = block_q * D * 4
    return sides + accs + 2 * block_q * block_k * 4


def _fit(want: int, T: int) -> int:
    """The largest multiple of 128 that divides T rounded up to 128 and is at
    most ``want``: a block never pads further than the 128 alignment Mosaic
    needs, so a 128-token bucket never chews a 512-wide block."""
    n = -(-T // 128)
    return 128 * max(d for d in range(1, n + 1)
                     if n % d == 0 and d <= max(1, want // 128))


def static_flash_blocks(Tq: int, Tk: int, *, D: int = 64,
                        causal: bool = False,
                        kernel: str = "fwd") -> Tuple[int, int]:
    """The table in source: (block_q, block_k) of one kernel of
    ``FLASH_KERNELS`` for a call of these lengths, measured on a v5e on
    today's kernels at D 64 (PERF.md section 6, PR 32: ms a call by block,
    kernel and shape). The grid runs sequentially and a step costs ~0.3 us
    before it computes, so a block is as large as pays:

    - the forward takes up to 1024 x 1024: with the whole key axis in one
      block it keeps no running softmax state, which beats skipping one
      causal block in four at T 1024;
    - the backward kernels take the same without a causal mask; under one
      they take 512 x 512, so that there are dead blocks to skip (a fifth
      off both at T 1024), except dQ from T 2048 on, where 1024 x 1024 wins
      again (3 % at 2048, 11 % at 4096);
    - wide heads shrink a block until ``block_vmem_bytes`` fits.
    """
    if kernel not in FLASH_KERNELS:
        raise ValueError(f"kernel must be one of {FLASH_KERNELS}, got {kernel!r}")
    want_q = want_k = 1024  # block-ok: measured, PR 32
    if causal and (kernel == "dkv" or (kernel == "dq" and Tk < 2048)):
        want_q = want_k = 512  # block-ok: measured, PR 32
    backward = kernel != "fwd"
    while block_vmem_bytes(want_q, want_k, D, backward=backward) > _VMEM_BUDGET_BYTES:
        if want_k >= want_q:
            want_k //= 2
        else:
            want_q //= 2
    return _fit(want_q, Tq), _fit(want_k, Tk)


def candidate_valid(block_q: int, block_k: int, Tq: int, Tk: int,
                    D: int) -> bool:
    """A candidate is searchable when its blocks don't exceed the (bucketed)
    sequence lengths — the pad shim would round T up to the block and the
    kernel would mostly chew padding — and what a backward grid step holds
    (the search times forward and backward with one block) fits VMEM."""
    if block_q > max(Tq, 128) or block_k > max(Tk, 128):
        return False
    return block_vmem_bytes(block_q, block_k, D,
                            backward=True) <= _VMEM_BUDGET_BYTES


def shape_key(op: str, *, B: int, H: int, Tq: int, Tk: int, D: int,
              dtype: str) -> str:
    """Per-(op, shape-bucket, dtype) table key. T dims bucket to powers of
    two (min one 128-block), B*H to a power of two — shapes that would
    share an XLA executable after bucketing share an autotune entry."""
    bh = bucket_size(max(1, B * H))
    tq = bucket_size(Tq, min_bucket=128)
    tk = bucket_size(Tk, min_bucket=128)
    return f"{op}|bh{bh}|tq{tq}|tk{tk}|d{D}|{dtype}"


# --------------------------------------------------------------- the table


class AutotuneTable:
    """Persistent per-backend winner table.

    On-disk format (``autotune_<backend>.json``, atomic tmp+rename)::

        {"version": 1, "backend": "tpu",
         "entries": {"flash_attention|bh16|tq8192|tk8192|d64|bfloat16":
                     {"block_q": 512, "block_k": 1024, "measured": true,
                      "best_us": 22400.0, "static_us": 80800.0,
                      "trials": 3}}}

    An entry with no ``block_q`` / ``block_k`` says the static table won (or
    nothing was timed): lookups fall through to it.

    A corrupt or missing file degrades to an empty table (the static
    fallback answers every lookup), never an exception on the hot path.
    """

    VERSION = 1

    def __init__(self, path: Optional[str] = None,
                 backend: Optional[str] = None):
        if backend is None:
            import jax

            backend = jax.default_backend()
        self.backend = backend
        self.path = path
        self._lock = threading.Lock()
        self._entries: Dict[str, dict] = {}
        if path:
            self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                data = json.load(f)
            if (isinstance(data, dict) and data.get("version") == self.VERSION
                    and data.get("backend") == self.backend
                    and isinstance(data.get("entries"), dict)):
                self._entries = {k: v for k, v in data["entries"].items()
                                 if isinstance(v, dict)}
            elif isinstance(data, dict) and data.get("backend") not in (
                    None, self.backend):
                log.warning("autotune table %s is for backend %r, not %r — "
                            "starting empty", self.path,
                            data.get("backend"), self.backend)
        except FileNotFoundError:
            pass
        except (OSError, ValueError) as e:
            log.warning("autotune table %s unreadable (%s) — starting empty",
                        self.path, e)

    def save(self) -> None:
        if not self.path:
            return
        with self._lock:
            payload = {"version": self.VERSION, "backend": self.backend,
                       "entries": dict(self._entries)}
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".autotune_")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            # atomic AND durable: readers never see a torn file, and both
            # the bytes and the rename are fsynced (ISSUE 15 discipline —
            # measured winners survive power loss)
            from ..common.durability import durable_replace

            durable_replace(tmp, self.path, fsync=True)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def lookup(self, key: str) -> Optional[dict]:
        with self._lock:
            e = self._entries.get(key)
            return dict(e) if e else None

    def record(self, key: str, entry: dict, persist: bool = True) -> None:
        with self._lock:
            self._entries[key] = dict(entry)
        _metrics()[1].set(len(self._entries))
        if persist:
            self.save()

    def forget(self, key: str) -> None:
        with self._lock:
            self._entries.pop(key, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


_DEFAULT_TABLE: Optional[AutotuneTable] = None
_TABLE_LOCK = threading.Lock()


def default_table_path() -> Optional[str]:
    """``$TDL_AUTOTUNE_DIR/autotune_<backend>.json``, or None when the
    variable is unset. The table deliberately does NOT live beside the
    executable cache: a table left in a shared cache directory by one run
    would change the next run's block sizes."""
    import jax

    d = os.environ.get(ENV_DIR)
    if not d:
        return None
    return os.path.join(d, f"autotune_{jax.default_backend()}.json")


def get_table(refresh: bool = False) -> AutotuneTable:
    """The process-default table (re-resolved when the env contract
    changes)."""
    global _DEFAULT_TABLE
    path = default_table_path()
    with _TABLE_LOCK:
        if (_DEFAULT_TABLE is None or refresh
                or _DEFAULT_TABLE.path != path):
            _DEFAULT_TABLE = AutotuneTable(path)
        return _DEFAULT_TABLE


def reset_table() -> None:
    """Drop the cached default table (tests re-pointing the env contract)."""
    global _DEFAULT_TABLE
    with _TABLE_LOCK:
        _DEFAULT_TABLE = None


# ---------------------------------------------------------------- metrics


def _metrics():
    from ..monitoring.registry import get_registry

    r = get_registry()
    lookups = r.counter(
        "tdl_autotune_lookups_total",
        "Block-size resolutions by source: a persisted measured entry "
        "('table') or the static table in source ('static')",
        labels=("op", "source"))
    entries = r.gauge(
        "tdl_autotune_table_entries",
        "Entries in the process-default autotune table")
    trials = r.counter(
        "tdl_autotune_trials_total",
        "Timed candidate measurements run by autotune searches",
        labels=("op",))
    return lookups, entries, trials


# ---------------------------------------------------------------- resolve


def resolve_blocks(op: str, *, B: int, H: int, Tq: int, Tk: int, D: int,
                   dtype: str, causal: bool = False, kernel: str = "fwd",
                   table: Optional[AutotuneTable] = None) -> Tuple[int, int]:
    """The kernel-side front door: persisted measured winner for this
    (op, shape-bucket, dtype) if one exists — one block, timed over forward
    and backward, for all three kernels — else the static table's answer
    for this kernel."""
    t = table if table is not None else get_table()
    entry = t.lookup(shape_key(op, B=B, H=H, Tq=Tq, Tk=Tk, D=D, dtype=dtype))
    lookups, _, _ = _metrics()
    if entry and "block_q" in entry and "block_k" in entry:
        lookups.labels(op, "table").inc()
        return int(entry["block_q"]), int(entry["block_k"])
    lookups.labels(op, "static").inc()
    return static_flash_blocks(Tq, Tk, D=D, causal=causal, kernel=kernel)


# ----------------------------------------------------------------- search


def _time_best_of(fn, *args, trials: int, warmup: int = 1) -> float:
    """Best-of-N seconds with the first ``warmup`` runs discarded (the
    first run pays compilation; best-of over the rest sheds scheduler
    noise — the same discipline as bench.py's calibration probes)."""
    import jax

    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_flash_attention(B: int, H: int, T: int, D: int,
                             dtype=None, *, causal: bool = False,
                             trials: int = 3,
                             candidates=None,
                             table: Optional[AutotuneTable] = None,
                             interpret: Optional[bool] = None,
                             include_backward: bool = True,
                             persist: bool = True) -> dict:
    """Measure flash-attention block candidates for one (shape, dtype)
    point and record the winner.

    Returns the recorded entry (also persisted to the table). The winner
    can never regress below the static table: the call with no block
    argument — the static table's blocks, each kernel its own — is always
    measured as the baseline, and a candidate (one block for all three
    kernels) must beat it to displace it; where none does, the entry
    carries no block and lookups keep falling through to the static table.
    In interpret mode (CPU tier-1) the search is the deterministic
    fallback described in the module docstring.
    """
    import jax
    import jax.numpy as jnp

    from .attention import flash_attention

    if dtype is None:
        dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = table if table is not None else get_table()
    key = shape_key("flash_attention", B=B, H=H, Tq=T, Tk=T, D=D,
                    dtype=jnp.dtype(dtype).name)

    if interpret:
        # deterministic fallback: the Pallas interpreter's wall time says
        # nothing about Mosaic tiles, so "measuring" would persist noise.
        # The static table is the answer; record that, unmeasured and with
        # no block of its own, so lookups stay stable and answer exactly
        # what the static table answers for each kernel.
        entry = {"measured": False, "source": "static-fallback", "trials": 0}
        t.record(key, entry, persist=persist)
        return entry

    cands = [c for c in (candidates or FLASH_CANDIDATES)
             if candidate_valid(c[0], c[1], T, T, D)]

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, T, D), dtype)
    k = jnp.asarray(rs.randn(B, H, T, D), dtype)
    v = jnp.asarray(rs.randn(B, H, T, D), dtype)

    def run_for(bq, bk):
        """``(None, None)`` is the call as every caller makes it."""
        def attend(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, interpret=interpret)

        if include_backward:
            def loss(q, k, v):
                return jnp.sum(attend(q, k, v).astype(jnp.float32))

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))  # donate-ok: timing harness re-reads its inputs every trial
        return jax.jit(attend)  # donate-ok: timing harness re-reads its inputs every trial

    _, _, trials_counter = _metrics()
    static = (None, None)
    # the baseline must be the table in source: a row already held for this
    # key would answer the call with no block argument instead
    t.forget(key)
    timings: Dict[Tuple[Optional[int], Optional[int]], float] = {}
    last_error: Optional[Exception] = None
    for bq, bk in cands + [static]:
        try:
            timings[(bq, bk)] = _time_best_of(run_for(bq, bk), q, k, v,
                                              trials=trials)
            trials_counter.labels("flash_attention").inc(trials)
        except Exception as e:  # a candidate the hardware rejects is skipped
            log.warning("autotune: candidate (%s, %s) failed at T=%d D=%d: "
                        "%s", bq, bk, T, D, e)
            last_error = e
    if not timings:
        # the compiled kernel ran for NO candidate, the static choice
        # included: the kernel is broken on this device, and recording a
        # fallback entry would hide that behind a table row
        raise RuntimeError(
            f"autotune: every flash-attention candidate {cands} failed on "
            f"{jax.default_backend()} at B={B} H={H} T={T} D={D}"
        ) from last_error
    static_s = timings.get(static, float("inf"))
    best = min(timings, key=timings.get)
    if timings[best] > static_s:
        # regression guard: the acceptance bar is "tuned >= static at every
        # grid point" — when measurement noise crowns a slower candidate,
        # the static table stays the answer
        best = static
    entry = {"measured": True,
             "best_us": round(timings[best] * 1e6, 1),
             "static_us": (None if static_s == float("inf")
                           else round(static_s * 1e6, 1)),
             "trials": trials}
    if best != static:
        entry["block_q"], entry["block_k"] = best
    t.record(key, entry, persist=persist)
    return entry
