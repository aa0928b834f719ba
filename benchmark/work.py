"""The work an algorithm REQUIRES, from its shapes: what MFU and a kernel's
roofline share divide by. Kept with the benchmark so that no PR that claims a
gain can change the yardstick. Recomputed operations are not counted."""

from __future__ import annotations

import re
from typing import Optional, Tuple


def matmul_params_per_layer(d_model: int, d_ff: int) -> int:
    """Weights a token is multiplied by in one block: QKV, output, two FFN."""
    return 4 * d_model * d_model + 2 * d_model * d_ff


def train_flops_per_step(model: dict, *, batch: int, seq: int,
                         head_positions: Optional[int] = None) -> float:
    """Forward + backward FLOPs of one training step.

    6 x (matmul weights) per token for the blocks; attention's two batched
    matmuls, 4 T d forward and twice that backward = 12 L T d per token, half
    of it under a causal mask; the head (the d x d transform and the tied
    d x V decoder, 6 FLOPs a weight) at its own positions only
    (``head_positions`` a sequence, else every position). Embedding lookups,
    LayerNorm, softmax, GELU and the optimizer are not matmuls and are left
    out, as the usual definition of model FLOPs does."""
    d, L, V = model["d_model"], model["n_layers"], model["vocab_size"]
    tokens = batch * seq
    blocks = 6.0 * L * matmul_params_per_layer(d, model["d_ff"]) * tokens
    attn = 12.0 * L * seq * d * tokens * (0.5 if model.get("causal") else 1.0)
    head_tokens = batch * (head_positions if head_positions else seq)
    head = 6.0 * (d * d + d * V) * head_tokens
    return blocks + attn + head


def flash_call_work(kind: str, *, bh: int, tq: int, tk: int, d: int,
                    causal: bool, bytes_per_el: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one flash kernel call requires.

    ``fwd``: S = QK^T and PV, 4 Tq Tk D a head; reads Q, K, V, writes O (and
    the log-sum-exp, 4 bytes a row). ``dkv``: recomputes S and forms dV, dP,
    dK: 8 Tq Tk D; reads Q, K, V, dO (+ lse, delta), writes dK, dV. ``dq``:
    recomputes S and forms dP, dQ: 6 Tq Tk D; reads Q, K, V, dO, writes dQ.
    The recomputation of S is part of the flash algorithm's own required work
    (it is what keeps memory O(T)), so it is counted; a causal mask halves
    the FLOPs."""
    pairs = float(bh) * tq * tk * d * (0.5 if causal else 1.0)
    q_bytes = bh * tq * d * bytes_per_el
    k_bytes = bh * tk * d * bytes_per_el
    row = bh * tq * 4
    if kind == "fwd":
        return 4 * pairs, 2 * q_bytes + 2 * k_bytes + row
    if kind == "dkv":
        return 8 * pairs, 2 * q_bytes + 4 * k_bytes + 2 * row
    if kind == "dq":
        return 6 * pairs, 3 * q_bytes + 2 * k_bytes + 2 * row
    raise ValueError(f"unknown flash kernel kind {kind!r}")


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


_SHAPES = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def classify_flash_call(name: str) -> Optional[Tuple[str, int, int, int]]:
    """(kind, BH, T, D) of a Mosaic flash call from its trace name, which
    carries the shapes it writes: the forward writes O [BH,T,D] and the
    log-sum-exp f32[BH,T,1]; dKV writes two [BH,T,D]; dQ writes one."""
    shapes = [tuple(int(x) for x in dims.split(",") if x)
              for _, dims in _SHAPES.findall(name)]
    big = [s for s in shapes if len(s) == 3 and s[2] > 1]
    if not big:
        return None
    bh, t, d = big[0]
    if any(len(s) == 3 and s[2] == 1 for s in shapes):
        return "fwd", bh, t, d
    return ("dkv" if len(big) >= 2 else "dq"), bh, t, d
