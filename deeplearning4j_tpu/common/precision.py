"""Mixed-precision policy (TDL_MATMUL_PRECISION).

Reference: nd4j exposes a global dtype (``Nd4j.setDefaultDataTypes``) and the
cuDNN helpers pick TensorCore math where legal; the TPU equivalent (SURVEY.md
§7.2 #8, BASELINE.md protocol) is an AMP policy applied inside the ONE
compiled train step:

- **master params fp32** — updater state and the canonical weights stay
  float32 for stable accumulation;
- **compute bf16** — a cast-on-entry copy of params + activations feeds the
  MXU at bf16 (2x HBM bandwidth, full-rate systolic array);
- **loss/statistics fp32** — logits are upcast before softmax/log, batch-norm
  moments are computed in fp32 (see ``BatchNormalization.forward_bn``);
- **grads fp32** — the transpose of the entry cast re-accumulates gradients
  in float32 automatically (JAX's convert_element_type transpose), so the
  updater sees fp32 grads against fp32 masters.

Policy values (env ``TDL_MATMUL_PRECISION`` or ``env().set(...)``):
``auto`` (default) → bf16 AMP on the ``tpu`` backend, fp32 on ``cpu``, so
CPU/dev runs keep the reference's fp32-default training numerics while the
TPU path gets MXU-rate bf16; any other backend is an error under ``auto``
(name the policy explicitly there); ``bfloat16``/``bf16`` → AMP
unconditionally;
``float32``/``highest`` → everything fp32 (the numerics-testing default);
``tf32`` → treated as float32 on TPU (no tf32 unit; XLA's fp32 matmul
already runs multi-pass bf16 on the MXU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .environment import env


def compute_dtype():
    """The activation/matmul dtype the current policy dictates."""
    p = str(env().matmul_precision).lower()
    if p in ("bfloat16", "bf16"):
        return jnp.bfloat16
    if p == "auto":
        backend = jax.default_backend()
        if backend == "tpu":
            return jnp.bfloat16
        if backend != "cpu":
            # a backend nobody measured gets no silent numerics choice
            raise ValueError(
                f"TDL_MATMUL_PRECISION=auto knows the 'tpu' (bf16 AMP) and "
                f"'cpu' (fp32) backends, not {backend!r} — set the policy "
                f"explicitly (bfloat16 | float32)")
    return jnp.float32


def amp_enabled(model_dtype=jnp.float32) -> bool:
    """AMP is active only for fp32 models (an explicitly-bf16 or fp64 model
    already states its own policy)."""
    return compute_dtype() == jnp.bfloat16 and model_dtype == jnp.float32


def cast_floating(tree, dtype):
    """Cast every floating leaf of a pytree (ints/bools untouched)."""

    def c(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(c, tree)


def cast_input(x, dtype):
    """Cast one (possibly-None) array if floating."""
    if x is None:
        return None
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return jnp.asarray(x).astype(dtype)
    return x
