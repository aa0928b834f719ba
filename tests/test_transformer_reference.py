"""The GPT-2 / BERT family against its plain float32 reference
(``benchmark/reference/transformer.py``, the second, deliberate definition of
the block), at a small float32 size on the CPU: the whole forward under both
norm positions, both masks and key padding, and causal configs SERVED through
the paged slot pool — the one ``_layer`` of ``models/transformer.py`` under
each of its ``attend``s.

2e-4 of the largest logit covers float32 accumulation in another order.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import transformer as ref
from deeplearning4j_tpu.models import transformer as tfm

TOL = 2e-4


def small_cfg(norm_position, causal, **kw):
    return tfm.TransformerConfig(
        vocab_size=101, max_len=64, d_model=32, n_heads=4, n_layers=3, d_ff=64,
        causal=causal, norm_position=norm_position, dropout=0.0,
        gelu_approximate=causal, param_dtype=jnp.float32,
        compute_dtype=jnp.float32, attn_impl="xla", **kw)


def ref_logits(params, tokens, cfg, pad_mask=None):
    model = dataclasses.asdict(cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            params, ref.hidden(params, tokens, model, pad_mask), model))


def _params(cfg):
    """Random weights, with biases and norm gains drawn too: at their initial
    0 and 1 a misplaced bias or norm would not show."""
    params = tfm.init_params(jax.random.key(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


@pytest.mark.parametrize("masked", [False, True], ids=["full", "key-padding"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("norm_position", ["pre", "post"])
def test_forward_matches_the_reference(norm_position, causal, masked):
    cfg = small_cfg(norm_position, causal)
    params = _params(cfg)
    rs = np.random.RandomState(7)
    tokens = jnp.asarray(rs.randint(1, cfg.vocab_size, (3, 24)), jnp.int32)
    pad_mask = None
    if masked:
        lengths = np.array([24, 17, 9])
        pad_mask = jnp.asarray(np.arange(24)[None, :] < lengths[:, None])
    got = np.asarray(tfm.forward(params, tokens, cfg, pad_mask=pad_mask))
    want = ref_logits(params, tokens, cfg, pad_mask)
    live = np.asarray(pad_mask) if masked else np.ones((3, 24), bool)
    # a padded QUERY row attends what the mask leaves it: only live rows count
    assert np.abs(got - want)[live].max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("norm_position", ["pre", "post"])
def test_served_tokens_are_the_references_largest_logits(norm_position):
    """Prompt prefill, then 8 decode steps through the paged pool: every
    served token is the reference's largest logit at its position (within
    the tolerance, should two logits lie that close)."""
    cfg = small_cfg(norm_position, True)
    params = _params(cfg)
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, cfg.vocab_size, n).tolist() for n in (5, 13)]
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=2, block_T=8)
    served = tfm.generate(params, prompts, 9, cfg, pool=pool)
    assert pool.decode_traces == 1
    for prompt, answer in zip(prompts, served):
        assert len(answer) == 9  # the prefill's token and 8 decode steps
        row = jnp.asarray([prompt + answer], jnp.int32)
        want = ref_logits(params, row, cfg)[0]
        for i, tok in enumerate(answer):
            at = want[len(prompt) + i - 1]
            assert at[tok] >= at.max() - TOL * np.abs(want).max(), (i, tok)


@pytest.mark.parametrize("norm_position", ["pre", "post"])
def test_remat_changes_memory_not_the_loss_or_its_gradient(norm_position):
    """``remat=True`` wraps each block in ``jax.checkpoint``: the training
    loss and its gradient are those of the plain config."""
    cfg = small_cfg(norm_position, norm_position == "pre")
    params = _params(cfg)
    rs = np.random.RandomState(13)
    batch = {"tokens": jnp.asarray(rs.randint(1, cfg.vocab_size, (2, 16)), jnp.int32),
             "labels": jnp.asarray(rs.randint(1, cfg.vocab_size, (2, 16)), jnp.int32)}

    def loss_and_grad(c):
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_fn(p, batch, c, jax.random.key(2), True)))(params)

    loss, grad = loss_and_grad(cfg)
    loss_r, grad_r = loss_and_grad(dataclasses.replace(cfg, remat=True))
    np.testing.assert_allclose(loss_r, loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grad_r), jax.tree.leaves(grad)):
        np.testing.assert_allclose(a, b, atol=1e-6)
