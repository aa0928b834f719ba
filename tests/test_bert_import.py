"""BERT checkpoint import (VERDICT r1 Missing #1, SURVEY §2.2 J14):
HF weights → transformer params, golden-output verified, fine-tunable
under dp sharding. Uses a randomly-initialized local HF model — zero
network, same code path as a downloaded checkpoint."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deeplearning4j_tpu.models.bert_import import (
    config_from_hf,
    import_hf_bert,
    params_from_state_dict,
)
from deeplearning4j_tpu.models.transformer import forward, loss_fn, make_train_step


def _small_hf_bert(seed=0):
    torch.manual_seed(seed)
    cfg = transformers.BertConfig(
        vocab_size=120, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=48, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        hidden_act="gelu",
    )
    return transformers.BertForMaskedLM(cfg).eval()


def test_import_forward_matches_hf_golden():
    model = _small_hf_bert()
    params, cfg = import_hf_bert(model)
    assert cfg.norm_position == "post" and not cfg.gelu_approximate

    rs = np.random.RandomState(0)
    tokens = rs.randint(0, 120, (3, 16))
    segments = np.zeros((3, 16), np.int64)

    with torch.no_grad():
        golden = model(input_ids=torch.tensor(tokens),
                       token_type_ids=torch.tensor(segments)).logits.numpy()

    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg,
                              segments=jnp.asarray(segments, jnp.int32),
                              train=False))
    assert ours.shape == golden.shape
    np.testing.assert_allclose(ours, golden, atol=1e-3, rtol=1e-3)


def test_import_respects_attention_mask():
    model = _small_hf_bert(1)
    params, cfg = import_hf_bert(model)
    rs = np.random.RandomState(1)
    tokens = rs.randint(0, 120, (2, 12))
    mask = np.ones((2, 12), np.int64)
    mask[:, 8:] = 0

    with torch.no_grad():
        golden = model(input_ids=torch.tensor(tokens),
                       attention_mask=torch.tensor(mask)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg,
                              pad_mask=jnp.asarray(mask, jnp.float32),
                              train=False))
    # only compare unmasked positions (HF computes garbage at padded ones too,
    # but identical garbage is not contractual)
    np.testing.assert_allclose(ours[:, :8], golden[:, :8], atol=1e-3, rtol=1e-3)


def test_imported_model_fine_tunes_under_dp():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.models.transformer import batch_specs
    from deeplearning4j_tpu.nn.updaters import Adam

    model = _small_hf_bert(2)
    params, cfg = import_hf_bert(model)
    devices = np.array(jax.devices()[:4]).reshape(4, 1, 1)
    mesh = Mesh(devices, ("dp", "tp", "sp"))

    updater = Adam(1e-4)
    opt = updater.init(params)
    step = jax.jit(make_train_step(cfg, updater), donate_argnums=(0, 1))
    rs = np.random.RandomState(3)
    B, T = 8, 16
    bspec = batch_specs(cfg)
    batch = {
        "tokens": jnp.asarray(rs.randint(0, 120, (B, T)), jnp.int32),
        "labels": jnp.asarray(rs.randint(0, 120, (B, T)), jnp.int32),
        "weights": jnp.asarray((rs.rand(B, T) < 0.15).astype(np.float32)),
    }
    batch = {k: jax.device_put(v, NamedSharding(mesh, bspec[k])) for k, v in batch.items()}
    with jax.sharding.set_mesh(mesh):
        losses = []
        for i in range(4):
            params, opt, loss = step(params, opt, batch,
                                     jnp.asarray(i, jnp.int32), jax.random.key(i))
            losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # fine-tuning moves the loss


def test_plain_bertmodel_without_head_imports():
    torch.manual_seed(4)
    hf_cfg = transformers.BertConfig(
        vocab_size=80, hidden_size=16, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=32,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    base = transformers.BertModel(hf_cfg).eval()
    cfg = config_from_hf(hf_cfg)
    params = params_from_state_dict(base.state_dict(), cfg)
    out = forward(params, jnp.zeros((1, 8), jnp.int32), cfg, train=False)
    assert out.shape == (1, 8, 80)
    assert np.isfinite(np.asarray(out)).all()
