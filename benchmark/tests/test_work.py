"""Required-work arithmetic against numbers worked by hand for bert-large
at B 16 x T 512 (hidden 1024, 24 layers, d_ff 4096, vocab 30522, 76 MLM
positions a sequence)."""

import pytest

from benchmark import work

BERT = {"d_model": 1024, "n_layers": 24, "d_ff": 4096, "vocab_size": 30522,
        "n_heads": 16}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_params_per_layer():
    # 4 x 1024^2 (QKV + output) + 2 x 1024 x 4096 (FFN)
    assert work.matmul_params_per_layer(1024, 4096) == 4_194_304 + 8_388_608


def test_train_flops_bert_large_b16_t512():
    tokens = 16 * 512
    blocks = 6 * 24 * 12_582_912 * tokens            # 14,843,406,974,976
    attn = 12 * 24 * 512 * 1024 * tokens             #  1,236,950,581,248
    head = 6 * (1024 * 1024 + 1024 * 30522) * 16 * 76  #    235,683,446,784
    assert (blocks, attn, head) == (14_843_406_974_976, 1_236_950_581_248,
                                    235_683_446_784)
    got = work.train_flops_per_step(BERT, batch=16, seq=512, head_positions=76)
    assert got == pytest.approx(16_316_041_003_008, rel=1e-12)


def test_causal_halves_attention_and_head_runs_everywhere():
    full = work.train_flops_per_step(BERT, batch=1, seq=512)
    causal = work.train_flops_per_step({**BERT, "causal": True}, batch=1, seq=512)
    assert full - causal == pytest.approx(0.5 * 12 * 24 * 512 * 1024 * 512)
    head = 6 * (1024 * 1024 + 1024 * 30522) * 512
    assert full == pytest.approx(6 * 24 * 12_582_912 * 512
                                 + 12 * 24 * 512 * 1024 * 512 + head)


@pytest.mark.parametrize("kind,flops,nbytes", [
    # BH 256, T 512, D 64, bf16: T*T*D*BH = 4,294,967,296 pairs
    ("fwd", 17_179_869_184, 4 * 16_777_216 + 524_288),
    ("dkv", 34_359_738_368, 6 * 16_777_216 + 1_048_576),
    ("dq", 25_769_803_776, 5 * 16_777_216 + 1_048_576),
])
def test_flash_work_bert_large(kind, flops, nbytes):
    assert work.flash_call_work(kind, bh=256, tq=512, tk=512, d=64,
                                causal=False) == (flops, nbytes)


def test_flash_forward_is_compute_bound_on_v5e():
    f, b = work.flash_call_work("fwd", bh=256, tq=512, tk=512, d=64, causal=False)
    assert f / 197e12 == pytest.approx(87.2e-6, rel=1e-3)
    assert b / 819e9 == pytest.approx(82.6e-6, rel=1e-3)
    assert work.least_seconds(f, b, V5E) == f / 197e12


@pytest.mark.parametrize("name,want", [
    ("tpu_custom_call:jvp__ bf16[256,512,64] f32[256,512,1]", ("fwd", 256, 512, 64)),
    ("tpu_custom_call:transpose_jvp___ bf16[256,512,64] bf16[256,512,64]", ("dkv", 256, 512, 64)),
    ("tpu_custom_call:transpose_jvp___ bf16[256,512,64]", ("dq", 256, 512, 64)),
    ("custom-call", None),
])
def test_classify_flash_call(name, want):
    assert work.classify_flash_call(name) == want
