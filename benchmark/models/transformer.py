"""The ``transformer`` family: a configuration file's ``model`` block to the
program's own ``TransformerConfig`` and to weights made on the device."""

from __future__ import annotations


def build_config(config: dict, *, on_tpu: bool, causal=None, max_len=None):
    """``models.transformer.TransformerConfig`` as the cell runs it."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    kw = dict(config["model"])
    if causal is not None:
        kw["causal"] = causal
    if max_len is not None:
        kw["max_len"] = max_len
    # off the chip "auto" picks the dense path; a rehearsal names the kernel
    # so the flash route still runs (interpreted), as chip_smoke.py does
    kw["attn_impl"] = "auto" if on_tpu else "flash"
    return TransformerConfig(**kw)


def make_init(cfg):
    """The function of the KEY that makes every weight: jit it once, so the
    seed reaches the device as data and one program serves every seed."""
    from deeplearning4j_tpu.models.transformer import init_params

    return lambda key: init_params(key, cfg)
