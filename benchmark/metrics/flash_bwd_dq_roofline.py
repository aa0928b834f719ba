"""Roofline share of the flash backward dQ kernel: least time its shapes allow
(``work.flash_call_work`` against the chip's peaks) over its traced time."""

from benchmark import reduce


def read(obs):
    return reduce.flash_roofline(obs, "dq")
