"""Replica targets for ServingPool tests (ISSUE 13).

Loaded BY PATH inside replica subprocesses
(``python -m deeplearning4j_tpu.serving.pool /path/pool_workers.py:fn``).
Deliberately jax-free: the pool mechanics under test (spawn, heartbeat,
respawn, routing, readiness, autoscaling) are model-agnostic, and a
numpy-only replica spawns in well under a second — which is what keeps the
replica-kill chaos test in the fast tier.

Knobs ride the pool's ``extra_env``:

- ``TDL_STUB_START_DELAY``  seconds to sleep before serving (warmup window)
- ``TDL_STUB_STEP_DELAY``   fake decode-step seconds (generative stub)
- ``TDL_STUB_MAX_NEW``      default max_new_tokens (generative stub)
- ``TDL_STUB_QUEUE``        admission queue size
"""

import os
import time

import numpy as np

from deeplearning4j_tpu.serving import StepAtDispatch


class DoubleModel:
    """output(x) = 2x — deterministic, numpy-only."""

    def output(self, x):
        return np.asarray(x, np.float32) * 2.0


class StubSession(StepAtDispatch):
    """FakeSession twin (see tests/test_serving_generative.py): emits
    ``prompt[-1]+1, +2, ...`` with a configurable per-step delay."""

    def __init__(self, slots=4, max_len=100_000, step_delay=0.0):
        self.slots = slots
        self.max_len = max_len
        self.step_delay = step_delay
        self.eos_id = None
        self._next = {}

    @property
    def free_slots(self):
        return self.slots - len(self._next)

    def admit(self, prompt, max_new_tokens):
        prompt = np.asarray(prompt)
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError("prompt too long for the cache")
        if len(self._next) >= self.slots:
            raise RuntimeError("no free decode slot")
        slot = min(set(range(self.slots)) - set(self._next))
        first = int(prompt[-1]) + 1
        self._next[slot] = first + 1
        return slot, first

    def step(self):
        if self.step_delay:
            time.sleep(self.step_delay)
        out = dict(self._next)
        self._next = {s: t + 1 for s, t in self._next.items()}
        return out

    def release(self, slot):
        del self._next[slot]


def _maybe_start_delay():
    delay = float(os.environ.get("TDL_STUB_START_DELAY", "0"))
    if delay:
        time.sleep(delay)


def stub_server():
    """Plain inference replica: POST [[...]] -> 2x."""
    from deeplearning4j_tpu.serving import JsonModelServer

    _maybe_start_delay()
    return JsonModelServer(
        DoubleModel(), port=0,
        max_queue=int(os.environ.get("TDL_STUB_QUEUE", "64")),
        warmup_input=np.zeros((1, 4), np.float32))


class ScaledModel:
    """output(x) = scale·x — the 'model version' is the scale factor."""

    def __init__(self, scale):
        self.scale = float(scale)

    def output(self, x):
        return np.asarray(x, np.float32) * self.scale


def swappable_server():
    """Versioned inference replica (ISSUE 14 swap tests): the model version
    rides ``TDL_MODEL_CKPT`` — a json file ``{"scale": k}`` (``{"fail":
    true}`` simulates a checkpoint the new build cannot load, the swap
    validation-failure path). No env = the historical 2x model."""
    import json as _json

    from deeplearning4j_tpu.serving import JsonModelServer

    _maybe_start_delay()
    ckpt = os.environ.get("TDL_MODEL_CKPT")
    scale = 2.0
    if ckpt:
        with open(ckpt) as f:
            doc = _json.load(f)
        if doc.get("fail"):
            raise RuntimeError(f"injected model-load failure from {ckpt}")
        scale = float(doc["scale"])
    return JsonModelServer(
        ScaledModel(scale), port=0,
        max_queue=int(os.environ.get("TDL_STUB_QUEUE", "64")),
        warmup_input=np.zeros((1, 4), np.float32))


def generative_stub_server():
    """Continuous-batching generative replica over the stub session."""
    from deeplearning4j_tpu.serving import JsonModelServer

    _maybe_start_delay()
    session = StubSession(
        slots=4, step_delay=float(os.environ.get("TDL_STUB_STEP_DELAY", "0")))
    return JsonModelServer(
        None, port=0, generative_session=session,
        default_max_new_tokens=int(os.environ.get("TDL_STUB_MAX_NEW", "8")),
        max_queue=int(os.environ.get("TDL_STUB_QUEUE", "64")),
        warmup_input=[1])
