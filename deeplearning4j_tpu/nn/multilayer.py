"""MultiLayerNetwork — sequential-stack runtime.

Reference: ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork`` (~4k LoC):
init() flattens params, fit() drives Solver→StochasticGradientDescent→
computeGradientAndScore→updater→step per minibatch (SURVEY §3.2).

TPU-native inversion (SURVEY §7.0): the entire boxed region
computeGradientAndScore→updater→step is ONE jit-compiled XLA executable with
donated param/updater buffers — per-layer op dispatch, JNI crossings, and the
Java workspace machinery all disappear into the compiled step. Params are a
pytree (shardable for DP/TP via jax.sharding); the reference's flat-vector
design survives as the ``params()``/``set_params()`` flat view used by
serialization and parameter averaging.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.dtypes import to_jax
from ..common.precision import amp_enabled, cast_floating, cast_input, compute_dtype
from ..monitoring import trace as _trace
from ..monitoring import watchdogs as _watchdogs
from ..data.dataset import DataSet
from ..data.iterators import (AsyncDataSetIterator, ArrayDataSetIterator,
                              DataSetIterator, ListDataSetIterator)
from ..eval.evaluation import Evaluation, RegressionEvaluation
from ..ndarray.ndarray import NDArray
from . import conf as conf_mod
from .conf import (
    BatchNormalization,
    GlobalPoolingLayer,
    GravesLSTM,
    LastTimeStep,
    LSTM,
    MultiLayerConfiguration,
)


def _mask_frozen(grads, frozen):
    """FrozenLayer semantics (TransferLearning C10): zero the gradients of
    frozen layers inside the compiled step."""
    if not frozen:
        return grads
    return {k: (jax.tree.map(jnp.zeros_like, v) if k in frozen else v)
            for k, v in grads.items()}


def _grad_normalize(grads, kind: Optional[str], threshold: float):
    """org.deeplearning4j.nn.conf.GradientNormalization semantics."""
    if kind is None:
        return grads
    if kind == "ClipElementWiseAbsoluteValue":
        return jax.tree.map(lambda g: jnp.clip(g, -threshold, threshold), grads)
    if kind == "ClipL2PerLayer":
        def clip_layer(layer_grads):
            flat = jax.tree.leaves(layer_grads)
            n = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in flat) + 1e-12)
            scale = jnp.minimum(1.0, threshold / n)
            return jax.tree.map(lambda g: g * scale, layer_grads)

        return {k: clip_layer(v) for k, v in grads.items()}
    if kind == "ClipL2PerParamType":
        return jax.tree.map(
            lambda g: g * jnp.minimum(1.0, threshold / jnp.sqrt(jnp.sum(jnp.square(g)) + 1e-12)), grads
        )
    if kind == "RenormalizeL2PerLayer":
        def renorm(layer_grads):
            flat = jax.tree.leaves(layer_grads)
            n = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in flat) + 1e-12)
            return jax.tree.map(lambda g: g / n, layer_grads)

        return {k: renorm(v) for k, v in grads.items()}
    raise ValueError(f"unknown gradient normalization {kind}")


class _LazyScoreMixin:
    """``score_`` accepts a device scalar and converts host-side on first
    READ: assigning the raw jit-output loss keeps fit() free of per-batch
    device round-trips (a read per batch would serialize host and device
    and forfeit async dispatch), while listeners/tests that read the score
    still see a plain float."""

    @property
    def score_(self):
        v = self.__dict__.get("_score_v", float("nan"))
        if not isinstance(v, float):
            v = float(v)
            self.__dict__["_score_v"] = v
        return v

    @score_.setter
    def score_(self, v):
        # device arrays are stored as-is (no sync); floats pass through
        self.__dict__["_score_v"] = v if not isinstance(v, (int, float)) else float(v)

    # -- on-device input ingest (narrow wire format) ------------------------
    # shared by MultiLayerNetwork and ComputationGraph: the installed fn runs
    # INSIDE the compiled step on the raw wire batch (uint8 NHWC → f32 NCHW
    # normalized); see data.normalizers.make_device_ingest

    _device_ingest = None

    def set_device_ingest(self, fn):
        """Install ``fn`` (raw wire batch → f32 model-layout batch, pure jnp)
        to run inside the compiled train/inference step. Pass None to remove.
        On ComputationGraph, a dict ``{input_name: fn}`` scopes ingests to
        specific inputs (others stage at model dtype, untouched); a dict is
        rejected here on single-input networks. Clears the jit cache — the
        ingest is traced into the executables."""
        if isinstance(fn, dict) and not hasattr(self.conf, "network_inputs"):
            raise TypeError(
                "a dict of ingests needs named inputs (ComputationGraph); "
                "MultiLayerNetwork takes a single callable")
        self._device_ingest = fn
        self._jit_cache.clear()
        return self

    def _ingest_fn(self, name=None):
        fn = self._device_ingest
        return fn.get(name) if isinstance(fn, dict) else fn

    def _ingest_input(self, name, x):
        f = self._ingest_fn(name)
        return x if f is None else jnp.asarray(f(x), self._dtype)

    def _wire_dtype(self, name=None):
        """Staging dtype for one input: None (keep the narrow wire dtype,
        e.g. uint8) when an on-device ingest will cast inside the step."""
        return None if self._ingest_fn(name) is not None else self._dtype

    # single-input forms (MultiLayerNetwork)
    def _ingest(self, x):
        return self._ingest_input(None, x)

    def _features_dtype(self):
        return self._wire_dtype()

    # -- shape bucketing (ISSUE 12) -----------------------------------------
    # shared by MultiLayerNetwork and ComputationGraph: ragged final batches
    # (and, opted in, variable sequence lengths) pad to the serving bucket
    # policy so they stop minting fresh XLA signatures; padding rows carry a
    # zero labels-mask, so loss/grads match the unpadded batch exactly (the
    # masked mean divides by the true count — common.bucketing docstring)

    _bucketing = None

    def set_bucketing(self, spec):
        """Install a :class:`~deeplearning4j_tpu.common.bucketing.BucketSpec`
        (or ``True`` for the defaults, ``None`` to disable) on the fit
        paths. ``last_batch_size`` keeps reporting the TRUE example count,
        never the padded one.

        Refuses nets with BatchNormalization: the labels mask keeps padded
        rows out of the LOSS, but BN's batch mean/variance are computed over
        every row of the padded batch — phantom zero rows would silently
        change the training dynamics vs unbucketed (no parity), so this
        raises instead."""
        from ..common.bucketing import BucketSpec

        if spec is True:
            spec = BucketSpec()
        if spec is not None and spec.batch:
            from .conf import BatchNormalization

            for name, layer in self._iter_layer_confs():
                if isinstance(layer, BatchNormalization):
                    raise ValueError(
                        "shape bucketing is unsupported with "
                        f"BatchNormalization (layer {name}): padded zero "
                        "rows would enter the batch mean/variance, silently "
                        "breaking parity with unbucketed training; train "
                        "without bucketing (ragged tails fall back to one "
                        "executable per distinct shape)")
        self._bucketing = spec
        return self

    def _iter_layer_confs(self):
        """(name, layer-conf) pairs — MultiLayerNetwork stores a layer list,
        ComputationGraph a node dict; bucketing guards need to scan both."""
        conf = getattr(self, "conf", None)
        layers = getattr(conf, "layers", None)
        if layers is not None:
            for i, layer in enumerate(layers):
                yield str(i), layer
            return
        nodes = getattr(conf, "nodes", None) or {}
        for name, node in nodes.items():
            layer = getattr(node, "layer", None)
            if layer is not None:
                yield name, layer

    def _bucket_dataset(self, ds):
        """(possibly padded ds, true example count or None when disabled)."""
        if self._bucketing is None:
            return ds, None
        from ..common.bucketing import pad_dataset

        return pad_dataset(ds, self._bucketing)


class MultiLayerNetwork(_LazyScoreMixin):
    def __init__(self, conf: MultiLayerConfiguration):
        # persistent executable cache on before the first jit builds, so a
        # respawned gang restores its step executables from disk
        from ..common import compile_cache

        compile_cache.enable()
        self.conf = conf
        self.params_: Dict[str, Any] = {}
        self.bn_state: Dict[str, Any] = {}
        self.updater_state: Dict[str, Any] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self.score_ = float("nan")
        self._rnn_state: Dict[str, Any] = {}  # streaming rnnTimeStep state
        self._input_types = conf.input_types()
        self._dtype = to_jax(conf.dtype)
        self._jit_cache: Dict[str, Any] = {}
        # optional placement hook for minibatch arrays (ParallelTrainer sets
        # this to a mesh-sharding device_put so the SAME fit paths — incl.
        # tbptt — run data-parallel)
        self._input_put = None

    def _put(self, arr, dtype=None):
        if arr is None:
            return None
        if isinstance(arr, jax.Array):
            # already staged (DevicePrefetchIterator): no host copy, no
            # re-upload — at most an on-device cast / sharding no-op
            a = arr if dtype is None or arr.dtype == dtype else arr.astype(dtype)
        else:
            a = jnp.asarray(arr, dtype) if dtype is not None else jnp.asarray(arr)
        return self._input_put(a) if self._input_put is not None else a

    # ------------------------------------------------------------------ init

    def init(self) -> "MultiLayerNetwork":
        """Allocate parameters (MultiLayerNetwork.init(): one flat buffer in
        the reference; a pytree here, flat view via params())."""
        key = jax.random.key(self.conf.seed)
        params = {}
        bn_state = {}
        for i, layer in enumerate(self.conf.layers):
            key, sub = jax.random.split(key)
            it = self._input_types[i]
            if layer.has_params():
                params[str(i)] = layer.init_params(sub, it, self._dtype)
            if isinstance(layer, BatchNormalization):
                bn_state[str(i)] = layer.init_state(it, self._dtype)
        self.params_ = params
        self.bn_state = bn_state
        self.updater_state = self.conf.updater.init(params)
        return self

    # -------------------------------------------------------------- forward

    def _forward(self, params, bn_state, x, *, training: bool, rng, fmask=None, rnn_states=None, collect=False):
        """Pure forward over all layers (feedForward); returns
        (activations|last, new_bn_state, new_rnn_states)."""
        new_bn = dict(bn_state)
        new_rnn = {}
        acts = []
        it_list = self._input_types
        h = x
        for i, layer in enumerate(self.conf.layers[:-1]):
            h = self._apply_layer(
                i, layer, params, new_bn, h, it_list[i], training, rng, fmask, rnn_states, new_rnn
            )
            if collect:
                acts.append(h)
        return (acts if collect else h), new_bn, new_rnn

    def _apply_layer(self, i, layer, params, new_bn, h, it, training, rng, fmask, rnn_states, new_rnn):
        si = str(i)
        if i in self.conf.preprocessors:
            h = self.conf.preprocessors[i].pre_process(h, it)
        p = params.get(si, {})
        sub = jax.random.fold_in(rng, i) if rng is not None else None
        if layer.weight_noise is not None and training:
            p = layer.weight_noise.apply(p, jax.random.fold_in(sub, 0x9015E)
                                         if sub is not None else None, training)
        if isinstance(layer, BatchNormalization):
            out, nb = layer.forward_bn(p, new_bn[si], h, it, training=training)
            new_bn[si] = nb
            return out
        if isinstance(layer, (LSTM, GravesLSTM)) and rnn_states is not None and si in rnn_states:
            h0, c0 = rnn_states[si]
            out, hT, cT = layer.forward_with_state(p, h, h0, c0)
            new_rnn[si] = (hT, cT)
            return out
        from .attention_layers import LearnedSelfAttentionLayer, RecurrentAttentionLayer, SelfAttentionLayer
        from .layers_tail import MaskLayer

        if isinstance(layer, (LastTimeStep, GlobalPoolingLayer, SelfAttentionLayer,
                              LearnedSelfAttentionLayer, RecurrentAttentionLayer,
                              MaskLayer)):
            return layer.forward(p, h, it, training=training, rng=sub, mask=fmask)
        return layer.forward(p, h, it, training=training, rng=sub)

    def _loss_fn(self, params, bn_state, x, y, fmask, lmask, rng, training: bool, rnn_states=None):
        h, new_bn, new_rnn = self._forward(
            params, bn_state, x, training=training, rng=rng, fmask=fmask, rnn_states=rnn_states
        )
        out_layer = self.conf.layers[-1]
        i = len(self.conf.layers) - 1
        it = self._input_types[i]
        if i in self.conf.preprocessors:
            h = self.conf.preprocessors[i].pre_process(h, it)
        p = params.get(str(i), {})
        sub = jax.random.fold_in(rng, i) if rng is not None else None
        loss = out_layer.compute_loss(p, h, y, it, training=training, rng=sub, mask=lmask)
        # L1/L2 regularization (BaseLayer.calcRegularizationScore — part of score)
        reg = 0.0
        for j, layer in enumerate(self.conf.layers):
            pj = params.get(str(j))
            if not pj:
                continue
            if layer.l2 > 0.0:
                reg = reg + layer.l2 * 0.5 * sum(jnp.sum(jnp.square(w)) for k, w in pj.items() if k != "b")
            if layer.l1 > 0.0:
                reg = reg + layer.l1 * sum(jnp.sum(jnp.abs(w)) for k, w in pj.items() if k != "b")
        return loss + reg, (new_bn, new_rnn)

    # ------------------------------------------------------------- train step

    def _step_body(self):
        """The raw (unjitted) train step — jitted by ``_train_step_fn`` and
        scanned by ``_train_scan_fn``."""
        # AMP (TDL_MATMUL_PRECISION=bfloat16): forward/backward in bf16 off a
        # cast-on-entry copy; masters/grads/updater stay fp32 (the entry cast's
        # transpose re-accumulates grads in fp32). Cache keyed on the resolved
        # policy so env().set("matmul_precision", ...) mid-run takes effect.
        amp = amp_enabled(self._dtype)
        cdt = compute_dtype()
        updater = self.conf.updater
        gn, gnt = self.conf.gradient_normalization, self.conf.gradient_normalization_threshold

        frozen = {str(i) for i, l in enumerate(self.conf.layers) if l.frozen}

        def step(params, upd_state, bn_state, iteration, epoch, x, y, fmask, lmask, rng):
            def lossf(p):
                pc = cast_floating(p, cdt) if amp else p
                xi = self._ingest(x)  # on-device: cast/layout/normalize
                xc = cast_input(xi, cdt) if amp else xi
                return self._loss_fn(pc, bn_state, xc, y, fmask, lmask, rng, True)

            (loss, (new_bn, _)), grads = jax.value_and_grad(lossf, has_aux=True)(params)
            grads = _mask_frozen(grads, frozen)
            grads = _grad_normalize(grads, gn, gnt)
            updates, new_upd = updater.apply(grads, upd_state, params, iteration, epoch)
            new_params = jax.tree.map(lambda p, u: p - u, params, updates)
            new_params = self._apply_constraints(new_params)
            return new_params, new_upd, new_bn, loss

        return step, amp

    def _train_step_fn(self):
        """Build/jit-cache THE train step: grads+updater+apply in one XLA
        program with donated state (§3.2 'TPU equivalent' note)."""
        amp = amp_enabled(self._dtype)
        cache_key = ("train", amp)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        step, _ = self._step_body()
        jitted = jax.jit(step, donate_argnums=(0, 1, 2))
        from ..common.debug import buffers_debug_enabled, donation_guard

        if buffers_debug_enabled():  # SURVEY §5.2: donation-misuse check
            jitted = donation_guard(jitted, (0, 1, 2))
        self._jit_cache[cache_key] = jitted
        return jitted

    def _tbptt_step_body(self):
        """The single-segment tbptt update, scanned over segments by
        ``_tbptt_scan_fn``."""
        amp = amp_enabled(self._dtype)
        cdt = compute_dtype()
        updater = self.conf.updater
        gn, gnt = self.conf.gradient_normalization, self.conf.gradient_normalization_threshold
        frozen = {str(i) for i, l in enumerate(self.conf.layers) if l.frozen}

        def step(params, upd_state, bn_state, rnn_states, iteration, epoch, x, y, fmask, lmask, rng):
            def loss_with_states(p):
                pc = cast_floating(p, cdt) if amp else p
                xi = self._ingest(x)
                xc = cast_input(xi, cdt) if amp else xi
                return self._loss_fn(pc, bn_state, xc, y, fmask, lmask, rng, True, rnn_states)

            (loss, (new_bn, new_rnn)), grads = jax.value_and_grad(loss_with_states, has_aux=True)(params)
            grads = _mask_frozen(grads, frozen)
            grads = _grad_normalize(grads, gn, gnt)
            updates, new_upd = updater.apply(grads, upd_state, params, iteration, epoch)
            new_params = jax.tree.map(lambda p, u: p - u, params, updates)
            new_params = self._apply_constraints(new_params)
            # stop grads flowing across segments (tBPTT semantics)
            new_rnn = jax.tree.map(jax.lax.stop_gradient, new_rnn)
            return new_params, new_upd, new_bn, new_rnn, loss

        return step, amp

    def _tbptt_scan_fn(self, has_fmask: bool):
        """ALL tbptt segments of one minibatch in ONE XLA executable: a
        lax.scan over the segment axis carrying (params, updater, bn, rnn
        state). One dispatch + one host sync per fit instead of one per
        segment: short segments are dispatch-bound, not math-bound."""
        amp = amp_enabled(self._dtype)
        cache_key = ("tbptt_scan", amp, has_fmask)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        step, _ = self._tbptt_step_body()

        def scan_fit(params, upd_state, bn_state, rnn_states, iteration, epoch,
                     xs, ys, fms, lms, rng):
            def body(carry, seg):
                params, upd, bn, rnn = carry
                if has_fmask:
                    x, y, fm, lm = seg
                else:
                    x, y, lm = seg
                    fm = None
                params, upd, bn, rnn, loss = step(
                    params, upd, bn, rnn, iteration, epoch, x, y, fm, lm, rng)
                return (params, upd, bn, rnn), loss

            segs = (xs, ys, fms, lms) if has_fmask else (xs, ys, lms)
            (params, upd_state, bn_state, _), losses = jax.lax.scan(
                body, (params, upd_state, bn_state, rnn_states), segs)
            return params, upd_state, bn_state, losses

        jitted = jax.jit(scan_fit, donate_argnums=(0, 1, 2))
        self._jit_cache[cache_key] = jitted
        return jitted

    def _apply_constraints(self, params):
        """Post-update constraint projection (BaseConstraint.applyConstraint
        placement) — runs inside the compiled step."""
        from .constraints import apply_constraints

        out = dict(params)
        for i, layer in enumerate(self.conf.layers):
            si = str(i)
            if layer.constraints and si in out:
                out[si] = apply_constraints(out[si], layer.constraints)
        return out

    # ------------------------------------------------------------------- fit

    def fit(self, data, labels=None, epochs: int = 1, batch_size: Optional[int] = None):
        """fit(DataSetIterator) | fit(DataSet) | fit(features, labels)."""
        if isinstance(data, DataSetIterator):
            it = data
        elif isinstance(data, DataSet):
            it = ListDataSetIterator([data])
        else:
            f = data.numpy() if hasattr(data, "numpy") else np.asarray(data)  # host-ok: fit(features, labels) batches/shuffles host-side
            l = labels.numpy() if hasattr(labels, "numpy") else np.asarray(labels)  # host-ok: see above
            it = ArrayDataSetIterator(f, l, batch_size or f.shape[0])
        try:
            for _ in range(epochs):
                for ds in it:
                    self._fit_batch(ds)
                self.epoch += 1
                for lst in self.listeners:
                    if hasattr(lst, "on_epoch_end"):
                        lst.on_epoch_end(self)
        finally:
            # async prefetch wrappers join their worker here, so an exception
            # mid-epoch can't leak the thread (or the ETL worker PROCESSES a
            # restart-safe base owns) until GC
            if isinstance(it, AsyncDataSetIterator):
                it.close()
        return self

    def _train_scan_fn(self, has_fmask: bool, has_lmask: bool):
        """K whole train steps in ONE executable (generalization of the
        tbptt segment fusion to any model — see ComputationGraph.fit_scan)."""
        amp = amp_enabled(self._dtype)
        cache_key = ("train_scan", amp, has_fmask, has_lmask)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        step, _ = self._step_body()

        def scan_fit(params, upd_state, bn_state, iteration, epoch, xs, ys,
                     fms, lms, rng):
            def body(carry, seg):
                params, upd, bn, it = carry
                x, y = seg[0], seg[1]
                k = 2
                fm = seg[k] if has_fmask else None
                k += 1 if has_fmask else 0
                lm = seg[k] if has_lmask else None
                params, upd, bn, loss = step(
                    params, upd, bn, it, epoch, x, y, fm, lm,
                    jax.random.fold_in(rng, it))
                return (params, upd, bn, it + 1), loss

            segs = tuple(s for s, keep in
                         ((xs, True), (ys, True), (fms, has_fmask), (lms, has_lmask))
                         if keep)
            (params, upd_state, bn_state, _), losses = jax.lax.scan(
                body, (params, upd_state, bn_state, iteration), segs)
            return params, upd_state, bn_state, losses

        self._jit_cache[cache_key] = jax.jit(scan_fit, donate_argnums=(0, 1, 2))
        return self._jit_cache[cache_key]

    def fit_scan(self, datasets) -> np.ndarray:
        """Fit a list of equal-shaped DataSets as ONE compiled dispatch;
        returns per-step losses. Not available on the tbptt path (that
        already scan-fuses within each batch)."""
        if self.conf.backprop_type == "TruncatedBPTT" and self.conf.tbptt_fwd_length > 0:
            raise ValueError("fit_scan: use fit() — tbptt already scan-fuses")
        datasets = list(datasets)
        if not datasets:
            return np.zeros(0, np.float32)
        has_fm = datasets[0].features_mask is not None
        has_lm = datasets[0].labels_mask is not None
        for ds in datasets[1:]:
            if (ds.features_mask is not None) != has_fm or \
                    (ds.labels_mask is not None) != has_lm:
                raise ValueError("fit_scan: all datasets must agree on "
                                 "features/labels masks")
        xs = jnp.stack([self._put(ds.features, self._features_dtype()) for ds in datasets])
        ys = jnp.stack([self._put(ds.labels) for ds in datasets])
        fms = (jnp.stack([self._put(ds.features_mask) for ds in datasets])
               if has_fm else None)
        lms = (jnp.stack([self._put(ds.labels_mask) for ds in datasets])
               if has_lm else None)
        scan_fit = self._train_scan_fn(has_fm, has_lm)
        # per-STEP batch (iteration advances by K, so rate listeners multiply
        # by their iteration delta — same contract as the _fit_batch path)
        self.last_batch_size = int(xs.shape[1])
        if _watchdogs.active():
            _watchdogs.note_step()
            _watchdogs.note_signature(
                "MultiLayerNetwork.train_scan",
                _watchdogs.signature_of(xs, ys, fms, lms))
        rng = jax.random.key(self.conf.seed ^ 0x5EED)
        self.params_, self.updater_state, self.bn_state, losses = scan_fit(
            self.params_, self.updater_state, self.bn_state,
            jnp.asarray(self.iteration, jnp.int32),
            jnp.asarray(self.epoch, jnp.int32), xs, ys, fms, lms, rng)
        self.iteration += len(datasets)
        self.score_ = losses[-1]  # lazy
        for lst in self.listeners:
            if hasattr(lst, "iteration_done"):
                lst.iteration_done(self, self.iteration, self.epoch)
        return losses

    def _fit_batch(self, ds: DataSet, true_examples: Optional[int] = None):
        if true_examples is None:
            ds, true_examples = self._bucket_dataset(ds)
        if self.conf.backprop_type == "TruncatedBPTT" and self.conf.tbptt_fwd_length > 0:
            self._fit_tbptt(ds, true_examples)
            return
        step = self._train_step_fn()
        rng = jax.random.fold_in(jax.random.key(self.conf.seed ^ 0x5EED), self.iteration)
        x = self._put(ds.features, self._features_dtype())
        y = self._put(ds.labels)
        fmask = self._put(ds.features_mask)
        lmask = self._put(ds.labels_mask)
        # the TRUE count when bucketing padded this batch — samples/sec
        # listeners must never count phantom rows (ISSUE 12 satellite)
        self.last_batch_size = (true_examples if true_examples is not None
                                else int(x.shape[0]))
        if _watchdogs.active():  # recompile watchdog: shape-churn detection
            _watchdogs.note_step()
            _watchdogs.note_signature(
                "MultiLayerNetwork.train_step",
                _watchdogs.signature_of(x, y, fmask, lmask))
        # step span (chrome-trace event host-side + XProf step boundary)
        # only when a trace profiler is attached; no-op context otherwise
        with (_trace.step_span(self.iteration)
              if _trace.get_trace_profiler() is not None
              else contextlib.nullcontext()):
            self.params_, self.updater_state, self.bn_state, loss = step(
                self.params_, self.updater_state, self.bn_state,
                jnp.asarray(self.iteration, jnp.int32), jnp.asarray(self.epoch, jnp.int32),
                x, y, fmask, lmask, rng,
            )
        self.score_ = loss  # lazy: syncs only when read
        self.iteration += 1
        for lst in self.listeners:
            if hasattr(lst, "iteration_done"):
                lst.iteration_done(self, self.iteration, self.epoch)

    def _fit_tbptt(self, ds: DataSet, true_examples: Optional[int] = None):
        """Truncated BPTT (MultiLayerNetwork fitHelper tbptt path): split the
        time axis into fwdLen segments; carry LSTM state across segments with
        stop-gradient between them.

        The WHOLE minibatch moves host→device ONCE (padded to a segment
        multiple) and segments are device-side slices: one transfer and no
        per-segment host round trip."""
        fwd = self.conf.tbptt_fwd_length

        def stage(a, dtype=None):
            """Keep numpy host-side (padding/segmentation before ONE bulk
            transfer) and device arrays device-side (a DevicePrefetchIterator
            batch must not round-trip d2h→h2d — pad/segment run as jnp ops)."""
            if isinstance(a, jax.Array):
                return a if dtype is None or a.dtype == dtype else a.astype(dtype)
            return np.asarray(a, dtype) if dtype is not None else np.asarray(a)  # host-ok: numpy path; device arrays handled above

        def xp(a):
            return jnp if isinstance(a, jax.Array) else np

        def pad_tail(a, pad):
            return xp(a).pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])

        x_all = stage(ds.features)
        y_all = stage(ds.labels)
        T = x_all.shape[-1]
        B = x_all.shape[0]
        rnn_states = self._zero_rnn_states(B)
        lm_all = (stage(ds.labels_mask, np.float32) if ds.labels_mask is not None
                  else np.ones((B, T), np.float32))
        if lm_all.ndim == 1:
            # a per-example [B] mask (batch bucketing pads rows with mask 0):
            # broadcast to the [B, T] per-timestep form this path segments —
            # padded rows mask out every timestep, real rows keep all of them
            lm_all = (lm_all[:, None] * xp(lm_all).ones((1, T), np.float32))
        fm_all = None if ds.features_mask is None else stage(ds.features_mask, np.float32)
        pad = (-T) % fwd
        if pad:
            # pad the tail ONCE to a fwd multiple so ONE executable serves all
            # segments (static shapes — §7.2 hard part #3); padded steps are
            # masked out ON TOP of any user mask
            x_all = pad_tail(x_all, pad)
            y_all = pad_tail(y_all, pad)
            lm_all = pad_tail(lm_all, pad)
            if fm_all is not None:
                fm_all = pad_tail(fm_all, pad)
        S = x_all.shape[-1] // fwd
        # per-segment unmasked-timestep weights; stays device-side (lazy) for
        # a device-resident mask, numpy for the host path
        seg_weights = xp(lm_all).moveaxis(
            lm_all.reshape(*lm_all.shape[:-1], S, fwd), -2, 0
        ).reshape(S, -1).sum(axis=1).astype(np.float32)

        def to_segs(a):
            """[..., S*fwd] → [S, ..., fwd] device-side."""
            segs = a.reshape(*a.shape[:-1], S, fwd)
            return jnp.moveaxis(segs, -2, 0)

        xj = to_segs(self._put(x_all, self._dtype))
        yj = to_segs(self._put(y_all))
        lmj = to_segs(self._put(lm_all))
        fmj = None if fm_all is None else to_segs(self._put(fm_all))
        rng = jax.random.fold_in(jax.random.key(self.conf.seed ^ 0x5EED), self.iteration)
        self.last_batch_size = true_examples if true_examples is not None else B
        if _watchdogs.active():
            _watchdogs.note_step()
            _watchdogs.note_signature(
                "MultiLayerNetwork.tbptt_step",
                _watchdogs.signature_of(xj, yj, fmj, lmj))
        scan_fit = self._tbptt_scan_fn(fmj is not None)
        args = (self.params_, self.updater_state, self.bn_state, rnn_states,
                jnp.asarray(self.iteration, jnp.int32), jnp.asarray(self.epoch, jnp.int32),
                xj, yj)
        if fmj is not None:
            self.params_, self.updater_state, self.bn_state, losses = scan_fit(
                *args, fmj, lmj, rng)
        else:
            self.params_, self.updater_state, self.bn_state, losses = scan_fit(
                *args, None, lmj, rng)
        # fit-wide score = unmasked-timestep-weighted mean over segments (the
        # reference reports one score per fit call, not per tbptt segment);
        # computed device-side, synced lazily on first score_ read
        if isinstance(seg_weights, jax.Array):
            # device-resident mask: keep the whole score computation lazy
            # (an eager float() here would sync every prefetched fit)
            wt = seg_weights.sum()
            self.score_ = jnp.where(
                wt > 0, (losses * seg_weights).sum() / jnp.maximum(wt, 1e-12),
                losses[-1])
        else:
            weight_total = float(seg_weights.sum())
            if weight_total > 0:
                self.score_ = (losses * jnp.asarray(seg_weights)).sum() / weight_total
            else:
                self.score_ = losses[-1]
        self.iteration += 1
        for lst in self.listeners:
            if hasattr(lst, "iteration_done"):
                lst.iteration_done(self, self.iteration, self.epoch)

    def _zero_rnn_states(self, batch: int):
        states = {}
        for i, layer in enumerate(self.conf.layers):
            if isinstance(layer, (LSTM, GravesLSTM)):
                H = layer.n_out
                states[str(i)] = (
                    jnp.zeros((batch, H), self._dtype),
                    jnp.zeros((batch, H), self._dtype),
                )
        return states

    # --------------------------------------------------------------- output

    def _head_forward(self, params, h):
        """Final layer (preprocessor + forward) applied to the last hidden
        state — shared by output()/export and feed_forward()."""
        i = len(self.conf.layers) - 1
        layer = self.conf.layers[i]
        it = self._input_types[i]
        if i in self.conf.preprocessors:
            h = self.conf.preprocessors[i].pre_process(h, it)
        return layer.forward(params.get(str(i), {}), h, it, training=False, rng=None)

    def _inference_fn(self):
        """The pure inference forward fwd(params, bn_state, x) — single
        source of truth for output() and the compiled artifact export."""

        def fwd(params, bn_state, x):
            x = self._ingest(x)
            h, _, _ = self._forward(params, bn_state, x, training=False, rng=None)
            return self._head_forward(params, h)

        return fwd

    def output(self, x, training: bool = False) -> NDArray:
        """Forward to final layer activations (MultiLayerNetwork.output)."""
        if "output" not in self._jit_cache:
            self._jit_cache["output"] = jax.jit(self._inference_fn())  # donate-ok: read-only inference; params must survive the call
        xj = jnp.asarray(x.numpy() if hasattr(x, "numpy") else x,
                         self._features_dtype())
        return NDArray(self._jit_cache["output"](self.params_, self.bn_state, xj))

    def feed_forward(self, x) -> List[NDArray]:
        """All layer activations (MultiLayerNetwork.feedForward)."""
        xj = self._ingest(jnp.asarray(x.numpy() if hasattr(x, "numpy") else x,
                                      self._features_dtype()))
        acts, _, _ = self._forward(self.params_, self.bn_state, xj, training=False, rng=None, collect=True)
        out = self._head_forward(self.params_, acts[-1] if acts else xj)
        return [NDArray(a) for a in acts] + [NDArray(out)]

    def score(self, ds: Optional[DataSet] = None) -> float:
        """Score = loss on dataset (Model.score)."""
        if ds is None:
            return self.score_
        x = self._ingest(jnp.asarray(ds.features, self._features_dtype()))
        y = jnp.asarray(ds.labels)
        fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        loss, _ = self._loss_fn(self.params_, self.bn_state, x, y, fmask, lmask, None, False)
        return float(loss)

    # ----------------------------------------------------------- rnn streaming

    def rnn_time_step(self, x) -> NDArray:
        """Streaming inference with persistent hidden state
        (MultiLayerNetwork.rnnTimeStep)."""
        xj = jnp.asarray(x.numpy() if hasattr(x, "numpy") else x, self._dtype)
        if xj.ndim == 2:
            xj = xj[:, :, None]  # single timestep
        B = xj.shape[0]
        if not self._rnn_state:
            self._rnn_state = self._zero_rnn_states(B)
        if "rnn_step" not in self._jit_cache:
            def fwd(params, bn_state, rnn_states, x):
                new_rnn = {}
                h = x
                for i, layer in enumerate(self.conf.layers[:-1]):
                    h = self._apply_layer(
                        i, layer, params, dict(bn_state), h, self._input_types[i], False, None, None,
                        rnn_states, new_rnn,
                    )
                i = len(self.conf.layers) - 1
                layer = self.conf.layers[i]
                it = self._input_types[i]
                if i in self.conf.preprocessors:
                    h = self.conf.preprocessors[i].pre_process(h, it)
                out = layer.forward(params.get(str(i), {}), h, it, training=False, rng=None)
                return out, new_rnn

            self._jit_cache["rnn_step"] = jax.jit(fwd)  # donate-ok: streaming inference; params/rnn state are reused across calls
        out, self._rnn_state = self._jit_cache["rnn_step"](self.params_, self.bn_state, self._rnn_state, xj)
        return NDArray(out)

    def rnn_clear_previous_state(self):
        self._rnn_state = {}

    # ------------------------------------------------------------- evaluation

    def evaluate(self, iterator: DataSetIterator) -> Evaluation:
        ev = Evaluation()
        for ds in iterator:
            preds = self.output(ds.features)
            ev.eval(ds.labels, preds.numpy(), mask=ds.labels_mask)
        return ev

    def evaluate_regression(self, iterator: DataSetIterator) -> RegressionEvaluation:
        ev = RegressionEvaluation()
        for ds in iterator:
            preds = self.output(ds.features)
            ev.eval(ds.labels, preds.numpy(), mask=ds.labels_mask)
        return ev

    # --------------------------------------------------------- params flat view

    def _param_entries(self):
        for i in sorted(self.params_, key=int):
            for name in sorted(self.params_[i]):
                yield i, name, self.params_[i][name]

    def params(self) -> NDArray:
        """Flat 1-D view of all parameters (deterministic order), parity with
        MultiLayerNetwork.params() flat buffer."""
        chunks = [np.asarray(w).reshape(-1) for _, _, w in self._param_entries()]  # host-ok: params() export is an intentional d2h
        return NDArray(jnp.concatenate([jnp.asarray(c) for c in chunks]) if chunks else jnp.zeros((0,)))

    def num_params(self) -> int:
        return sum(int(np.prod(w.shape)) for _, _, w in self._param_entries())

    def set_params(self, flat) -> None:
        arr = np.asarray(flat.numpy() if hasattr(flat, "numpy") else flat).reshape(-1)  # host-ok: set_params ingests user input
        expected = self.num_params()
        if arr.size != expected:
            raise ValueError(f"param vector length {arr.size} != model numParams {expected}")
        off = 0
        new = {k: dict(v) for k, v in self.params_.items()}
        for i, name, w in self._param_entries():
            n = int(np.prod(w.shape))
            new[i][name] = jnp.asarray(arr[off : off + n].reshape(w.shape), w.dtype)
            off += n
        self.params_ = new

    setParams = set_params

    def export(self, path: str, example_input) -> None:
        """Compiled-artifact export: StableHLO module + weights zip that
        reloads and runs WITHOUT this class (serde.compiled.load_compiled)
        — the reference's C++ GraphExecutioner deployment path (SURVEY §2.9
        N11/N12)."""
        from ..serde.compiled import export_multilayer

        export_multilayer(self, path, example_input)

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)

    setListeners = add_listeners

    def clone(self) -> "MultiLayerNetwork":
        # deep-copy buffers: the train step donates state, so replicas must
        # not alias (a donated buffer is deleted under every alias)
        m = MultiLayerNetwork(self.conf)
        m.init()
        m.params_ = jax.tree.map(jnp.copy, self.params_)
        m.bn_state = jax.tree.map(jnp.copy, self.bn_state)
        m.updater_state = jax.tree.map(jnp.copy, self.updater_state)
        return m
