"""ComputationGraph — DAG runtime.

Reference: ``org.deeplearning4j.nn.graph.ComputationGraph`` (~4.8k LoC):
topo-sorted GraphVertex[] execution, multi-input/multi-output, flat params.
TPU-native: the whole DAG (all vertices, all output losses, updater) traces
into ONE jit-compiled step, same as MultiLayerNetwork.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.dtypes import to_jax
from ..common.precision import amp_enabled, cast_floating, cast_input, compute_dtype
from ..data.dataset import DataSet, MultiDataSet
from ..monitoring import trace as _trace
from ..monitoring import watchdogs as _watchdogs
from ..eval.evaluation import Evaluation
from ..ndarray.ndarray import NDArray
from .conf import BatchNormalization, GlobalPoolingLayer, LastTimeStep, LSTM, GravesLSTM
from .graph_conf import ComputationGraphConfiguration
from .multilayer import _grad_normalize, _mask_frozen, _LazyScoreMixin


class ComputationGraph(_LazyScoreMixin):
    def __init__(self, conf: ComputationGraphConfiguration):
        # persistent executable cache on before the first jit builds
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
        self._dtype = to_jax(conf.dtype)
        self._topo = conf.topo_order()
        self._types = conf.infer_types()  # output type per node
        self._in_types = self._compute_in_types()
        self._jit_cache: Dict[str, Any] = {}
        # on-device input ingest (narrow wire format): set_device_ingest /
        # _ingest_input / _wire_dtype come from _LazyScoreMixin. A plain
        # callable applies to EVERY network input; multi-input graphs pass a
        # dict keyed by input name so e.g. an image scaler never touches a
        # dense side-input.

    def _compute_in_types(self):
        """Input InputType per node AFTER its preprocessor."""
        types = dict(self.conf.input_types)
        types.update(self._types)
        in_types = {}
        for name in self._topo:
            node = self.conf.nodes[name]
            its = [types[i] for i in node.inputs]
            it = its[0] if its else None
            if node.preprocessor is not None:
                it = node.preprocessor.output_type(it)
            in_types[name] = it
        return in_types

    def init(self) -> "ComputationGraph":
        key = jax.random.key(self.conf.seed)
        for name in self._topo:
            node = self.conf.nodes[name]
            if node.layer is not None and node.layer.has_params():
                key, sub = jax.random.split(key)
                self.params_[name] = node.layer.init_params(sub, self._in_types[name], self._dtype)
            if node.vertex is not None and hasattr(node.vertex, "init_params"):
                # parameterized vertex (e.g. AttentionVertex)
                key, sub = jax.random.split(key)
                self.params_[name] = node.vertex.init_params(sub, self._dtype)
            if isinstance(node.layer, BatchNormalization):
                self.bn_state[name] = node.layer.init_state(self._in_types[name], self._dtype)
        self.updater_state = self.conf.updater.init(self.params_)
        return self

    # -------------------------------------------------------------- forward

    def _forward(self, params, bn_state, inputs: Dict[str, jnp.ndarray], *, training, rng, stop_at_loss=False,
                 labels: Optional[Dict[str, jnp.ndarray]] = None, lmasks=None, fmask=None):
        """Evaluate DAG. If labels given, returns (total_loss, new_bn); else
        returns ({output_name: activation}, new_bn)."""
        acts: Dict[str, jnp.ndarray] = dict(inputs)
        new_bn = dict(bn_state)
        total_loss = 0.0
        for idx, name in enumerate(self._topo):
            node = self.conf.nodes[name]
            xs = [acts[i] for i in node.inputs]
            if node.preprocessor is not None:
                xs = [node.preprocessor.pre_process(xs[0], None)] + xs[1:]
            sub = jax.random.fold_in(rng, idx) if rng is not None else None
            if node.vertex is not None:
                if hasattr(node.vertex, "init_params"):
                    acts[name] = node.vertex.apply(xs, params.get(name))
                else:
                    acts[name] = node.vertex.apply(xs)
                continue
            layer = node.layer
            p = params.get(name, {})
            if layer.weight_noise is not None and training:
                p = layer.weight_noise.apply(p, jax.random.fold_in(sub, 0x9015E)
                                             if sub is not None else None, training)
            it = self._in_types[name]
            is_output = name in self.conf.network_outputs and hasattr(layer, "compute_loss")
            if labels is not None and is_output:
                y = labels[name]
                lm = lmasks.get(name) if lmasks else None
                total_loss = total_loss + layer.compute_loss(p, xs[0], y, it, training=training, rng=sub, mask=lm)
                continue
            if isinstance(layer, BatchNormalization):
                out, nb = layer.forward_bn(p, new_bn[name], xs[0], it, training=training)
                new_bn[name] = nb
                acts[name] = out
            elif isinstance(layer, (LastTimeStep, GlobalPoolingLayer)):
                acts[name] = layer.forward(p, xs[0], it, training=training, rng=sub, mask=fmask)
            else:
                acts[name] = layer.forward(p, xs[0], it, training=training, rng=sub)
        if labels is not None:
            # L1/L2 regularization
            reg = 0.0
            for name, node in self.conf.nodes.items():
                pj = params.get(name)
                if not pj or node.layer is None:
                    continue
                if node.layer.l2 > 0.0:
                    reg = reg + node.layer.l2 * 0.5 * sum(
                        jnp.sum(jnp.square(w)) for k, w in pj.items() if k != "b"
                    )
                if node.layer.l1 > 0.0:
                    reg = reg + node.layer.l1 * sum(jnp.sum(jnp.abs(w)) for k, w in pj.items() if k != "b")
            return total_loss + reg, new_bn
        return {o: acts[o] for o in self.conf.network_outputs}, new_bn

    # ------------------------------------------------------------------- fit

    def _step_body(self):
        """The raw (unjitted) train step — jitted directly by
        ``_train_step_fn`` and scanned by ``_train_scan_fn``."""
        # AMP: bf16 compute off cast-on-entry params, fp32 masters/grads/loss
        # (see common/precision.py); cache keyed on the resolved policy
        amp = amp_enabled(self._dtype)
        cdt = compute_dtype()
        updater = self.conf.updater
        gn, gnt = self.conf.gradient_normalization, self.conf.gradient_normalization_threshold

        frozen = {name for name, node in self.conf.nodes.items()
                  if node.layer is not None and node.layer.frozen}

        def step(params, upd_state, bn_state, iteration, epoch, inputs, labels, lmasks, rng):
            def loss_fn(p):
                pc = cast_floating(p, cdt) if amp else p
                xi = {k: self._ingest_input(k, v) for k, v in inputs.items()}
                xc = {k: cast_input(v, cdt) for k, v in xi.items()} if amp else xi
                return self._forward(pc, bn_state, xc, training=True, rng=rng, labels=labels, lmasks=lmasks)

            (loss, new_bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            grads = _mask_frozen(grads, frozen)
            grads = _grad_normalize(grads, gn, gnt)
            updates, new_upd = updater.apply(grads, upd_state, params, iteration, epoch)
            new_params = jax.tree.map(lambda p, u: p - u, params, updates)
            new_params = self._apply_constraints(new_params)
            return new_params, new_upd, new_bn, loss

        return step, amp

    def _train_step_fn(self):
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

    def _train_scan_fn(self, has_lmasks: bool):
        """K train steps fused into ONE executable (lax.scan over a stacked
        leading batch axis) — the tbptt/w2v epoch-fusion pattern generalized
        to any model. Per-step dispatch cost (the binding term on
        high-latency links) collapses to one dispatch per K steps."""
        amp = amp_enabled(self._dtype)
        cache_key = ("train_scan", amp, has_lmasks)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        step, _ = self._step_body()

        def scan_fit(params, upd_state, bn_state, iteration, epoch, xs, ys, lms, rng):
            def body(carry, seg):
                params, upd, bn, it = carry
                if has_lmasks:
                    x, y, lm = seg
                else:
                    x, y = seg
                    lm = None
                params, upd, bn, loss = step(
                    params, upd, bn, it, epoch, x, y, lm,
                    jax.random.fold_in(rng, it))
                return (params, upd, bn, it + 1), loss

            segs = (xs, ys, lms) if has_lmasks else (xs, ys)
            (params, upd_state, bn_state, _), losses = jax.lax.scan(
                body, (params, upd_state, bn_state, iteration), segs)
            return params, upd_state, bn_state, losses

        self._jit_cache[cache_key] = jax.jit(scan_fit, donate_argnums=(0, 1, 2))
        return self._jit_cache[cache_key]

    def fit_scan(self, datasets) -> np.ndarray:
        """Fit a list of equal-shaped DataSets/MultiDataSets as ONE compiled
        dispatch (scan-fused steps). Returns the per-step losses. All
        batches transfer in bulk before the dispatch — no per-step host
        round trips (how w2v/tbptt already train; SURVEY §3.2)."""
        datasets = list(datasets)
        if not datasets:
            return np.zeros(0, np.float32)
        ins, lbs, lms = [], [], []
        for ds in datasets:
            if isinstance(ds, DataSet):
                ins.append(self._coerce_inputs([ds.features]))
                lbs.append(self._coerce_labels([ds.labels]))
                lms.append({self.conf.network_outputs[0]: jnp.asarray(ds.labels_mask)}
                           if ds.labels_mask is not None else None)
            else:
                ins.append(self._coerce_inputs(list(ds.features)))
                lbs.append(self._coerce_labels(list(ds.labels)))
                lms.append({n: jnp.asarray(m) for n, m in
                            zip(self.conf.network_outputs, ds.labels_masks)}
                           if getattr(ds, "labels_masks", None) else None)
        has_lm = lms[0] is not None
        if any((m is not None) != has_lm for m in lms):
            raise ValueError("fit_scan: all datasets must agree on label masks")
        stack = lambda seq: jax.tree.map(lambda *xs: jnp.stack(xs), *seq)  # noqa: E731
        xs, ys = stack(ins), stack(lbs)
        lm_s = stack(lms) if has_lm else None
        scan_fit = self._train_scan_fn(has_lm)
        first = next(iter(xs.values()))
        # per-STEP batch: iteration advances by K, rate listeners multiply
        # by their iteration delta (same contract as _fit_batch)
        self.last_batch_size = int(first.shape[1])
        if _watchdogs.active():
            _watchdogs.note_step()
            _watchdogs.note_signature(
                "ComputationGraph.train_scan",
                _watchdogs.signature_of(xs, ys, lm_s))
        rng = jax.random.key(self.conf.seed ^ 0x5EED)
        self.params_, self.updater_state, self.bn_state, losses = scan_fit(
            self.params_, self.updater_state, self.bn_state,
            jnp.asarray(self.iteration, jnp.int32),
            jnp.asarray(self.epoch, jnp.int32), xs, ys, lm_s, rng)
        self.iteration += len(datasets)
        self.score_ = losses[-1]  # lazy
        for lst in self.listeners:
            if hasattr(lst, "iteration_done"):
                lst.iteration_done(self, self.iteration, self.epoch)
        return losses

    def _apply_constraints(self, params):
        """Post-update constraint projection inside the compiled step (parity
        with MultiLayerNetwork; ADVICE r2: CG previously ignored constraints)."""
        from .constraints import apply_constraints

        out = dict(params)
        for name, node in self.conf.nodes.items():
            if node.layer is not None and node.layer.constraints and name in out:
                out[name] = apply_constraints(out[name], node.layer.constraints)
        return out

    def _coerce_inputs(self, features) -> Dict[str, jnp.ndarray]:
        # device-resident arrays pass straight through (no host round trip);
        # for inputs with an on-device ingest installed the wire dtype is
        # preserved so uint8 batches stay 4x narrower over the h2d link
        if isinstance(features, dict):
            return {k: jnp.asarray(v, self._wire_dtype(k))
                    for k, v in features.items()}
        if not isinstance(features, (list, tuple)):
            features = [features]
        return {
            name: jnp.asarray(f.numpy() if hasattr(f, "numpy") else f,
                              self._wire_dtype(name))
            for name, f in zip(self.conf.network_inputs, features)
        }

    def _coerce_labels(self, labels) -> Dict[str, jnp.ndarray]:
        out_layers = [n for n in self.conf.network_outputs]
        if isinstance(labels, dict):
            return {k: jnp.asarray(v) for k, v in labels.items()}
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        return {name: jnp.asarray(l.numpy() if hasattr(l, "numpy") else l) for name, l in zip(out_layers, labels)}

    def fit(self, data, labels=None, epochs: int = 1):
        """fit(DataSet/MultiDataSet/iterator) or fit(features, labels)."""
        try:
            for _ in range(epochs):
                if hasattr(data, "__iter__") and not isinstance(data, (DataSet, MultiDataSet, np.ndarray, list, tuple, dict)):
                    for ds in data:
                        self._fit_one(ds)
                elif isinstance(data, (DataSet, MultiDataSet)):
                    self._fit_one(data)
                else:
                    self._fit_batch(self._coerce_inputs(data), self._coerce_labels(labels), None)
                self.epoch += 1
        finally:
            # join async prefetch workers even when an epoch raises (thread
            # leak until GC otherwise; ETL bases also free their processes)
            from ..data.iterators import AsyncDataSetIterator

            if isinstance(data, AsyncDataSetIterator):
                data.close()
        return self

    def _fit_one(self, ds):
        true_n = None
        if self._bucketing is not None:
            # ISSUE 12: pad to the shared bucket policy BEFORE coercion so a
            # ragged final batch reuses the bucket's executable; the padded
            # rows carry a zero labels-mask (loss parity — common.bucketing)
            from ..common import bucketing as _bucketing_mod

            if isinstance(ds, DataSet):
                ds, true_n = _bucketing_mod.pad_dataset(ds, self._bucketing)
            else:
                ds, true_n = _bucketing_mod.pad_multidataset(
                    ds, self._bucketing)
        if isinstance(ds, DataSet):
            inputs = self._coerce_inputs([ds.features])
            labels = self._coerce_labels([ds.labels])
            lmasks = {self.conf.network_outputs[0]: jnp.asarray(ds.labels_mask)} if ds.labels_mask is not None else None
        else:
            inputs = self._coerce_inputs(list(ds.features))
            labels = self._coerce_labels(list(ds.labels))
            lmasks = (
                {n: jnp.asarray(m) for n, m in zip(self.conf.network_outputs, ds.labels_masks)}
                if ds.labels_masks
                else None
            )
        self._fit_batch(inputs, labels, lmasks, true_examples=true_n)

    def _fit_batch(self, inputs, labels, lmasks, true_examples=None):
        step = self._train_step_fn()
        rng = jax.random.fold_in(jax.random.key(self.conf.seed ^ 0x5EED), self.iteration)
        first = next(iter(inputs.values()))
        # TRUE count when bucketing padded this batch (ISSUE 12 satellite)
        self.last_batch_size = (true_examples if true_examples is not None
                                else int(first.shape[0]))
        if _watchdogs.active():  # recompile watchdog: shape-churn detection
            _watchdogs.note_step()
            _watchdogs.note_signature(
                "ComputationGraph.train_step",
                _watchdogs.signature_of(inputs, labels, lmasks))
        # step span (chrome-trace event host-side + XProf step boundary)
        # only when a trace profiler is attached; no-op context otherwise
        with (_trace.step_span(self.iteration)
              if _trace.get_trace_profiler() is not None
              else contextlib.nullcontext()):
            self.params_, self.updater_state, self.bn_state, loss = step(
                self.params_, self.updater_state, self.bn_state,
                jnp.asarray(self.iteration, jnp.int32), jnp.asarray(self.epoch, jnp.int32),
                inputs, labels, lmasks, rng,
            )
        self.score_ = loss  # lazy: syncs only when read
        self.iteration += 1
        for lst in self.listeners:
            if hasattr(lst, "iteration_done"):
                lst.iteration_done(self, self.iteration, self.epoch)

    # --------------------------------------------------------------- output

    def output(self, *features) -> List[NDArray]:
        if "output" not in self._jit_cache:
            def fwd(params, bn_state, inputs):
                inputs = {k: self._ingest_input(k, v) for k, v in inputs.items()}
                outs, _ = self._forward(params, bn_state, inputs, training=False, rng=None)
                return outs

            self._jit_cache["output"] = jax.jit(fwd)  # donate-ok: read-only inference; params must survive the call
        inputs = self._coerce_inputs(list(features) if len(features) > 1 else features[0])
        outs = self._jit_cache["output"](self.params_, self.bn_state, inputs)
        return [NDArray(outs[o]) for o in self.conf.network_outputs]

    def output_single(self, features) -> NDArray:
        return self.output(features)[0]

    def score(self, ds: Optional[DataSet] = None) -> float:
        if ds is None:
            return self.score_
        inputs = self._coerce_inputs([ds.features] if isinstance(ds, DataSet) else list(ds.features))
        inputs = {k: self._ingest_input(k, v) for k, v in inputs.items()}
        labels = self._coerce_labels([ds.labels] if isinstance(ds, DataSet) else list(ds.labels))
        loss, _ = self._forward(self.params_, self.bn_state, inputs, training=False, rng=None, labels=labels)
        return float(loss)

    def clone(self) -> "ComputationGraph":
        # deep-copy buffers: the train step donates state, so replicas must
        # not alias (a donated buffer is deleted under every alias)
        g = ComputationGraph(self.conf)
        g.init()
        g.params_ = jax.tree.map(jnp.copy, self.params_)
        g.bn_state = jax.tree.map(jnp.copy, self.bn_state)
        g.updater_state = jax.tree.map(jnp.copy, self.updater_state)
        return g

    def evaluate(self, iterator) -> Evaluation:
        ev = Evaluation()
        for ds in iterator:
            preds = self.output_single(ds.features)
            ev.eval(ds.labels, preds.numpy(), mask=ds.labels_mask)
        return ev

    # --------------------------------------------------------- params flat view

    def _param_entries(self):
        for name in self._topo:
            if name in self.params_:
                for pname in sorted(self.params_[name]):
                    yield name, pname, self.params_[name][pname]

    def params(self) -> NDArray:
        chunks = [jnp.asarray(w).reshape(-1) for _, _, w in self._param_entries()]
        return NDArray(jnp.concatenate(chunks) if chunks else jnp.zeros((0,)))

    def num_params(self) -> int:
        return sum(int(np.prod(w.shape)) for _, _, w in self._param_entries())

    def set_params(self, flat) -> None:
        arr = np.asarray(flat.numpy() if hasattr(flat, "numpy") else flat).reshape(-1)  # host-ok: set_params ingests user input
        expected = self.num_params()
        if arr.size != expected:
            raise ValueError(f"param vector length {arr.size} != model numParams {expected}")
        off = 0
        new = {k: dict(v) for k, v in self.params_.items()}
        for name, pname, w in self._param_entries():
            n = int(np.prod(w.shape))
            new[name][pname] = jnp.asarray(arr[off : off + n].reshape(w.shape), w.dtype)
            off += n
        self.params_ = new

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
