"""Network configuration: builders, InputType shape inference, layer configs.

Reference: deeplearning4j-nn ``org.deeplearning4j.nn.conf.*``:
``NeuralNetConfiguration.Builder`` (global defaults cascading into layers),
``MultiLayerConfiguration`` / ``ComputationGraphConfiguration``,
``conf.layers.*`` (~100 config beans), ``conf.inputs.InputType`` (shape
inference), ``conf.preprocessor.*``.

TPU-native divergence: the reference splits config beans from runtime layer
classes (``nn.conf.layers.DenseLayer`` vs ``nn.layers.feedforward.dense.
DenseLayer``); here each config class carries its pure-functional runtime
(``init_params`` + ``forward``) — the "runtime" is a jax function traced once
into the whole-network compiled step, so there is no per-layer object state to
manage. JSON round-trip of configs is preserved (C1 invariant).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import activations as act
from . import losses as loss_fns
from . import updaters as upd
from .updaters import IUpdater, Sgd
from .weights import init_weights

# ----------------------------------------------------------------- InputType


@dataclass(frozen=True)
class InputType:
    """org.deeplearning4j.nn.conf.inputs.InputType — shape inference tokens.

    kind: "ff" (size,), "rnn" (size, tlen or None), "cnn" (h, w, channels),
    "cnnflat" (h, w, channels flattened).
    """

    kind: str
    size: int = 0
    height: int = 0
    width: int = 0
    channels: int = 0
    timeseries_length: Optional[int] = None
    depth: int = 0  # cnn3d (NCDHW)

    @staticmethod
    def feed_forward(size: int) -> "InputType":
        return InputType("ff", size=size)

    @staticmethod
    def recurrent(size: int, timeseries_length: Optional[int] = None) -> "InputType":
        return InputType("rnn", size=size, timeseries_length=timeseries_length)

    @staticmethod
    def convolutional(height: int, width: int, channels: int) -> "InputType":
        return InputType("cnn", height=height, width=width, channels=channels)

    @staticmethod
    def convolutional_flat(height: int, width: int, channels: int) -> "InputType":
        return InputType("cnnflat", height=height, width=width, channels=channels)

    @staticmethod
    def convolutional3d(depth: int, height: int, width: int, channels: int) -> "InputType":
        """NCDHW (Convolution3D.DataFormat.NCDHW)."""
        return InputType("cnn3d", depth=depth, height=height, width=width, channels=channels)

    def flat_size(self) -> int:
        if self.kind == "ff":
            return self.size
        if self.kind == "rnn":
            return self.size
        if self.kind == "cnn3d":
            return self.depth * self.height * self.width * self.channels
        return self.height * self.width * self.channels

    def to_json(self):
        return dataclasses.asdict(self)


# conv output-size helper (ConvolutionUtils.getOutputSize: 'truncate'/'same')
def _conv_out(size, k, s, p, same):
    if same:
        return -(-size // s)
    return (size + 2 * p - k) // s + 1


def _conv_taps(in_size, k, s, p, d, same, out_size):
    """Total kernel taps landing INSIDE the input along one spatial dim,
    summed over output positions — XLA's cost_analysis counts conv flops
    over valid taps only (padding positions multiply nothing), so the
    per-layer estimate must too or SAME-padded stacks overcount ~15%."""
    if same:  # lax SAME padding: pad_total so out = ceil(in/s)
        pad_total = max((out_size - 1) * s + (k - 1) * d + 1 - in_size, 0)
        pad_lo = pad_total // 2
    else:
        pad_lo = p
    total = 0
    for o in range(out_size):
        start = o * s - pad_lo
        for j in range(k):
            if 0 <= start + j * d < in_size:
                total += 1
    return total


# ---------------------------------------------------------------- param roles

# Role vocabulary for parameter partitioning (parallel.partition.SpecLayout
# maps each role to a PartitionSpec over the data/fsdp/tp mesh). nn owns the
# vocabulary and the name→role tagging; parallel owns the role→spec policy.
ROLE_EMBEDDING = "embedding"   # lookup tables: vocab/class dim shards fsdp×tp
ROLE_KERNEL = "kernel"         # dense/conv/recurrent projection matrices
ROLE_NORM = "norm"             # per-feature scales (gamma/beta/alpha/ln_*)
ROLE_BIAS = "bias"             # per-unit offsets (and scalar margins)
# Under tensor parallelism a plain ``kernel`` is the FIRST of a pair (its
# output features split over tp). The other sides of a pair say so:
ROLE_KERNEL_ROW = "kernel_row"      # the SECOND of a pair: its input is the
#                                     split activation the first one left
ROLE_KERNEL_WHOLE = "kernel_whole"  # whole activation in, whole out: no tp
ROLE_BIAS_COLUMN = "bias_column"    # a first-of-pair kernel's bias: split
#                                     with that kernel's columns

# Canonical param-name → role table covering every name produced by the
# bundled layers and functional models. Partitioning treats an unknown name
# as UNCOVERED (no silent replication) — add new names here, or override
# ``Layer.param_roles`` where a name's role is layer-dependent.
_PARAM_NAME_ROLES = {
    # conf.py layers
    "W": ROLE_KERNEL, "RW": ROLE_KERNEL, "b": ROLE_BIAS,
    "gamma": ROLE_NORM, "beta": ROLE_NORM,
    "pi": ROLE_BIAS, "pf": ROLE_BIAS, "po": ROLE_BIAS,  # LSTM peepholes [H]
    "dW": ROLE_KERNEL, "pW": ROLE_KERNEL,  # separable conv depth/pointwise
    # layers_ext / layers_tail / attention / capsules
    "rb": ROLE_BIAS,                       # GRU reset_after bias
    "alpha": ROLE_NORM,                    # PReLU per-feature slope
    "centers": ROLE_EMBEDDING,             # CenterLoss per-class centers
    "V": ROLE_KERNEL, "w": ROLE_KERNEL, "r": ROLE_BIAS,  # OCNN
    "Wq": ROLE_KERNEL, "Wk": ROLE_KERNEL, "Wv": ROLE_KERNEL,
    "Wo": ROLE_KERNEL, "Wr": ROLE_KERNEL,
    "Wh": ROLE_KERNEL, "Wx": ROLE_KERNEL,
    "Q": ROLE_EMBEDDING,                   # learned query table [n_queries, proj]
    # functional transformer (models/transformer.py)
    "tok": ROLE_EMBEDDING, "pos": ROLE_EMBEDDING, "seg": ROLE_EMBEDDING,
    # (the two Megatron pairs of a block: qkv_w -> out_w, ffn_w1 -> ffn_w2)
    "qkv_w": ROLE_KERNEL, "out_w": ROLE_KERNEL_ROW,
    "ffn_w1": ROLE_KERNEL, "ffn_w2": ROLE_KERNEL_ROW,
    "qkv_b": ROLE_BIAS_COLUMN, "out_b": ROLE_BIAS,
    "ffn_b1": ROLE_BIAS_COLUMN, "ffn_b2": ROLE_BIAS, "out_bias": ROLE_BIAS,
    "ln_scale": ROLE_NORM, "ln_bias": ROLE_NORM,
    "ln1_scale": ROLE_NORM, "ln1_bias": ROLE_NORM,
    "ln2_scale": ROLE_NORM, "ln2_bias": ROLE_NORM,
}


# A name that means different things in different containers is tagged by the
# last two components of its path, which win over the bare name: the
# functional transformer's head transform ``mlm/w`` (its input the whole
# residual stream, its output normalised whole) shares ``w`` with OCNN's.
_PARAM_PATH_ROLES = {
    "mlm/w": ROLE_KERNEL_WHOLE,
}


def param_role(name: str, leaf=None, parent: Optional[str] = None) -> Optional[str]:
    """Role for one param leaf by name (None = uncovered), ``parent`` being
    the key of the dict that holds it. Falls back to suffix patterns so new
    functional-model names with conventional suffixes
    (``*_w``/``*_b``/``*_scale``/``*_bias``/``*embed*``) stay covered."""
    by_path = _PARAM_PATH_ROLES.get(f"{parent}/{name}")
    if by_path is not None:
        return by_path
    if name in _PARAM_NAME_ROLES:
        return _PARAM_NAME_ROLES[name]
    ln = name.lower()
    if "embed" in ln:
        return ROLE_EMBEDDING
    if ln.endswith("_scale") or ln.endswith("_gain"):
        return ROLE_NORM
    if ln.endswith("_bias") or ln.endswith("_b"):
        return ROLE_BIAS
    if ln.endswith("_w") or ln.endswith("_kernel"):
        return ROLE_KERNEL
    return None


def classify_param_tree(params, _parent: Optional[str] = None) -> Any:
    """Mirror a params (sub)tree with role strings / None per leaf. Nested
    containers (Bidirectional fwd/bwd, graph node dicts, transformer block
    lists) recurse; leaf role comes from the leaf's own key name, read with
    the key of the dict that holds it (``_PARAM_PATH_ROLES``)."""
    if isinstance(params, dict):
        return {k: (classify_param_tree(v, k) if isinstance(v, (dict, list, tuple))
                    else param_role(k, v, _parent))
                for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        return type(params)(classify_param_tree(v, _parent) for v in params)
    return None  # bare leaf with no name context


# --------------------------------------------------------------- base config


@dataclass
class Layer:
    """Base layer config (org.deeplearning4j.nn.conf.layers.Layer)."""

    name: Optional[str] = None
    # cascaded defaults (filled by ListBuilder from NeuralNetConfiguration)
    updater: Optional[IUpdater] = None
    weight_init: str = "xavier"
    activation: str = "identity"
    l1: float = 0.0
    l2: float = 0.0
    dropout: float = 0.0  # retain prob (float) or an nn.dropout IDropout scheme
    frozen: bool = False  # FrozenLayer (TransferLearning): no param updates
    constraints: tuple = ()      # nn.constraints.*, applied after each update
    weight_noise: Optional[Any] = None  # nn.constraints.WeightNoise/DropConnect

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init_params(self, key, input_type: InputType, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
        return {}

    def forward(self, params, x, input_type, *, training: bool, rng=None):
        return x

    def has_params(self) -> bool:
        return True

    def param_roles(self, params) -> Any:
        """Role tree mirroring ``init_params`` output (see the role
        vocabulary above). The default classifies each leaf by its canonical
        param name; layers whose names are role-ambiguous (EmbeddingLayer's
        ``W`` is a table, not a projection) override."""
        return classify_param_tree(params)

    def flops_per_example(self, it: InputType) -> float:
        """Estimated FORWARD floating-point operations for ONE example
        (monitoring.costmodel multiplies by batch and the train factor).
        The default models a cheap elementwise layer: one op per output
        element. Layers with real arithmetic (dense/conv/recurrent) override
        with the textbook 2·MACs formulas, which is also how XLA's
        ``cost_analysis()`` counts dots and convolutions — so the per-layer
        table can be validated against the compiled step's total."""
        out = self.output_type(it)
        T = out.timeseries_length if out.kind == "rnn" else 1
        return float(out.flat_size()) * float(T or 1)

    def _apply_dropout(self, x, training, rng):
        """DL4J conf .dropOut(...): a float (probability of RETAINING an
        activation, inverted scaling) or an IDropout scheme object
        (nn.dropout.Gaussian*/Alpha*/Spatial*), applied to the layer INPUT."""
        from .dropout import apply_dropout

        return apply_dropout(self.dropout, x, rng, training)

    def to_json(self) -> dict:
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, IUpdater):
                v = v.to_json()
            elif isinstance(v, InputType):
                v = v.to_json()
            elif f.name == "dropout" and hasattr(v, "apply"):  # IDropout scheme
                v = {"@dropout": type(v).__name__, **dataclasses.asdict(v)}
            d[f.name] = v
        d["@class"] = type(self).__name__
        return d

    @staticmethod
    def from_json(d: dict) -> "Layer":
        d = dict(d)
        cls = LAYER_REGISTRY[d.pop("@class")]
        if d.get("updater") and isinstance(d["updater"], dict):
            d["updater"] = IUpdater.from_json(d["updater"])
        if isinstance(d.get("dropout"), dict) and "@dropout" in d["dropout"]:
            from . import dropout as dropout_mod

            dd = dict(d["dropout"])
            d["dropout"] = getattr(dropout_mod, dd.pop("@dropout"))(**dd)
        for k, v in list(d.items()):
            # nested layer configs (Bidirectional.fwd, TimeDistributed/
            # MaskZeroLayer/FrozenLayerWithBackprop.underlying) recurse
            if isinstance(v, dict) and "@class" in v:
                d[k] = Layer.from_json(v)
        flds = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in flds})


# ------------------------------------------------------------- dense / output


@dataclass
class DenseLayer(Layer):
    """org.deeplearning4j.nn.conf.layers.DenseLayer → runtime
    nn.layers.feedforward.dense.DenseLayer (preOut = x@W + b on the MXU)."""

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = True

    def output_type(self, it: InputType) -> InputType:
        if it.kind == "rnn":
            return InputType.recurrent(self.n_out, it.timeseries_length)
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.flat_size()
        kw, _ = jax.random.split(key)
        p = {"W": init_weights(kw, (n_in, self.n_out), n_in, self.n_out, self.weight_init, dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return act.get(self.activation)(z)

    def flops_per_example(self, it: InputType) -> float:
        n_in = self.n_in or it.flat_size()
        # time-distributed over [B,T,C] when the input kept its timeline
        T = (it.timeseries_length or 1) if it.kind == "rnn" else 1
        return float(T) * (2.0 * n_in * self.n_out + self.n_out)


@dataclass
class OutputLayer(DenseLayer):
    """conf.layers.OutputLayer: dense + loss head. When activation=softmax and
    loss=mcxent the compiled step uses the fused logits path
    (softmax_cross_entropy_with_logits) for stability — the analog of libnd4j's
    fused softmax_cross_entropy_loss op."""

    loss: str = "mcxent"
    activation: str = "softmax"

    def compute_loss(self, params, x, labels, it, *, training, rng=None, mask=None):
        x = self._apply_dropout(x, training, rng)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        # AMP policy: loss math in fp32 even when the stack ran bf16
        z = z.astype(jnp.float32)
        a = self.activation.lower()
        l = self.loss.lower().replace("_", "")
        if a == "softmax" and l in ("mcxent", "negativeloglikelihood"):
            return loss_fns.softmax_cross_entropy_with_logits(labels, z, mask=mask)
        if a == "sigmoid" and l == "xent":
            return loss_fns.sigmoid_cross_entropy_with_logits(labels, z, mask=mask)
        preds = act.get(self.activation)(z)
        return loss_fns.get(self.loss)(labels, preds, mask=mask)


@dataclass
class LossLayer(Layer):
    """conf.layers.LossLayer — loss head without params."""

    loss: str = "mse"
    activation: str = "identity"

    def has_params(self):
        return False

    def compute_loss(self, params, x, labels, it, *, training, rng=None, mask=None):
        preds = act.get(self.activation)(x.astype(jnp.float32))
        return loss_fns.get(self.loss)(labels, preds, mask=mask)

    def forward(self, params, x, it, *, training, rng=None):
        return act.get(self.activation)(x)


@dataclass
class ActivationLayer(Layer):
    def has_params(self):
        return False

    def forward(self, params, x, it, *, training, rng=None):
        return act.get(self.activation)(x)


@dataclass
class DropoutLayer(Layer):
    def has_params(self):
        return False

    def forward(self, params, x, it, *, training, rng=None):
        return self._apply_dropout(x, training, rng)


# ------------------------------------------------------------------ conv 2d


def _nhwc(x):
    """NCHW → NHWC. The public inter-layer layout is NCHW (DL4J parity:
    [B,C,H,W] features, 'c'-order CnnToFeedForward flatten) but every
    conv-family layer computes in NHWC — the TPU-native layout (measured
    4-15x faster than NCHW dimension_numbers through the XLA:TPU pipeline).
    Adjacent out/in transpose pairs across a conv→pool→BN→conv chain compose
    to identity and are removed by XLA's algebraic simplifier, so stacks run
    pure NHWC with transposes only at the true boundaries."""
    return jnp.transpose(x, (0, 2, 3, 1))


def _nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


@dataclass
class ConvolutionLayer(Layer):
    """conf.layers.ConvolutionLayer → XLA conv_general_dilated on the MXU
    (reference: libnd4j generic/nn/convo/conv2d.cpp via im2col+gemm or cuDNN
    helper C5 — on TPU the XLA compiler IS the vendor library, SURVEY §2.9
    N10). NCHW API / OIHW weights for parity; NHWC compute (see _nhwc)."""

    n_in: int = 0  # channels in (inferred)
    n_out: int = 0  # filters
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"  # truncate | same
    has_bias: bool = True
    activation: str = "identity"

    def output_type(self, it: InputType) -> InputType:
        same = self.convolution_mode == "same"
        h = _conv_out(it.height, self.kernel_size[0] * self.dilation[0] - self.dilation[0] + 1, self.stride[0], self.padding[0], same)
        w = _conv_out(it.width, self.kernel_size[1] * self.dilation[1] - self.dilation[1] + 1, self.stride[1], self.padding[1], same)
        return InputType.convolutional(h, w, self.n_out)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.channels
        kh, kw = self.kernel_size
        fan_in = c_in * kh * kw
        fan_out = self.n_out * kh * kw
        k1, _ = jax.random.split(key)
        # OIHW weight layout (DL4J: [out, in, kH, kW])
        p = {"W": init_weights(k1, (self.n_out, c_in, kh, kw), fan_in, fan_out, self.weight_init, dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        same = self.convolution_mode == "same"
        pad = "SAME" if same else [(p, p) for p in self.padding]
        z = jax.lax.conv_general_dilated(
            _nhwc(x),
            jnp.transpose(params["W"], (2, 3, 1, 0)),  # OIHW → HWIO
            window_strides=self.stride,
            padding=pad,
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            z = z + params["b"]
        return _nchw(act.get(self.activation)(z))

    def _spatial_taps(self, it: InputType) -> float:
        out = self.output_type(it)
        same = self.convolution_mode == "same"
        th = _conv_taps(it.height, self.kernel_size[0], self.stride[0],
                        self.padding[0], self.dilation[0], same, out.height)
        tw = _conv_taps(it.width, self.kernel_size[1], self.stride[1],
                        self.padding[1], self.dilation[1], same, out.width)
        return float(th) * float(tw)

    def flops_per_example(self, it: InputType) -> float:
        c_in = self.n_in or it.channels
        return 2.0 * self._spatial_taps(it) * self.n_out * c_in


@dataclass
class Deconvolution2D(ConvolutionLayer):
    """conf.layers.Deconvolution2D (transpose conv)."""

    def output_type(self, it: InputType) -> InputType:
        same = self.convolution_mode == "same"
        if same:
            h, w = it.height * self.stride[0], it.width * self.stride[1]
        else:
            h = (it.height - 1) * self.stride[0] + self.kernel_size[0] - 2 * self.padding[0]
            w = (it.width - 1) * self.stride[1] + self.kernel_size[1] - 2 * self.padding[1]
        return InputType.convolutional(h, w, self.n_out)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.channels
        kh, kw = self.kernel_size
        k1, _ = jax.random.split(key)
        p = {"W": init_weights(k1, (c_in, self.n_out, kh, kw), c_in * kh * kw, self.n_out * kh * kw, self.weight_init, dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        same = self.convolution_mode == "same"
        pad = "SAME" if same else [(p, p) for p in self.padding]
        z = jax.lax.conv_transpose(
            _nhwc(x),
            jnp.transpose(params["W"], (2, 3, 0, 1)),  # IOHW → HWIO
            strides=self.stride,
            padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            z = z + params["b"]
        return _nchw(act.get(self.activation)(z))

    def flops_per_example(self, it: InputType) -> float:
        # each input pixel scatters through the kernel into cout outputs
        c_in = self.n_in or it.channels
        kh, kw = self.kernel_size
        return 2.0 * it.height * it.width * c_in * kh * kw * self.n_out


@dataclass
class DepthwiseConvolution2D(ConvolutionLayer):
    """conf.layers.DepthwiseConvolution2D; depth_multiplier semantics."""

    depth_multiplier: int = 1

    def output_type(self, it: InputType) -> InputType:
        base = super().output_type(it)
        c = (self.n_in or it.channels) * self.depth_multiplier
        return InputType.convolutional(base.height, base.width, c)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.channels
        kh, kw = self.kernel_size
        k1, _ = jax.random.split(key)
        p = {"W": init_weights(k1, (c_in * self.depth_multiplier, 1, kh, kw), kh * kw, kh * kw, self.weight_init, dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((c_in * self.depth_multiplier,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        c_in = x.shape[1]
        same = self.convolution_mode == "same"
        pad = "SAME" if same else [(p, p) for p in self.padding]
        z = jax.lax.conv_general_dilated(
            _nhwc(x),
            jnp.transpose(params["W"], (2, 3, 1, 0)),  # OIHW → HWIO (I=1)
            window_strides=self.stride,
            padding=pad,
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c_in,
        )
        if self.has_bias:
            z = z + params["b"]
        return _nchw(act.get(self.activation)(z))

    def flops_per_example(self, it: InputType) -> float:
        c_in = self.n_in or it.channels
        return 2.0 * self._spatial_taps(it) * c_in * self.depth_multiplier


@dataclass
class SeparableConvolution2D(ConvolutionLayer):
    depth_multiplier: int = 1

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.channels
        kh, kw = self.kernel_size
        k1, k2 = jax.random.split(key)
        p = {
            "dW": init_weights(k1, (c_in * self.depth_multiplier, 1, kh, kw), kh * kw, kh * kw, self.weight_init, dtype),
            "pW": init_weights(
                k2, (self.n_out, c_in * self.depth_multiplier, 1, 1), c_in * self.depth_multiplier, self.n_out, self.weight_init, dtype
            ),
        }
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        c_in = x.shape[1]
        same = self.convolution_mode == "same"
        pad = "SAME" if same else [(p, p) for p in self.padding]
        z = jax.lax.conv_general_dilated(
            _nhwc(x), jnp.transpose(params["dW"], (2, 3, 1, 0)),
            window_strides=self.stride, padding=pad, rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c_in,
        )
        z = jax.lax.conv_general_dilated(
            z, jnp.transpose(params["pW"], (2, 3, 1, 0)), window_strides=(1, 1),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            z = z + params["b"]
        return _nchw(act.get(self.activation)(z))

    def flops_per_example(self, it: InputType) -> float:
        out = self.output_type(it)
        c_in = self.n_in or it.channels
        mid = c_in * self.depth_multiplier
        depthwise = 2.0 * self._spatial_taps(it) * mid
        pointwise = 2.0 * out.height * out.width * mid * self.n_out
        return depthwise + pointwise


@dataclass
class SubsamplingLayer(Layer):
    """conf.layers.SubsamplingLayer (max/avg pooling) → lax.reduce_window."""

    pooling_type: str = "max"  # max | avg | pnorm
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        same = self.convolution_mode == "same"
        h = _conv_out(it.height, self.kernel_size[0], self.stride[0], self.padding[0], same)
        w = _conv_out(it.width, self.kernel_size[1], self.stride[1], self.padding[1], same)
        return InputType.convolutional(h, w, it.channels)

    def forward(self, params, x, it, *, training, rng=None):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        same = self.convolution_mode == "same"
        pad = "SAME" if same else [(0, 0), (self.padding[0],) * 2, (self.padding[1],) * 2, (0, 0)]
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        x = _nhwc(x)  # pool in the TPU-native layout (transposes cancel with neighbors)
        if self.pooling_type == "max":
            return _nchw(jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strides, pad))
        if self.pooling_type == "avg":
            s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pad)
            ones = jnp.ones_like(x)
            c = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strides, pad)
            return _nchw(s / c)
        if self.pooling_type == "pnorm":
            p = float(self.pnorm)
            s = jax.lax.reduce_window(jnp.abs(x) ** p, 0.0, jax.lax.add, dims, strides, pad)
            return _nchw(s ** (1.0 / p))
        raise ValueError(f"unknown pooling {self.pooling_type}")

    def flops_per_example(self, it: InputType) -> float:
        out = self.output_type(it)
        return (float(out.height * out.width * out.channels)
                * self.kernel_size[0] * self.kernel_size[1])


@dataclass
class Upsampling2D(Layer):
    size: Tuple[int, int] = (2, 2)

    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        return InputType.convolutional(it.height * self.size[0], it.width * self.size[1], it.channels)

    def forward(self, params, x, it, *, training, rng=None):
        x = _nhwc(x)
        return _nchw(jnp.repeat(jnp.repeat(x, self.size[0], axis=1), self.size[1], axis=2))


@dataclass
class ZeroPaddingLayer(Layer):
    padding: Tuple[int, int, int, int] = (0, 0, 0, 0)  # top,bottom,left,right

    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        t, b, l, r = self.padding
        return InputType.convolutional(it.height + t + b, it.width + l + r, it.channels)

    def forward(self, params, x, it, *, training, rng=None):
        t, b, l, r = self.padding
        return _nchw(jnp.pad(_nhwc(x), ((0, 0), (t, b), (l, r), (0, 0))))


@dataclass
class BatchNormalization(Layer):
    """conf.layers.BatchNormalization → runtime
    nn.layers.normalization.BatchNormalization (running stats, gamma/beta).
    Running stats are non-gradient state carried through the train step
    (reference stores them as params excluded from updates; here they live in
    a separate 'state' collection updated functionally)."""

    n_out: int = 0  # inferred from input
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False

    def output_type(self, it: InputType) -> InputType:
        return it

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        n = self.n_out or (it.channels if it.kind == "cnn" else it.flat_size())
        p = {}
        if not self.lock_gamma_beta:
            p["gamma"] = jnp.ones((n,), dtype)
            p["beta"] = jnp.zeros((n,), dtype)
        return p

    def init_state(self, it: InputType, dtype=jnp.float32):
        n = self.n_out or (it.channels if it.kind == "cnn" else it.flat_size())
        return {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}

    def forward_bn(self, params, state, x, it, *, training):
        nchw_in = x.ndim == 4
        if nchw_in:  # [B,C,H,W] → normalize in NHWC (transposes cancel with conv neighbors)
            x = _nhwc(x)
            axes, bshape = (0, 1, 2), (1, 1, 1, -1)
        elif x.ndim == 3:  # [B,C,T] recurrent: per-channel over (B,T)
            axes, bshape = (0, 2), (1, -1, 1)
        else:
            axes, bshape = (0,), (1, -1)
        # AMP policy: moments in fp32 regardless of activation dtype (running
        # state stays fp32); output back in the stack's compute dtype.
        # ONE-PASS statistics: sum and sum-of-squares in the same fused
        # reduction (var = E[x^2]-E[x]^2) instead of jnp.mean + jnp.var's two
        # reads of the activation. BN between convs is HBM-bandwidth-bound on
        # TPU; measured on ResNet-50/v5e this single change is worth ~13%
        # step time (112.8 -> 99.5 ms/step, batch 256, r4 probe).
        xf = x.astype(jnp.float32)
        if training:
            n = 1
            for a in axes:
                n *= x.shape[a]
            mean = jnp.sum(xf, axis=axes) / n
            var = jnp.maximum(jnp.sum(xf * xf, axis=axes) / n - mean * mean, 0.0)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"].astype(jnp.float32), state["var"].astype(jnp.float32)
            new_state = state
        # scale/offset form: one multiply-add over the activation, fusable
        # into the producing conv's epilogue
        inv = jax.lax.rsqrt(var + self.eps)
        if "gamma" in params:
            inv = inv * params["gamma"].astype(jnp.float32)
            off = params["beta"].astype(jnp.float32) - mean * inv
        else:
            off = -mean * inv
        xh = xf * inv.reshape(bshape) + off.reshape(bshape)
        out = act.get(self.activation)(xh).astype(x.dtype)
        return (_nchw(out) if nchw_in else out), new_state

    def forward(self, params, x, it, *, training, rng=None, state=None):
        out, _ = self.forward_bn(params, state or self.init_state(it, x.dtype), x, it, training=False)
        return out

    def flops_per_example(self, it: InputType) -> float:
        # one-pass moments (sum + sum-of-squares) + scale/offset apply
        T = (it.timeseries_length or 1) if it.kind == "rnn" else 1
        return 8.0 * it.flat_size() * float(T)


@dataclass
class LocalResponseNormalization(Layer):
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self):
        return False

    def forward(self, params, x, it, *, training, rng=None):
        # cross-channel LRN over NCHW axis 1
        sq = jnp.square(x)
        half = self.n // 2
        padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
        windows = sum(padded[:, i : i + x.shape[1]] for i in range(self.n))
        return x / (self.k + self.alpha * windows) ** self.beta


# ----------------------------------------------------------------- embedding


@dataclass
class EmbeddingLayer(Layer):
    """conf.layers.EmbeddingLayer: int index input [B] or one-hot [B,V] →
    [B, nOut] (gather on the embedding table)."""

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = False

    def output_type(self, it: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.flat_size()
        k1, _ = jax.random.split(key)
        p = {"W": init_weights(k1, (n_in, self.n_out), n_in, self.n_out, self.weight_init, dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None):
        if jnp.issubdtype(x.dtype, jnp.integer):
            z = params["W"][x.reshape(-1)]
        elif x.ndim == 2 and x.shape[-1] == params["W"].shape[0]:
            z = x @ params["W"]  # one-hot path
        else:
            z = params["W"][x.astype(jnp.int32).reshape(-1)]
        if self.has_bias:
            z = z + params["b"]
        return act.get(self.activation)(z)

    def param_roles(self, params) -> Any:
        # W is the [vocab, n_out] lookup TABLE here, not a projection kernel
        return {k: (ROLE_EMBEDDING if k == "W" else param_role(k, v))
                for k, v in params.items()}

    def flops_per_example(self, it: InputType) -> float:
        # a gather moves bytes, not flops — count only the bias/activation
        return float(self.n_out)


@dataclass
class EmbeddingSequenceLayer(EmbeddingLayer):
    """conf.layers.EmbeddingSequenceLayer: [B,T] ints → [B, nOut, T] (DL4J
    RNN layout NCT)."""

    def output_type(self, it: InputType) -> InputType:
        # an int-sequence input may be declared feed-forward([T]) (Keras
        # Embedding inputs have shape [B,T]); its length is the timeline
        T = it.timeseries_length if it.kind == "rnn" else (it.flat_size() or None)
        return InputType.recurrent(self.n_out, T)

    def forward(self, params, x, it, *, training, rng=None):
        ix = x.astype(jnp.int32)
        if ix.ndim == 3:  # [B,1,T]
            ix = ix[:, 0, :]
        z = params["W"][ix]  # [B,T,nOut]
        if self.has_bias:
            z = z + params["b"]
        z = act.get(self.activation)(z)
        return jnp.swapaxes(z, 1, 2)  # [B,nOut,T]


# ----------------------------------------------------------------- recurrent


def _lstm_scan(x_tbi, h0, c0, Wx, Wh, b, gate_act, cell_act, peephole=None):
    """Fused LSTM over time via lax.scan — the XLA-native replacement for the
    reference's per-timestep Java loop (LSTMHelpers.activateHelper: gemm(x_t,W)
    + gemm(h_{t-1},U) + 4 gate transforms per step, SURVEY §3.2 hot loop).
    Input [T,B,I]; gate order IFOG (input, forget, output, cell-gate) matching
    libnd4j lstmLayer. Returns outputs [T,B,H], (hT, cT)."""
    n_hidden = Wh.shape[0]
    # precompute input projections for all timesteps in ONE big matmul (MXU-friendly)
    xz = jnp.einsum("tbi,ig->tbg", x_tbi, Wx) + b

    def step(carry, xz_t):
        h, c = carry
        z = xz_t + h @ Wh
        i_g, f_g, o_g, g_g = jnp.split(z, 4, axis=-1)
        if peephole is not None:
            pi, pf, po = peephole
            i_g = i_g + c * pi
            f_g = f_g + c * pf
        i_t = gate_act(i_g)
        f_t = gate_act(f_g)
        g_t = cell_act(g_g)
        c_new = f_t * c + i_t * g_t
        if peephole is not None:
            o_g = o_g + c_new * po
        o_t = gate_act(o_g)
        h_new = o_t * cell_act(c_new)
        return (h_new, c_new), h_new

    (hT, cT), outs = jax.lax.scan(step, (h0, c0), xz)
    return outs, (hT, cT)


@dataclass
class LSTM(Layer):
    """conf.layers.LSTM → libnd4j generic/recurrent/lstmLayer.cpp. Data layout
    [B, nIn, T] (DL4J NCT convention); internally time-major scan."""

    n_in: int = 0
    n_out: int = 0
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    peephole: bool = False

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        H = self.n_out
        k1, k2 = jax.random.split(key)
        p = {
            "W": init_weights(k1, (n_in, 4 * H), n_in, H, self.weight_init, dtype),
            "RW": init_weights(k2, (H, 4 * H), H, H, self.weight_init, dtype),
            "b": jnp.zeros((4 * H,), dtype)
            .at[H : 2 * H]
            .set(1.0),  # forget-gate bias 1.0 (DL4J forgetGateBiasInit default)
        }
        if self.peephole:
            p["pi"] = jnp.zeros((H,), dtype)
            p["pf"] = jnp.zeros((H,), dtype)
            p["po"] = jnp.zeros((H,), dtype)
        return p

    def forward(self, params, x, it, *, training, rng=None, initial_state=None):
        x = self._apply_dropout(x, training, rng)
        x_tbi = jnp.transpose(x, (2, 0, 1))  # [B,I,T] -> [T,B,I]
        B = x.shape[0]
        H = self.n_out
        if initial_state is None:
            h0 = jnp.zeros((B, H), x.dtype)
            c0 = jnp.zeros((B, H), x.dtype)
        else:
            h0, c0 = initial_state
        peep = (params["pi"], params["pf"], params["po"]) if self.peephole else None
        outs, _ = _lstm_scan(
            x_tbi, h0, c0, params["W"], params["RW"], params["b"],
            act.get(self.gate_activation), act.get(self.activation), peep,
        )
        return jnp.transpose(outs, (1, 2, 0))  # [T,B,H] -> [B,H,T]

    def forward_with_state(self, params, x, h0, c0):
        """Streaming rnnTimeStep support: returns (out [B,H,T], hT, cT)."""
        x_tbi = jnp.transpose(x, (2, 0, 1))
        peep = (params["pi"], params["pf"], params["po"]) if self.peephole else None
        outs, (hT, cT) = _lstm_scan(
            x_tbi, h0, c0, params["W"], params["RW"], params["b"],
            act.get(self.gate_activation), act.get(self.activation), peep,
        )
        return jnp.transpose(outs, (1, 2, 0)), hT, cT

    def flops_per_example(self, it: InputType) -> float:
        n_in = self.n_in or it.size
        H = self.n_out
        T = float(it.timeseries_length or 1)
        # input + recurrent projections into 4 gates, plus ~10 elementwise
        # ops/unit for the gate math (peepholes add 3 multiply-adds)
        per_step = 2.0 * n_in * 4 * H + 2.0 * H * 4 * H + 10.0 * H
        if self.peephole:
            per_step += 6.0 * H
        return T * per_step


@dataclass
class GravesLSTM(LSTM):
    """conf.layers.GravesLSTM — peephole LSTM (Graves 2013), baseline config #3."""

    peephole: bool = True


@dataclass
class SimpleRnn(Layer):
    n_in: int = 0
    n_out: int = 0
    activation: str = "tanh"

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        H = self.n_out
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (n_in, H), n_in, H, self.weight_init, dtype),
            "RW": init_weights(k2, (H, H), H, H, self.weight_init, dtype),
            "b": jnp.zeros((H,), dtype),
        }

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        x_tbi = jnp.transpose(x, (2, 0, 1))
        xz = jnp.einsum("tbi,ih->tbh", x_tbi, params["W"]) + params["b"]
        a = act.get(self.activation)

        def step(h, xz_t):
            h_new = a(xz_t + h @ params["RW"])
            return h_new, h_new

        h0 = jnp.zeros((x.shape[0], self.n_out), x.dtype)
        _, outs = jax.lax.scan(step, h0, xz)
        return jnp.transpose(outs, (1, 2, 0))

    def flops_per_example(self, it: InputType) -> float:
        n_in = self.n_in or it.size
        H = self.n_out
        T = float(it.timeseries_length or 1)
        return T * (2.0 * n_in * H + 2.0 * H * H + 2.0 * H)


@dataclass
class Bidirectional(Layer):
    """conf.layers.recurrent.Bidirectional wrapper: mode CONCAT/ADD/MUL/AVERAGE."""

    fwd: Optional[Layer] = None
    mode: str = "concat"

    def output_type(self, it: InputType) -> InputType:
        inner = self.fwd.output_type(it)
        if self.mode == "concat":
            return InputType.recurrent(inner.size * 2, inner.timeseries_length)
        return inner

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {"fwd": self.fwd.init_params(k1, it, dtype), "bwd": self.fwd.init_params(k2, it, dtype)}

    def forward(self, params, x, it, *, training, rng=None):
        out_f = self.fwd.forward(params["fwd"], x, it, training=training, rng=rng)
        x_rev = jnp.flip(x, axis=2)
        out_b = jnp.flip(self.fwd.forward(params["bwd"], x_rev, it, training=training, rng=rng), axis=2)
        if self.mode == "concat":
            return jnp.concatenate([out_f, out_b], axis=1)
        if self.mode == "add":
            return out_f + out_b
        if self.mode == "mul":
            return out_f * out_b
        if self.mode == "average":
            return 0.5 * (out_f + out_b)
        raise ValueError(self.mode)

    def to_json(self):
        d = super().to_json()
        d["fwd"] = self.fwd.to_json()
        return d

    def flops_per_example(self, it: InputType) -> float:
        return 2.0 * self.fwd.flops_per_example(it)


@dataclass
class LastTimeStep(Layer):
    """recurrent.LastTimeStep wrapper: [B,C,T] → [B,C] (respecting masks is
    handled by the network when a mask is present)."""

    underlying: Optional[Layer] = None

    def output_type(self, it: InputType) -> InputType:
        inner = self.underlying.output_type(it) if self.underlying else it
        return InputType.feed_forward(inner.size)

    def init_params(self, key, it: InputType, dtype=jnp.float32):
        return self.underlying.init_params(key, it, dtype) if self.underlying else {}

    def forward(self, params, x, it, *, training, rng=None, mask=None):
        if self.underlying is not None:
            x = self.underlying.forward(params, x, it, training=training, rng=rng)
        if mask is not None:
            # last unmasked step per example
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=-1) - 1, 0)
            return jnp.take_along_axis(x, idx[:, None, None], axis=2)[:, :, 0]
        return x[:, :, -1]

    def flops_per_example(self, it: InputType) -> float:
        return (self.underlying.flops_per_example(it)
                if self.underlying is not None else 0.0)


@dataclass
class RnnOutputLayer(OutputLayer):
    """conf.layers.RnnOutputLayer: time-distributed dense+loss over [B,C,T]."""

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def forward(self, params, x, it, *, training, rng=None):
        x = self._apply_dropout(x, training, rng)
        xt = jnp.swapaxes(x, 1, 2)  # [B,T,C]
        z = xt @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return jnp.swapaxes(act.get(self.activation)(z), 1, 2)

    def compute_loss(self, params, x, labels, it, *, training, rng=None, mask=None):
        x = self._apply_dropout(x, training, rng)
        xt = jnp.swapaxes(x, 1, 2)  # [B,T,C]
        z = xt @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        z = z.astype(jnp.float32)  # AMP policy: loss math in fp32
        lab = jnp.swapaxes(labels, 1, 2) if labels.ndim == 3 else labels
        a = self.activation.lower()
        l = self.loss.lower().replace("_", "")
        if a == "softmax" and l in ("mcxent", "negativeloglikelihood"):
            logp = jax.nn.log_softmax(z, axis=-1)
            ce = -jnp.sum(lab * logp, axis=-1)  # [B,T]
            if mask is not None:
                m = mask.astype(ce.dtype)
                return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)
            return jnp.mean(jnp.sum(ce, axis=-1))
        preds = act.get(self.activation)(z)
        return loss_fns.get(self.loss)(lab, preds, mask=mask)


# ------------------------------------------------------------ global pooling


@dataclass
class GlobalPoolingLayer(Layer):
    """conf.layers.GlobalPoolingLayer: MAX/AVG/SUM/PNORM over spatial or time
    dims; CNN [B,C,H,W]→[B,C]; RNN [B,C,T]→[B,C] (mask-aware)."""

    pooling_type: str = "max"
    pnorm: int = 2

    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        if it.kind in ("cnn", "cnn3d"):
            return InputType.feed_forward(it.channels)
        return InputType.feed_forward(it.size)

    def forward(self, params, x, it, *, training, rng=None, mask=None):
        axes = tuple(range(2, x.ndim))
        pt = self.pooling_type
        if mask is not None and x.ndim == 3:
            m = mask[:, None, :].astype(x.dtype)
            if pt == "max":
                return jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=2)
            if pt in ("avg", "mean"):
                return jnp.sum(x * m, axis=2) / jnp.maximum(jnp.sum(m, axis=2), 1.0)
            if pt == "sum":
                return jnp.sum(x * m, axis=2)
        if pt == "max":
            return jnp.max(x, axis=axes)
        if pt in ("avg", "mean"):
            return jnp.mean(x, axis=axes)
        if pt == "sum":
            return jnp.sum(x, axis=axes)
        if pt == "pnorm":
            p = float(self.pnorm)
            return jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p)
        raise ValueError(pt)


# -------------------------------------------------------------- preprocessors


@dataclass
class InputPreProcessor:
    """conf.preprocessor.* — shape adapters auto-inserted between layers."""

    def pre_process(self, x, it: InputType):
        return x

    def output_type(self, it: InputType) -> InputType:
        return it


@dataclass
class CnnToFeedForwardPreProcessor(InputPreProcessor):
    def pre_process(self, x, it):
        return x.reshape(x.shape[0], -1)

    def output_type(self, it):
        return InputType.feed_forward(it.flat_size())


@dataclass
class FeedForwardToCnnPreProcessor(InputPreProcessor):
    height: int = 0
    width: int = 0
    channels: int = 0

    def pre_process(self, x, it):
        return x.reshape(x.shape[0], self.channels, self.height, self.width)

    def output_type(self, it):
        return InputType.convolutional(self.height, self.width, self.channels)


@dataclass
class RnnToFeedForwardPreProcessor(InputPreProcessor):
    """[B,C,T] → [B,T,C]: dense layers then apply time-distributed over the
    trailing feature axis. (The reference reshapes to [B*T,C]; keeping the
    batch dim intact is equivalent math and XLA-friendlier — no dynamic
    reshape tied to T.)"""

    def pre_process(self, x, it):
        return jnp.swapaxes(x, 1, 2)

    def output_type(self, it):
        return InputType.feed_forward(it.size)


@dataclass
class FeedForwardToRnnPreProcessor(InputPreProcessor):
    """[B,T,C] (time-distributed ff) or [B,C] (single step) → [B,C,T]."""

    def pre_process(self, x, it):
        if x.ndim == 2:
            return x[:, :, None]
        return jnp.swapaxes(x, 1, 2)

    def output_type(self, it):
        return InputType.recurrent(it.flat_size())


def infer_preprocessor(prev: InputType, layer: Layer) -> Optional[InputPreProcessor]:
    """Auto-insertion logic (MultiLayerConfiguration inputPreProcessor
    inference via InputType.getPreProcessorForInputType)."""
    wants_ff = isinstance(
        layer, (DenseLayer, EmbeddingLayer)
    ) and not isinstance(layer, (RnnOutputLayer, EmbeddingSequenceLayer))
    wants_cnn = isinstance(layer, (ConvolutionLayer, SubsamplingLayer, Upsampling2D, ZeroPaddingLayer, LocalResponseNormalization))
    wants_rnn = isinstance(layer, (LSTM, SimpleRnn, Bidirectional, RnnOutputLayer))
    if prev.kind in ("cnn", "cnn3d") and wants_ff:
        return CnnToFeedForwardPreProcessor()
    if prev.kind == "cnnflat" and wants_cnn:
        return FeedForwardToCnnPreProcessor(prev.height, prev.width, prev.channels)
    if prev.kind == "rnn" and wants_ff:
        return RnnToFeedForwardPreProcessor()
    if prev.kind == "ff" and wants_rnn:
        return FeedForwardToRnnPreProcessor()
    return None


# ------------------------------------------------- NeuralNetConfiguration


@dataclass
class MultiLayerConfiguration:
    """org.deeplearning4j.nn.conf.MultiLayerConfiguration."""

    layers: List[Layer] = field(default_factory=list)
    input_type: Optional[InputType] = None
    preprocessors: Dict[int, InputPreProcessor] = field(default_factory=dict)
    seed: int = 0
    updater: IUpdater = field(default_factory=lambda: Sgd(0.1))
    dtype: str = "float32"
    tbptt_fwd_length: int = 0
    tbptt_back_length: int = 0
    backprop_type: str = "Standard"  # Standard | TruncatedBPTT
    gradient_normalization: Optional[str] = None  # ClipL2PerLayer|ClipElementWiseAbsoluteValue|ClipL2PerParamType
    gradient_normalization_threshold: float = 1.0
    mini_batch: bool = True

    def input_types(self) -> List[InputType]:
        """Per-layer input InputType after preprocessor application."""
        its = []
        it = self.input_type
        if it is None and self.layers:
            # DL4J allows omitting setInputType when the first layer declares
            # nIn explicitly — synthesize the InputType from it
            first = self.layers[0]
            if isinstance(first, Bidirectional):
                n_in = getattr(first.fwd, "n_in", 0)
                if n_in:
                    it = InputType.recurrent(n_in)
            elif isinstance(first, (ConvolutionLayer, SubsamplingLayer, Upsampling2D,
                                    ZeroPaddingLayer, LocalResponseNormalization)):
                # nIn alone cannot recover spatial dims for CNN inputs
                raise ValueError(
                    "first layer is convolutional: call "
                    ".set_input_type(InputType.convolutional(h, w, c))")
            else:
                n_in = getattr(first, "n_in", 0)
                if n_in:
                    if isinstance(first, (LSTM, SimpleRnn, EmbeddingSequenceLayer)):
                        it = InputType.recurrent(n_in)
                    else:
                        it = InputType.feed_forward(n_in)
        for i, layer in enumerate(self.layers):
            if i in self.preprocessors:
                it = self.preprocessors[i].output_type(it)
            its.append(it)
            it = layer.output_type(it)
        return its

    def to_json(self) -> str:
        d = {
            "layers": [l.to_json() for l in self.layers],
            "input_type": self.input_type.to_json() if self.input_type else None,
            "preprocessors": {str(k): type(v).__name__ for k, v in self.preprocessors.items()},
            "preprocessor_args": {
                str(k): dataclasses.asdict(v) for k, v in self.preprocessors.items()
            },
            "seed": self.seed,
            "updater": self.updater.to_json(),
            "dtype": self.dtype,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "backprop_type": self.backprop_type,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
        }
        return json.dumps(d, indent=2)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        layers = [Layer.from_json(ld) for ld in d["layers"]]
        it = None
        if d.get("input_type"):
            itd = d["input_type"]
            it = InputType(**itd)
        pre = {}
        for k, name in d.get("preprocessors", {}).items():
            args = d.get("preprocessor_args", {}).get(k, {})
            pre[int(k)] = PREPROCESSOR_REGISTRY[name](**args)
        return MultiLayerConfiguration(
            layers=layers,
            input_type=it,
            preprocessors=pre,
            seed=d.get("seed", 0),
            updater=IUpdater.from_json(d["updater"]),
            dtype=d.get("dtype", "float32"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 0),
            tbptt_back_length=d.get("tbptt_back_length", 0),
            backprop_type=d.get("backprop_type", "Standard"),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
        )


class ListBuilder:
    """NeuralNetConfiguration.ListBuilder — .layer(i, conf) chain →
    MultiLayerConfiguration with cascaded defaults."""

    def __init__(self, base: "NeuralNetConfiguration"):
        self._base = base
        self._layers: List[Layer] = []
        self._input_type: Optional[InputType] = None
        self._preprocessors: Dict[int, InputPreProcessor] = {}
        self._tbptt_fwd = 0
        self._tbptt_back = 0
        self._backprop_type = "Standard"

    def layer(self, *args) -> "ListBuilder":
        l = args[-1]
        self._layers.append(l)
        return self

    def set_input_type(self, it: InputType) -> "ListBuilder":
        self._input_type = it
        return self

    setInputType = set_input_type

    def input_pre_processor(self, index: int, pre: InputPreProcessor) -> "ListBuilder":
        self._preprocessors[index] = pre
        return self

    def backprop_type(self, t: str) -> "ListBuilder":
        self._backprop_type = t
        return self

    def t_bptt_length(self, fwd: int, back: Optional[int] = None) -> "ListBuilder":
        self._tbptt_fwd = fwd
        self._tbptt_back = back if back is not None else fwd
        self._backprop_type = "TruncatedBPTT"
        return self

    tBPTTLength = t_bptt_length

    def build(self) -> MultiLayerConfiguration:
        b = self._base
        # cascade global defaults into layers (NeuralNetConfiguration semantics)
        for l in self._layers:
            if l.updater is None:
                l.updater = b.updater_
            if l.weight_init == "xavier" and b.weight_init_ != "xavier":
                l.weight_init = b.weight_init_
            if l.l1 == 0.0:
                l.l1 = b.l1_
            if l.l2 == 0.0:
                l.l2 = b.l2_
            if l.dropout == 0.0 and b.dropout_ != 0.0:
                l.dropout = b.dropout_
            if l.activation == "identity" and b.activation_ is not None and not isinstance(
                l, (OutputLayer, LossLayer, SubsamplingLayer, BatchNormalization)
            ):
                l.activation = b.activation_
        conf = MultiLayerConfiguration(
            layers=self._layers,
            input_type=self._input_type,
            preprocessors=dict(self._preprocessors),
            seed=b.seed_,
            updater=b.updater_,
            dtype=b.dtype_,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            backprop_type=self._backprop_type,
            gradient_normalization=b.grad_norm_,
            gradient_normalization_threshold=b.grad_norm_threshold_,
            mini_batch=b.mini_batch_,
        )
        # auto-insert preprocessors where InputType demands (setInputType logic)
        if conf.input_type is not None:
            it = conf.input_type
            for i, layer in enumerate(conf.layers):
                if i in conf.preprocessors:
                    it = conf.preprocessors[i].output_type(it)
                else:
                    pre = infer_preprocessor(it, layer)
                    if pre is not None:
                        conf.preprocessors[i] = pre
                        it = pre.output_type(it)
                it = layer.output_type(it)
        return conf


class NeuralNetConfiguration:
    """org.deeplearning4j.nn.conf.NeuralNetConfiguration.Builder."""

    class Builder:
        def __init__(self):
            self.seed_ = 0
            self.updater_ = Sgd(0.1)
            self.weight_init_ = "xavier"
            self.activation_ = None
            self.l1_ = 0.0
            self.l2_ = 0.0
            self.dropout_ = 0.0
            self.dtype_ = "float32"
            self.grad_norm_ = None
            self.grad_norm_threshold_ = 1.0
            self.mini_batch_ = True

        def seed(self, s: int):
            self.seed_ = int(s)
            return self

        def updater(self, u: IUpdater):
            self.updater_ = u
            return self

        def weight_init(self, w: str):
            self.weight_init_ = str(w).lower()
            return self

        weightInit = weight_init

        def activation(self, a: str):
            self.activation_ = str(a).lower()
            return self

        def l1(self, v: float):
            self.l1_ = v
            return self

        def l2(self, v: float):
            self.l2_ = v
            return self

        def dropout(self, keep_prob: float):
            self.dropout_ = keep_prob
            return self

        dropOut = dropout

        def data_type(self, dt: str):
            self.dtype_ = dt
            return self

        def gradient_normalization(self, gn: str, threshold: float = 1.0):
            self.grad_norm_ = gn
            self.grad_norm_threshold_ = threshold
            return self

        def mini_batch(self, b: bool):
            self.mini_batch_ = b
            return self

        def list(self) -> ListBuilder:
            return ListBuilder(self)

        def graph_builder(self):
            from .graph_conf import GraphBuilder

            return GraphBuilder(self)

        graphBuilder = graph_builder


LAYER_REGISTRY = {
    c.__name__: c
    for c in (
        DenseLayer,
        OutputLayer,
        LossLayer,
        ActivationLayer,
        DropoutLayer,
        ConvolutionLayer,
        Deconvolution2D,
        DepthwiseConvolution2D,
        SeparableConvolution2D,
        SubsamplingLayer,
        Upsampling2D,
        ZeroPaddingLayer,
        BatchNormalization,
        LocalResponseNormalization,
        EmbeddingLayer,
        EmbeddingSequenceLayer,
        LSTM,
        GravesLSTM,
        SimpleRnn,
        Bidirectional,
        LastTimeStep,
        RnnOutputLayer,
        GlobalPoolingLayer,
    )
}

PREPROCESSOR_REGISTRY = {
    c.__name__: c
    for c in (
        CnnToFeedForwardPreProcessor,
        FeedForwardToCnnPreProcessor,
        RnnToFeedForwardPreProcessor,
        FeedForwardToRnnPreProcessor,
    )
}

# Forward-declare for nn/__init__ imports
ComputationGraphConfiguration = None  # replaced by graph_conf import at package init
