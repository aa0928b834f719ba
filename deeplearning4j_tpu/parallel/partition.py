"""Sharded-parameter training: FSDP × tensor-parallel mesh layouts (ISSUE 9).

The data-parallel gang (ParallelTrainer / MultiProcessTrainer) replicates
every parameter and optimizer slot on every rank, capping model size at one
chip's HBM. This module is the partitioner that lifts that cap:

- :class:`SpecLayout` — an axis map over a ``data``/``fsdp``/``tp`` mesh
  that assigns a ``PartitionSpec`` to every parameter by LAYER ROLE
  (embedding tables, dense/projection kernels, norms, biases — the role
  vocabulary lives in ``nn.conf``; layers tag their own params via
  ``Layer.param_roles``). ``fsdp`` shards parameter/optimizer STORAGE
  (ZeRO-3: GSPMD all-gathers shards for compute and reduce-scatters the
  gradients); ``tp`` shards a single layer's math (Megatron), and WHICH dim
  of a kernel it takes follows from the side of the pair the kernel stands
  on: the first of a pair (``kernel``: ``qkv_w``, ``ffn_w1``) splits its
  output features, the second (``kernel_row``: ``out_w``, ``ffn_w2``) its
  input features, so the activation between them stays split and the only
  collective of the pair is one sum of its output. A kernel between two
  whole activations (``kernel_whole``: the transformer head's ``mlm/w``)
  is not split over ``tp`` at all.
- :class:`Partitioner` — applies a layout to a network: places the param
  pytree per-spec, shards optimizer state identically to its params,
  replicates batch-norm state, and publishes ``tdl_param_bytes_per_rank`` /
  ``tdl_mesh_layout_info`` so per-rank memory is observable. Placement goes
  through ``jax.make_array_from_callback`` (each process materializes only
  its addressable shards), so the same code path works single-process and
  across a multi-process gang.

Updates happen IN PLACE on the shards: the fused train steps donate
(params, opt-state) buffers (``donate_argnums`` on every ``jax.jit`` — the
AST lint in tests/test_partition.py enforces it), and a donated sharded
buffer is reused shard-by-shard by XLA.

The reference (DL4J ``SharedTrainingMaster``) never had this — gradient
sharing replicates parameter state by construction (see PARITY.md "Sharded
training"); this is where tdl goes past parity.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.conf import (ROLE_BIAS, ROLE_BIAS_COLUMN, ROLE_EMBEDDING,
                       ROLE_KERNEL, ROLE_KERNEL_ROW, ROLE_KERNEL_WHOLE,
                       ROLE_NORM, classify_param_tree)
from .mesh import AXIS_DATA, AXIS_FSDP, AXIS_PIPE, AXIS_TP, mesh_from_shape

ROLES = (ROLE_EMBEDDING, ROLE_KERNEL, ROLE_KERNEL_ROW, ROLE_KERNEL_WHOLE,
         ROLE_NORM, ROLE_BIAS, ROLE_BIAS_COLUMN)


@dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs over a ``data × fsdp × tp`` mesh.

    Axis sizes define the mesh shape (one may be -1 to absorb the remaining
    devices; size-1 axes are kept so the spec vocabulary stays valid on any
    topology). Role policy:

    - ``embedding`` tables: leading (vocab/class) dim over ``fsdp×tp``
      combined — the widest dim of the widest tables.
    - ``kernel`` matrices: dim 0 (input features / out-channels) over
      ``fsdp``, dim 1 over ``tp`` — the first of a tensor-parallel pair,
      whose output leaves split over its features.
    - ``kernel_row`` matrices, the second of a pair (their input IS that
      split activation): dim 0, the contraction, over ``tp``; ``fsdp`` keeps
      the dim ``tp`` does not take. The product is a partial sum, added up
      once over ``tp``; nothing is gathered in between.
    - ``kernel_whole`` matrices (whole activation in, whole out): dim 0 over
      ``fsdp``, nothing over ``tp``.
    - ``norm`` / ``bias`` vectors: over ``fsdp`` (ZeRO-3 shards everything;
      GSPMD all-gathers them for compute), never over ``tp``.
    - ``bias_column`` vectors (a first-of-pair kernel's bias): over ``tp``
      like that kernel's columns, then ``fsdp``.

    A dim that an axis does not divide falls back per-axis (see
    :meth:`Partitioner.spec_tree`) — same "shard what fits" behavior GSPMD
    applies to activations — so a 3-class head never wedges a layout.

    ``pipe`` (ISSUE 19) adds the depth axis: layer stacks are partitioned
    into ``pipe`` stages, each stage owning a contiguous block of layers
    (and their optimizer slots). A ``pipe=1`` layout keeps the exact
    pre-pipe mesh/describe() identity, so existing checkpoints and gangs
    see no change; ``pipe>1`` puts the pipe axis OUTERMOST (stage hops are
    the rarest collective — one activation ppermute per microbatch tick).
    """

    data: int = 1
    fsdp: int = -1
    tp: int = 1
    pipe: int = 1
    data_axis: str = AXIS_DATA
    fsdp_axis: str = AXIS_FSDP
    tp_axis: str = AXIS_TP
    pipe_axis: str = AXIS_PIPE

    # ------------------------------------------------------------------ mesh

    def shape(self) -> Dict[str, int]:
        base = {self.data_axis: self.data, self.fsdp_axis: self.fsdp,
                self.tp_axis: self.tp}
        if self.pipe != 1:
            # pipe outermost; omitted entirely at size 1 so pipe-less
            # layouts keep their exact historical mesh + manifest identity
            return {self.pipe_axis: self.pipe, **base}
        return base

    def build_mesh(self, devices: Optional[Sequence] = None) -> Mesh:
        return mesh_from_shape(self.shape(), devices=devices)

    # ----------------------------------------------------------- role → spec

    def embedding(self, ndim: int = 2) -> P:
        return P((self.fsdp_axis, self.tp_axis), *([None] * (ndim - 1)))

    def kernel(self, ndim: int = 2) -> P:
        if ndim < 2:
            return self.bias() if ndim == 1 else P()
        return P(self.fsdp_axis, self.tp_axis, *([None] * (ndim - 2)))

    def kernel_row(self, ndim: int = 2) -> P:
        if ndim < 2:
            return self.kernel(ndim)
        return P(self.tp_axis, self.fsdp_axis, *([None] * (ndim - 2)))

    def norm(self, ndim: int = 1) -> P:
        return P(self.fsdp_axis, *([None] * (ndim - 1))) if ndim else P()

    def bias(self, ndim: int = 1) -> P:
        return self.norm(ndim)

    def bias_column(self, ndim: int = 1) -> P:
        if ndim != 1:
            return self.bias(ndim)
        return P((self.tp_axis, self.fsdp_axis))

    def spec_for(self, role: Optional[str], ndim: int) -> Optional[P]:
        """Untrimmed spec for one leaf; None = uncovered role (the caller
        decides whether that is an error — Partitioner's strict mode — or a
        reported replicated fallback)."""
        if ndim == 0:
            return P()
        if role == ROLE_EMBEDDING:
            return self.embedding(ndim)
        if role == ROLE_KERNEL:
            return self.kernel(ndim)
        if role == ROLE_KERNEL_ROW:
            return self.kernel_row(ndim)
        if role == ROLE_BIAS_COLUMN:
            return self.bias_column(ndim)
        if role in (ROLE_NORM, ROLE_BIAS, ROLE_KERNEL_WHOLE):
            return self.norm(ndim)
        return None

    # ------------------------------------------------------------- manifests

    def describe(self, mesh: Optional[Mesh] = None) -> Dict[str, Any]:
        """JSON-able layout identity for checkpoint manifests. Axis sizes are
        RESOLVED against the mesh (fsdp=-1 → the absorbed size), so two
        layouts compare equal iff a checkpoint written under one restores
        shard-for-shard under the other."""
        sizes = dict(mesh.shape) if mesh is not None else self.shape()
        out = {"axes": {"data": int(sizes.get(self.data_axis, self.data)),
                        "fsdp": int(sizes.get(self.fsdp_axis, self.fsdp)),
                        "tp": int(sizes.get(self.tp_axis, self.tp))},
               "axis_names": [self.data_axis, self.fsdp_axis, self.tp_axis]}
        pipe = int(sizes.get(self.pipe_axis, self.pipe))
        if pipe != 1:
            # pipe-less layouts keep the exact historical (3-axis) identity
            # so every pre-pipe checkpoint still compares equal on restore
            out["axes"]["pipe"] = pipe
            out["axis_names"] = [self.pipe_axis] + out["axis_names"]
        return out


def largest_layout(n_devices: int, tp: int = 1, data: int = 1,
                   pipe: int = 1) -> SpecLayout:
    """The largest valid :class:`SpecLayout` for a device count (ISSUE 14 —
    what an elastically-resized gang builds for its survivor count): ``fsdp``
    absorbs every device not claimed by ``pipe``/``data``/``tp``; a requested
    ``pipe``/``data``/``tp`` that does not divide falls back to its largest
    feasible divisor, never an invalid mesh. ``pipe`` is claimed FIRST — a
    resized gang keeps its stage count whenever the survivors can still hold
    it (ISSUE 19: the re-partitioned stages restore cross-topology)."""
    n = max(1, int(n_devices))
    pipe = max(1, int(pipe))
    while n % pipe:
        pipe -= 1
    rest = n // pipe
    data = max(1, int(data))
    while rest % data:
        data -= 1
    tp = max(1, min(int(tp), rest // data))
    while (rest // data) % tp:
        tp -= 1
    return SpecLayout(data=data, fsdp=rest // (data * tp), tp=tp, pipe=pipe)


# ------------------------------------------------------------------ role trees


def param_role_tree(net) -> Any:
    """Role tree mirroring ``net.params_`` for MultiLayerNetwork (layer-index
    keys) and ComputationGraph (node-name keys; parameterized vertices fall
    back to name classification). Plain dict/list param trees (functional
    models like models.transformer) classify by leaf name."""
    layers = _net_layer_map(net)
    if layers is None:
        return classify_param_tree(net if isinstance(net, (dict, list, tuple))
                                   else net.params_)
    roles = {}
    for key, sub in net.params_.items():
        layer = layers.get(key)
        if layer is not None and hasattr(layer, "param_roles"):
            roles[key] = layer.param_roles(sub)
        else:  # graph vertex (AttentionVertex et al.): canonical names
            roles[key] = classify_param_tree(sub)
    return roles


def _net_layer_map(net) -> Optional[Dict[str, Any]]:
    conf = getattr(net, "conf", None)
    if conf is None:
        return None
    if hasattr(conf, "layers"):          # MultiLayerNetwork
        return {str(i): l for i, l in enumerate(conf.layers)}
    if hasattr(conf, "nodes"):           # ComputationGraph
        return {name: node.layer for name, node in conf.nodes.items()}
    return None


def uncovered_params(params, roles) -> List[str]:
    """Leaf paths whose role is None — the params a layout would silently
    replicate. The bundled-model coverage gate asserts this is empty."""
    out: List[str] = []

    def walk(p, r, prefix):
        if isinstance(p, dict):
            for k in p:
                walk(p[k], r[k] if isinstance(r, dict) else None, f"{prefix}{k}/")
        elif isinstance(p, (list, tuple)):
            for i, v in enumerate(p):
                sub = r[i] if isinstance(r, (list, tuple)) else None
                walk(v, sub, f"{prefix}{i}/")
        elif r is None:
            out.append(prefix[:-1])

    walk(params, roles, "")
    return out


# ----------------------------------------------------------------- partitioner


@dataclass
class PartitionReport:
    """What one partition pass did — the observable contract of ISSUE 9."""

    params_bytes_total: int
    params_bytes_per_rank: int
    opt_bytes_per_rank: int
    per_device_params_bytes: int     # max over this process's devices
    uncovered: List[str]             # role=None paths (strict mode raises)
    replicated_fallback: List[str]   # covered but nothing divides → P()
    specs: Any                       # trimmed spec tree actually applied


class Partitioner:
    """Applies a :class:`SpecLayout` to param/optimizer pytrees on a mesh.

    ``strict=True`` (default) refuses to place a tree containing uncovered
    params — silent replication of an unmatched param is exactly the failure
    mode the coverage gate exists to catch. Divisibility fallback is not an
    error: it is reported per-path in :class:`PartitionReport`.
    """

    def __init__(self, layout: SpecLayout, mesh: Optional[Mesh] = None,
                 strict: bool = True):
        self.layout = layout
        self.mesh = mesh if mesh is not None else layout.build_mesh()
        self.strict = strict
        axes = [layout.data_axis, layout.fsdp_axis, layout.tp_axis]
        if layout.pipe != 1:
            axes.insert(0, layout.pipe_axis)
        for ax in axes:
            if ax not in self.mesh.shape:
                raise ValueError(
                    f"mesh {dict(self.mesh.shape)} lacks layout axis {ax!r}")

    # ------------------------------------------------------------ spec trees

    def describe(self) -> Dict[str, Any]:
        return self.layout.describe(self.mesh)

    def _trim(self, shape: Tuple[int, ...], spec: P) -> P:
        """Per-dim, per-axis divisibility fallback: keep only the spec axes
        whose (cumulative) product divides that dim."""
        dims = []
        for d, axes in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
            if axes is None:
                dims.append(None)
                continue
            kept, prod = [], 1
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                size = self.mesh.shape[ax]
                if shape[d] % (prod * size) == 0:
                    kept.append(ax)
                    prod *= size
            dims.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
        while dims and dims[-1] is None:  # canonical form: no trailing Nones
            dims.pop()
        return P(*dims)

    def spec_tree(self, params, roles: Optional[Any] = None,
                  report: Optional[dict] = None) -> Any:
        """Trimmed PartitionSpec tree for ``params`` (roles default to name
        classification). ``report`` (if given) collects ``uncovered`` and
        ``replicated_fallback`` path lists."""
        roles = roles if roles is not None else classify_param_tree(params)
        uncovered: List[str] = []
        fallback: List[str] = []

        def walk(p, r, prefix):
            if isinstance(p, dict):
                return {k: walk(p[k], r[k] if isinstance(r, dict) else None,
                                f"{prefix}{k}/")
                        for k in p}
            if isinstance(p, (list, tuple)):
                return type(p)(
                    walk(v, r[i] if isinstance(r, (list, tuple)) else None,
                         f"{prefix}{i}/")
                    for i, v in enumerate(p))
            path = prefix[:-1]
            ndim = int(np.ndim(p))
            spec = self.layout.spec_for(r, ndim)
            if spec is None:
                uncovered.append(path)
                return P()
            trimmed = self._trim(np.shape(p), spec)
            if ndim > 0 and all(a is None for a in trimmed) and \
                    not all(a is None for a in spec):
                fallback.append(path)
            return trimmed

        specs = walk(params, roles, "")
        if report is not None:
            report["uncovered"] = uncovered
            report["replicated_fallback"] = fallback
        if self.strict and uncovered:
            raise ValueError(
                "SpecLayout does not cover these params (unknown role — "
                "tag them via Layer.param_roles / nn.conf._PARAM_NAME_ROLES "
                f"instead of silently replicating): {uncovered}")
        return specs

    # ------------------------------------------------------------- placement

    def sharding_for(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _place_leaf(self, leaf, spec: P):
        if not hasattr(leaf, "dtype"):
            return leaf
        sharding = self.sharding_for(spec)
        if isinstance(leaf, jax.Array) and leaf.sharding == sharding:
            return leaf  # already placed (e.g. a sharded checkpoint restore)
        # the input here is a host array or a replicated leaf (the
        # replicated→sharded upgrade path) — a DIFFERENTLY-sharded source
        # never routes through placement; it restores via the chunk-
        # intersection path in serde.checkpoint instead
        host = np.asarray(leaf)  # gather-ok: host/replicated input only
        # each process materializes only its addressable shards — works
        # identically on a single-process mesh and across a gang (where
        # jax.device_put cannot address non-local devices)
        return jax.make_array_from_callback(host.shape, sharding,
                                            lambda idx: host[idx])

    def place(self, tree, specs) -> Any:
        return _tree_map_specs(self._place_leaf, tree, specs)

    @staticmethod
    def state_spec_tree(state, param_specs) -> Any:
        """Spec tree for optimizer state: subtrees that mirror the param
        tree STRUCTURALLY (Adam m/v, Nesterovs v, AdaGrad accumulators …)
        take the params' specs; anything else replicates. The ONE mirror-
        match rule — both placement (shard_state_like) and checkpoint
        restore (state_specs) derive from it, so training placement and the
        restore contract cannot drift apart."""
        pstruct = jax.tree.structure(param_specs, is_leaf=_is_spec)
        if not isinstance(state, dict):
            return _rep_specs(state)
        return {k: (param_specs if jax.tree.structure(sub) == pstruct
                    else _rep_specs(sub))
                for k, sub in state.items()}

    def shard_state_like(self, state, param_specs):
        return self.place(state, self.state_spec_tree(state, param_specs))

    def state_specs(self, net) -> Dict[str, Any]:
        """{'params','updater','bn'} spec trees for a net's full train state
        — the layout contract TrainingCheckpointer restores against."""
        pspecs = self.spec_tree(net.params_, param_role_tree(net))
        return {"params": pspecs,
                "updater": self.state_spec_tree(net.updater_state, pspecs),
                "bn": _rep_specs(net.bn_state)}

    # ----------------------------------------------------------- whole-net

    def partition_net(self, net) -> PartitionReport:
        """Place a network's (params, opt-state, bn-state) per the layout and
        publish the per-rank byte gauges. Optimizer state shards identically
        to its params; bn running stats replicate (they are per-feature host
        of the norm role but tiny and read by every shard group)."""
        rep: dict = {}
        roles = param_role_tree(net)
        specs = self.spec_tree(net.params_, roles, report=rep)
        net.params_ = self.place(net.params_, specs)
        net.updater_state = self.shard_state_like(net.updater_state, specs)
        net.bn_state = self.place(net.bn_state, _rep_specs(net.bn_state))
        return self.report(net.params_, net.updater_state, specs,
                           uncovered=rep["uncovered"],
                           fallback=rep["replicated_fallback"])

    def report(self, params, opt_state=None, specs=None,
               uncovered=(), fallback=()) -> PartitionReport:
        """Byte accounting + metric publication for already-placed trees."""
        from ..monitoring.partition import partition_metrics

        total = sum(int(getattr(l, "nbytes", 0))
                    for l in jax.tree.leaves(params))
        per_rank = addressable_nbytes(params)
        opt_rank = addressable_nbytes(opt_state) if opt_state is not None else 0
        per_dev: Dict[Any, int] = {}
        for leaf in jax.tree.leaves(params):
            if hasattr(leaf, "addressable_shards"):
                for sh in leaf.addressable_shards:
                    per_dev[sh.device] = per_dev.get(sh.device, 0) + int(sh.data.nbytes)
        m = partition_metrics()
        m.param_bytes.labels("params").set(per_rank)
        m.param_bytes.labels("opt_state").set(opt_rank)
        d = self.describe()["axes"]
        m.layout_info.clear_children()
        m.layout_info.labels(str(d["data"]), str(d["fsdp"]),
                             str(d["tp"])).set(self.mesh.devices.size)
        return PartitionReport(
            params_bytes_total=total, params_bytes_per_rank=per_rank,
            opt_bytes_per_rank=opt_rank,
            per_device_params_bytes=max(per_dev.values(), default=per_rank),
            uncovered=list(uncovered), replicated_fallback=list(fallback),
            specs=specs)


class PipelinePartitioner(Partitioner):
    """Partitioner for the CANONICAL pipeline train state (ISSUE 19).

    The pipeline trainer keeps params in canonical form — ``{"embed": ...,
    "blocks": <stacked leaves, leading dim = n_layers>, "mlm": ...}`` — and
    builds the per-stage view INSIDE the compiled step (a static gather the
    cost partitioner's boundaries parameterize). Storage therefore shards on
    the LAYER dim: over ``pipe`` when the layout has one (each stage's HBM
    holds only its own layers + optimizer slots), else over ``fsdp`` (the
    same leading-dim chunks — which is exactly why a ``pipe=2`` checkpoint
    restores onto an ``fsdp=2`` layout bitwise through the chunk-intersection
    reshard path). ``embed``/``mlm`` replicate (small; GSPMD dp-shards their
    compute via the batch).

    Role classification is bypassed on purpose: the canonical tree's layout
    contract is positional (dim 0 = layer), not role-shaped, and the ONE
    describe()/state_specs surface the checkpoint lineage consumes is
    inherited unchanged from :class:`Partitioner`.
    """

    BLOCKS_KEY = "blocks"

    def _depth_axis(self) -> str:
        return (self.layout.pipe_axis if self.layout.pipe != 1
                else self.layout.fsdp_axis)

    def spec_tree(self, params, roles: Optional[Any] = None,
                  report: Optional[dict] = None) -> Any:
        ax = self._depth_axis()

        def leaf_spec(in_blocks: bool, leaf) -> P:
            ndim = int(np.ndim(leaf))
            if not in_blocks or ndim == 0:
                return P()
            return self._trim(np.shape(leaf), P(ax, *([None] * (ndim - 1))))

        def walk(p, in_blocks):
            if isinstance(p, dict):
                return {k: walk(v, in_blocks or k == self.BLOCKS_KEY)
                        for k, v in p.items()}
            if isinstance(p, (list, tuple)):
                return type(p)(walk(v, in_blocks) for v in p)
            return leaf_spec(in_blocks, p)

        specs = walk(params, False)
        if report is not None:
            report["uncovered"] = []
            report["replicated_fallback"] = []
        return specs


# ------------------------------------------------------------------- helpers


def _is_spec(x) -> bool:
    return isinstance(x, P)


def _rep_specs(tree):
    return jax.tree.map(lambda _: P(), tree)


def _tree_map_specs(fn, tree, specs):
    return jax.tree.map(lambda l, s: fn(l, s), tree, specs, is_leaf=_is_spec)


def addressable_nbytes(tree) -> int:
    """Bytes this PROCESS actually holds for a placed tree: the sum over its
    addressable shards (a replicated leaf counts once per local device — that
    is real HBM). Host/numpy leaves count their full size."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "addressable_shards"):
            total += sum(int(sh.data.nbytes) for sh in leaf.addressable_shards)
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total
