"""Distributed training drivers.

Reference parity (SURVEY §2.6, §3.4):
- ``ParameterAveragingTrainingMaster`` (S2): synchronous DP where workers fit
  locally for ``averaging_frequency`` minibatches, then params (and
  optionally updater state) are averaged. Semantics preserved here with
  logical workers; on TPU hardware per-step sync DP is strictly better, so
  this exists for capability/semantics parity and for its actual algorithmic
  effect (local SGD / post-local averaging).
- ``SharedTrainingMaster`` (S3): the Aeron threshold-encoded async gradient
  mesh. On TPU its entire data plane collapses into the compiled step's ICI
  allreduce (§3.4 'TPU mapping'), so this class IS synchronous sharded DP;
  the threshold codecs live in ``parallel.compression`` for the optional
  cross-slice DCN mode.
- ``ParallelTrainer``: the TPU-native engine both masters delegate to — one
  jit-compiled train step with batch sharded over the mesh data axis; GSPMD
  inserts the gradient allreduce.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import faults
from ..data.dataset import DataSet
from ..monitoring import aggregate, flight, heartbeat
from ..monitoring.registry import get_registry
from ..monitoring.trace import StepPhaseRecorder
from .mesh import AXIS_DATA, build_mesh


def _trainer_metrics():
    """Shared metric families for every trainer class (get-or-create)."""
    r = get_registry()
    return (
        r.histogram("tdl_parallel_step_seconds",
                    "Host-observed wall time of one distributed fit-batch "
                    "dispatch (async: excludes device completion)",
                    labels=("trainer",)),
        r.counter("tdl_collective_bytes_total",
                  "Logical payload bytes moved by training collectives",
                  labels=("trainer", "kind")),
        r.gauge("tdl_parallel_devices", "Devices participating in the mesh",
                labels=("trainer",)),
        r.histogram("tdl_step_wall_seconds",
                    "Iteration-to-iteration wall time, including everything "
                    "between steps (checkpoint IO, input stalls, barriers) — "
                    "the per-rank signal the aggregated /metrics derives "
                    "straggler skew from",
                    labels=("trainer",)),
    )


class ParallelTrainer:
    """Synchronous data-parallel trainer over a mesh data axis.

    Params/updater/bn state are replicated; each batch is sharded on its
    leading dim. The network's own compiled train step is reused — GSPMD
    turns the (replicated-param, sharded-batch) layout into per-device
    partial gradients + ICI allreduce automatically.
    """

    #: whether this trainer runs the microbatch schedule a SpecLayout pipe
    #: axis implies; parallel.pipeline.PipelineParallelTrainer flips it
    _supports_pipe = False

    def __init__(self, net, mesh: Optional[Mesh] = None, data_axis: str = AXIS_DATA,
                 sharding_rules=None, mesh_layout=None, bucketing=None):
        # persistent executable cache: a respawned gang rank constructs its
        # trainer before its first compile, so enabling here restores its
        # executables from disk instead of recompiling
        from ..common import compile_cache

        compile_cache.enable()
        self.net = net
        if bucketing is not None:
            # ISSUE 12: pad-to-bucket on the fit paths; the mesh-divisibility
            # constraint is folded in below once _ndata is known
            net.set_bucketing(bucketing)
        # ISSUE 9: mesh_layout=SpecLayout(data=D, fsdp=F, tp=T) turns the
        # replicated gang into sharded-parameter training — params AND
        # optimizer state placed per layer role over the fsdp/tp axes, batch
        # still sharded over data. The replicated path (mesh_layout=None)
        # is unchanged and stays the default.
        if mesh_layout is not None and sharding_rules is not None:
            raise ValueError("pass mesh_layout OR sharding_rules, not both")
        self.partitioner = None
        self.partition_report = None
        if mesh_layout is not None:
            from .partition import Partitioner, SpecLayout

            if isinstance(mesh_layout, SpecLayout):
                mesh_layout = Partitioner(mesh_layout, mesh=mesh)
            elif mesh is not None and mesh is not mesh_layout.mesh:
                # a pre-built Partitioner owns its mesh; silently dropping a
                # different explicit mesh would train on the wrong devices
                raise ValueError(
                    "mesh conflicts with mesh_layout's Partitioner mesh — "
                    "pass the mesh to Partitioner(...), or pass a SpecLayout")
            mesh = mesh_layout.mesh
            data_axis = mesh_layout.layout.data_axis
            self.partitioner = mesh_layout
            if (getattr(mesh_layout.layout, "pipe", 1) != 1
                    and not self._supports_pipe):
                # a pipe axis silently treated as extra data/fsdp parallelism
                # would train wrong — only the pipeline trainer runs the
                # microbatch schedule the axis implies
                raise ValueError(
                    f"mesh_layout has a pipe axis (pipe="
                    f"{mesh_layout.layout.pipe}) but {type(self).__name__} "
                    "runs no pipeline schedule — use "
                    "parallel.pipeline.PipelineParallelTrainer")
        self.mesh = mesh or build_mesh(**{data_axis: -1})
        self.data_axis = data_axis
        # VERDICT r2: nets can now train tensor-parallel through the standard
        # fit path — pass a parallel.sharding.ShardingRules and params (and
        # matching updater-state subtrees) are placed per-rule instead of
        # replicated; GSPMD compiles the Megatron collectives into the step.
        self.sharding_rules = sharding_rules
        self._ndata = int(np.prod([self.mesh.shape[a] for a in (data_axis,) if a in self.mesh.shape]))
        self._placed = False
        (self._step_hist, self._coll_bytes, devices_gauge,
         self._step_wall) = _trainer_metrics()
        self._trainer_label = type(self).__name__
        devices_gauge.labels(self._trainer_label).set(self.mesh.devices.size)
        self._grad_bytes: Optional[int] = None
        # ISSUE 7 layer 3: per-step phase attribution (input/h2d/compute/
        # collective) through monitoring.trace — one recorder per trainer,
        # families land in the process registry
        self._phases = StepPhaseRecorder()
        self._last_step_entry: Optional[float] = None

    # -- placement ----------------------------------------------------------

    def _replicate(self, tree):
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _shard(self, x):
        if x is None:
            return None
        with self._phases.phase("h2d"):
            spec = P(self.data_axis, *([None] * (np.ndim(x) - 1)))
            return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def _place_net(self):
        if self._placed:
            return
        n = self.net
        if self.partitioner is not None:
            # sharded-parameter path: params + opt state per layer role over
            # fsdp/tp (a sharded-checkpoint restore already placed them —
            # the partitioner passes equal-sharding leaves through untouched)
            self.partition_report = self.partitioner.partition_net(n)
        elif self.sharding_rules is None:
            n.params_ = self._replicate(n.params_)
            n.updater_state = self._replicate(n.updater_state)
            n.bn_state = self._replicate(n.bn_state)
        else:
            n.params_, specs = self.sharding_rules.shard_tree(n.params_, self.mesh)
            n.updater_state = self._shard_state_like(n.updater_state, specs)
            n.bn_state = self._replicate(n.bn_state)
        self._placed = True

    def _shard_state_like(self, state, param_specs):
        """Shard updater-state subtrees that mirror the param tree (Adam m/v,
        Nesterovs v, …) with the params' specs; replicate anything else."""
        from jax.sharding import PartitionSpec

        is_spec = lambda s: isinstance(s, PartitionSpec)  # noqa: E731
        pstruct = jax.tree.structure(param_specs, is_leaf=is_spec)
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                 param_specs, is_leaf=is_spec)
        if not isinstance(state, dict):
            return self._replicate(state)
        out = {}
        for k, sub in state.items():
            if jax.tree.structure(sub) == pstruct:
                out[k] = jax.device_put(sub, shardings)
            else:
                out[k] = self._replicate(sub)
        return out

    # -- checkpointing ------------------------------------------------------

    def checkpointer(self, directory: str, **kw):
        """A :class:`~deeplearning4j_tpu.serde.checkpoint.TrainingCheckpointer`
        carrying this trainer's partitioner, so sharded gangs save/restore
        per-rank shards with the layout recorded in the manifest (and a
        mismatched-layout restore fails loudly instead of mixing shards)."""
        from ..serde.checkpoint import TrainingCheckpointer

        kw.setdefault("partitioner", self.partitioner)
        return TrainingCheckpointer(directory, **kw)

    # -- input staging ------------------------------------------------------

    def batch_sharding(self):
        """The NamedSharding a minibatch should be placed with: leading
        (batch) dim split over the mesh data axis. Hand this to
        :class:`~deeplearning4j_tpu.data.iterators.DevicePrefetchIterator`
        so batches land pre-sharded in ONE ``device_put`` — the portable
        one-shot redistribution of Rink et al. (arXiv:2112.01075) — and the
        fit loop's placement hook becomes a no-op."""
        from .sharding import batch_sharding

        return batch_sharding(self.mesh, self.data_axis)

    def prefetch(self, iterator, buffer_size: int = 2):
        """Wrap ``iterator`` so the next ``buffer_size`` batches stage to
        the mesh (sharded, asynchronously) while the current step runs."""
        from ..data.iterators import DevicePrefetchIterator

        return DevicePrefetchIterator(iterator, buffer_size=buffer_size,
                                      sharding=self.batch_sharding())

    # -- sharded ETL (ISSUE 6) ----------------------------------------------

    def _etl_rank_world(self):
        """(rank, world_size) for per-rank input sharding — single-process
        trainers own the whole stream; MultiProcessTrainer overrides."""
        return 0, 1

    #: whether this trainer's ``prefetch()`` wrapper buffers HOST views
    #: across ``base.next()`` calls. DevicePrefetchIterator stages each
    #: batch to device inside ``_stage`` BEFORE queueing it, so the shm
    #: ring view is done with by the time the next slot is released —
    #: zero-copy is safe. MultiProcessTrainer's plain AsyncDataSetIterator
    #: queues the raw views (see its override), where zero-copy would let
    #: workers overwrite still-buffered batches in place.
    _prefetch_buffers_host_views = False

    def sharded_etl(self, spec, num_workers=None, ring_slots=None,
                    prefetch: int = 2):
        """Build this rank's slice of a multi-process ETL pipeline: the spec
        is re-ranked to THIS trainer's (rank, world_size) — so each gang
        member's worker pool decodes only its ``rank/world_size`` batches,
        deterministically across GangSupervisor restarts — and wrapped in
        the trainer's device prefetcher (``prefetch=0`` returns the bare
        :class:`~deeplearning4j_tpu.data.etl_service.EtlDataSetIterator`,
        e.g. to ``set_state`` before fitting). Zero-copy ring views are
        only handed out when the prefetch wrapper consumes each batch
        before requesting the next (see ``_prefetch_buffers_host_views``)."""
        from ..data.etl_service import EtlDataSetIterator

        spec = spec.for_rank(*self._etl_rank_world())
        zero_copy = not (prefetch and self._prefetch_buffers_host_views)
        it = EtlDataSetIterator(spec, num_workers=num_workers,
                                ring_slots=ring_slots, zero_copy=zero_copy)
        return self.prefetch(it, buffer_size=prefetch) if prefetch else it

    # -- fit ----------------------------------------------------------------

    def fit(self, iterator, epochs: int = 1, prefetch: int = 0):
        """``prefetch=K`` overlaps host ETL + h2d staging of the next K
        batches with device execution (0 = synchronous staging, the
        pre-device-pipeline behavior)."""
        self._place_net()
        if prefetch:
            iterator = self.prefetch(iterator, buffer_size=prefetch)
        try:
            for _ in range(epochs):
                batches = iter(iterator)
                while True:
                    # pulling the next batch is the step's "input" phase —
                    # ≈0 when prefetch keeps the chip fed, the whole stall
                    # when ETL/decode is the wall
                    with self._phases.phase("input"):
                        try:
                            ds = next(batches)
                        except StopIteration:
                            break
                    self._fit_batch(ds)
                # the exhausting next() recorded an input slice belonging to
                # no step — don't smear it into the next epoch's first step
                self._phases.discard()
                self.net.epoch += 1
        finally:
            # join async prefetch workers even when a step raises — a
            # crashed rank must not leak the staging thread (or a restart-
            # safe ETL base's worker processes) until GC
            from ..data.iterators import AsyncDataSetIterator

            if isinstance(iterator, AsyncDataSetIterator):
                iterator.close()
            # last spool carries the final counters (no-op unsupervised)
            aggregate.maybe_spool(force=True)
            flight.flush()
        return self.net

    def _bucket_multiple(self) -> int:
        """Divisibility the bucket must satisfy: the whole data-axis size
        here (single process feeds the whole global batch); the PER-PROCESS
        share on MultiProcessTrainer (each rank feeds only its local shard —
        folding the global size there would over-pad every ragged tail by
        up to process_count x)."""
        return self._ndata

    def _bucket_for_mesh(self, ds):
        """Pad ``ds`` to the net's bucket spec with the mesh divisibility
        requirement folded into the bucket multiple, so a bucketed batch is
        always device-divisible and the remainder fallback stays dead.
        Returns ``(ds, true_examples_or_None)``."""
        spec = getattr(self.net, "_bucketing", None)
        if spec is None:
            return ds, None
        import math
        from dataclasses import replace

        from ..common.bucketing import pad_dataset

        multiple = self._bucket_multiple()
        if spec.batch_multiple % multiple:
            spec = replace(spec, batch_multiple=math.lcm(
                spec.batch_multiple, multiple))
        return pad_dataset(ds, spec)

    def _fit_batch(self, ds: DataSet):
        self._place_net()  # idempotent: direct _fit_batch callers skip fit()
        ds, true_n = self._bucket_for_mesh(ds)
        self._bucketed_true_examples = true_n
        b = ds.num_examples()  # shape read only: never syncs a device batch
        rem = b % self._ndata
        if rem:
            # trim to divisibility; remainder goes through a replicated step
            keep = b - rem
            if keep:
                self._fit_batch(_slice_ds(ds, 0, keep))
            self.net._fit_batch(_slice_ds(ds, b - rem, b))
            return
        self._fit_core(ds)

    def _fit_core(self, ds: DataSet):
        # gang-supervision hooks (no-ops unless the TDL_HEARTBEAT_DIR /
        # TDL_FAULT_SPEC env contracts are active): heartbeat FIRST so a
        # crash/hang injected at iteration k is attributed to k, then the
        # flight step_begin so a victim's final step is on the black box
        # BEFORE the fault fires (the injector flushes the ring)
        it = int(self.net.iteration)
        heartbeat.maybe_beat(it)
        flight_on = flight.active()
        if flight_on:
            flight.record("step_begin", iteration=it)
        faults.fault_point("train_step", iteration=it)
        spike = faults.poison_scale("train_step", iteration=it)
        if spike is not None:
            # loss_spike poisoning (ISSUE 18): scale the whole parameter
            # tree — training proceeds and the checkpointer keeps committing
            # structurally PERFECT generations whose weights are ruined,
            # the candidate only an offline eval gate can reject
            self.net.params_ = jax.tree.map(
                lambda a: a * spike, self.net.params_)
        now = time.perf_counter()
        if self._last_step_entry is not None:
            # iteration-to-iteration wall: includes checkpoint IO / barriers
            # between fit calls — what a straggling rank actually loses
            self._step_wall.labels(self._trainer_label).observe(
                now - self._last_step_entry)
        self._last_step_entry = now
        t0 = time.perf_counter()
        with self._phases.phase("compute"):
            self._fit_core_inner(ds)
        self._step_hist.labels(self._trainer_label).observe(time.perf_counter() - t0)
        if self._ndata > 1:
            # logical payload of the per-step gradient allreduce GSPMD
            # compiles into the step: one gradient tree's worth of bytes
            if self._grad_bytes is None:
                self._grad_bytes = sum(
                    getattr(l, "nbytes", 0)
                    for l in jax.tree.leaves(self.net.params_))
            self._coll_bytes.labels(self._trainer_label,
                                    "grad_allreduce").inc(self._grad_bytes)
        self._phases.step_done()
        if flight_on:
            loss = None
            if (it + 1) % flight.loss_every() == 0:
                try:  # reading the loss forces a device sync — see loss_every
                    s = getattr(self.net, "score_", None)
                    loss = float(s) if s is not None else None
                except Exception:
                    loss = None
            flight.record("step_end", iteration=it, loss=loss)
        aggregate.maybe_spool()

    def _fit_core_inner(self, ds: DataSet):
        n = self.net
        from ..nn.multilayer import MultiLayerNetwork

        # already padded by _bucket_for_mesh (mesh-divisible bucket): hand
        # the TRUE example count down so last_batch_size stays honest and
        # the net doesn't re-pad
        true_n = getattr(self, "_bucketed_true_examples", None)
        if isinstance(n, MultiLayerNetwork):
            # route through the net's OWN fit paths (incl. tbptt) with the
            # placement hook sharding every minibatch array over the mesh
            n._input_put = self._shard_placed
            try:
                n._fit_batch(ds, true_examples=true_n)
            finally:
                n._input_put = None
        else:  # ComputationGraph
            step = n._train_step_fn()
            rng = jax.random.fold_in(jax.random.key(n.conf.seed ^ 0x5EED), n.iteration)
            inputs = {k: self._shard(v) for k, v in n._coerce_inputs([ds.features]).items()}
            labels = {k: self._shard(v) for k, v in n._coerce_labels([ds.labels]).items()}
            # same lmasks shape the single-device path builds (ADVICE r1:
            # dropping the mask silently changed masked-sequence losses)
            lmasks = (
                {n.conf.network_outputs[0]: self._shard(jnp.asarray(ds.labels_mask))}
                if ds.labels_mask is not None else None
            )
            n.params_, n.updater_state, n.bn_state, loss = step(
                n.params_, n.updater_state, n.bn_state,
                jnp.asarray(n.iteration, jnp.int32), jnp.asarray(n.epoch, jnp.int32),
                inputs, labels, lmasks, rng)
            n.score_ = loss  # lazy: syncs only when read
            n.last_batch_size = (true_n if true_n is not None
                                 else ds.num_examples())
            n.iteration += 1
            for lst in n.listeners:
                if hasattr(lst, "iteration_done"):
                    lst.iteration_done(n, n.iteration, n.epoch)

    def _shard_placed(self, x):
        """Placement hook: shard an already-jnp minibatch array on the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec

        with self._phases.phase("h2d"):
            spec = PartitionSpec(self.data_axis, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(self.mesh, spec))


class MultiProcessTrainer(ParallelTrainer):
    """Data-parallel trainer spanning PROCESS boundaries.

    Same compiled step as :class:`ParallelTrainer`, but the mesh covers the
    global device set established by ``launcher.initialize`` and every input
    batch is this process's LOCAL shard (standard SPMD input pipeline: each
    process feeds batch_global / process_count examples). Params and states
    are replicated as global arrays; GSPMD's gradient allreduce then crosses
    the process boundary (gloo on CPU dev boxes, ICI/DCN on pods) — the
    TPU-native successor of ``SharedTrainingMaster``'s Aeron data plane
    (SURVEY §3.4 'TPU mapping').
    """

    def __init__(self, net, mesh: Optional[Mesh] = None, data_axis: str = AXIS_DATA,
                 sharding_rules=None, mesh_layout=None):
        if sharding_rules is not None:
            raise NotImplementedError(
                "sharding_rules placement uses jax.device_put, which cannot "
                "address a multi-process mesh; use mesh_layout=SpecLayout(...) "
                "— the partitioner places shards via make_array_from_callback, "
                "which works across process boundaries")
        super().__init__(net, mesh, data_axis, mesh_layout=mesh_layout)

    def prefetch(self, iterator, buffer_size: int = 2):
        """Host-staged prefetch only: one-shot sharded ``jax.device_put``
        cannot address a multi-process mesh (the global batch is assembled
        per-process via ``make_array_from_process_local_data`` in ``_shard``,
        which needs host buffers). Overlapping ETL with the step still pays;
        the h2d copy itself stays on the consumer thread."""
        from ..data.iterators import AsyncDataSetIterator

        return AsyncDataSetIterator(iterator, queue_size=buffer_size)

    # the Async wrapper above queues RAW host batches across base.next()
    # calls — an ETL ring view buffered there could be overwritten in place
    # by a fast worker, so sharded_etl must hand out copies
    _prefetch_buffers_host_views = True

    def _etl_rank_world(self):
        import jax

        return jax.process_index(), jax.process_count()

    def _bucket_multiple(self) -> int:
        # each rank buckets its LOCAL shard: divisibility only needs the
        # process-local device count (same invariant _fit_batch checks) —
        # lockstep feeds then land on the same bucket on every rank
        import jax

        return max(1, len(self.mesh.devices.flat) // jax.process_count())

    def _fit_batch(self, ds: DataSet):
        # the single-process remainder fallback cannot cross process
        # boundaries (it would mix global params with per-process inputs), so
        # multiprocess input pipelines must feed divisible LOCAL batches
        self._place_net()  # idempotent: direct _fit_batch callers skip fit()
        ds, true_n = self._bucket_for_mesh(ds)
        self._bucketed_true_examples = true_n
        b = ds.num_examples()
        if getattr(self.net, "_bucketing", None) is not None:
            _check_lockstep_buckets(b)
        local = self._bucket_multiple()
        if b % local:
            raise ValueError(
                f"multi-process local batch {b} must be divisible by the "
                f"process-local device count {local} (no remainder fallback "
                f"across process boundaries)")
        self._fit_core(ds)

    def _replicate(self, tree):
        sharding = NamedSharding(self.mesh, P())

        def put(x):
            if not hasattr(x, "dtype"):
                return x
            return jax.make_array_from_process_local_data(sharding, np.asarray(x))  # host-ok: API requires host buffers

        return jax.tree.map(put, tree)

    def _shard(self, x):
        if x is None:
            return None
        with self._phases.phase("h2d"):
            x = np.asarray(x)  # host-ok: make_array_from_process_local_data requires host buffers
            spec = P(self.data_axis, *([None] * (x.ndim - 1)))
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, spec), x)

    def _shard_placed(self, x):
        return self._shard(x)


def _check_lockstep_buckets(b: int) -> None:
    """Every process must pad to the SAME bucket: per-rank ragged tails that
    straddle a power-of-2 boundary (17 vs 16 rows) would otherwise hand
    ``make_array_from_process_local_data`` mismatched local shapes — a hang
    in the first collective instead of an error. One tiny allgather per
    batch (only when bucketing is enabled, so every rank participates)
    turns that into a deterministic ValueError."""
    import jax

    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    sizes = np.asarray(multihost_utils.process_allgather(  # host-ok: tiny fully-replicated int vector, host read is the point
        np.int32(b))).ravel()
    if not (sizes == sizes[0]).all():
        raise ValueError(
            "bucketed local batch sizes diverged across processes: "
            f"{sizes.tolist()} — multi-process bucketing requires lockstep "
            "feeds (the same local batch size on every rank each step); "
            "shard with shard_batches/sharded_etl or equalize the iterator")


def _slice_ds(ds: DataSet, a: int, b: int) -> DataSet:
    def s(x):
        # plain slicing works for numpy AND device arrays — np.asarray here
        # would pull a device-resident batch back to host (d2h→h2d round trip)
        return None if x is None else x[a:b]

    return DataSet(s(ds.features), s(ds.labels), s(ds.features_mask), s(ds.labels_mask))


class ParameterAveragingTrainingMaster:
    """SURVEY §2.6 S2 semantics: W logical workers each fit
    ``averaging_frequency`` minibatches locally, then flat params (and
    optionally updater state) are averaged across workers.
    """

    def __init__(self, workers: Optional[int] = None, averaging_frequency: int = 5,
                 average_updater_state: bool = True, batch_size_per_worker: Optional[int] = None):
        self.workers = workers or len(jax.devices())
        self.averaging_frequency = max(1, averaging_frequency)
        self.average_updater_state = average_updater_state
        self.batch_size_per_worker = batch_size_per_worker
        r = get_registry()
        self._coll_bytes = r.counter(
            "tdl_collective_bytes_total",
            "Logical payload bytes moved by training collectives",
            labels=("trainer", "kind"))
        self._trainer_label = type(self).__name__
        # workers here are LOGICAL model replicas, not devices — a separate
        # gauge keeps tdl_parallel_devices honest
        r.gauge("tdl_parallel_workers",
                "Logical workers in a parameter-averaging master",
                labels=("trainer",)).labels(self._trainer_label).set(self.workers)
        self._params_bytes: Optional[int] = None

    def fit(self, net, iterator, epochs: int = 1):
        replicas = [net] + [net.clone() for _ in range(self.workers - 1)]
        for _ in range(epochs):
            pending = 0
            batches = iter(iterator)
            while True:
                got = False
                for w, replica in enumerate(replicas):
                    try:
                        ds = next(batches)
                    except StopIteration:
                        break
                    replica._fit_batch(ds)
                    got = True
                if not got:
                    break
                pending += 1
                if pending >= self.averaging_frequency:
                    self._average(replicas)
                    pending = 0
            if pending:
                self._average(replicas)
            net.epoch += 1
        return net

    def _average(self, replicas):
        from ..monitoring.trace import step_phase_histogram

        t0 = time.perf_counter()
        if self._params_bytes is None:  # param sizes are fixed after init
            self._params_bytes = sum(getattr(l, "nbytes", 0)
                                     for l in jax.tree.leaves(replicas[0].params_))
        self._coll_bytes.labels(self._trainer_label, "param_average").inc(
            self._params_bytes * len(replicas))
        mean_params = jax.tree.map(
            lambda *xs: sum(xs) / len(xs), *[r.params_ for r in replicas])
        for r in replicas:
            # per-replica copies: the train step donates its param buffers
            r.params_ = jax.tree.map(jnp.copy, mean_params)
        if self.average_updater_state:
            mean_upd = jax.tree.map(
                lambda *xs: sum(xs) / len(xs) if hasattr(xs[0], "dtype") else xs[0],
                *[r.updater_state for r in replicas])
            for r in replicas:
                r.updater_state = jax.tree.map(
                    lambda x: jnp.copy(x) if hasattr(x, "dtype") else x, mean_upd)
        # the averaging pass IS this master's collective phase
        step_phase_histogram().labels("collective").observe(
            time.perf_counter() - t0)


class SharedTrainingMaster(ParallelTrainer):
    """SURVEY §2.6 S3 → TPU: the Aeron threshold-gradient mesh data plane is
    replaced by the compiled step's synchronous ICI allreduce (§3.4 'TPU
    mapping'). ``threshold_algorithm`` is accepted for API parity and used
    only by the host-side DCN codecs in ``parallel.compression``."""

    def __init__(self, net=None, mesh: Optional[Mesh] = None,
                 threshold_algorithm=None, batch_size: Optional[int] = None,
                 workers_per_node: Optional[int] = None, **_ignored):
        if net is not None:
            super().__init__(net, mesh)
        else:
            self._deferred_mesh = mesh
        self.threshold_algorithm = threshold_algorithm
        self.batch_size = batch_size

    def fit_net(self, net, iterator, epochs: int = 1):
        if not hasattr(self, "net") or self.net is None:
            super().__init__(net, getattr(self, "_deferred_mesh", None))
        return self.fit(iterator, epochs)
