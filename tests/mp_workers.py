"""Worker targets for the multi-process distributed tests.

Each function runs inside a freshly-spawned worker process AFTER
``launcher.initialize()`` (so jax already sees the global device set).
Results are written to the file named by TDL_MP_OUT (one file per rank) for
the parent pytest process to assert on — mirrors how the reference's
local-Spark tests collect per-executor results (SURVEY §4.4).
"""

import json
import os

import numpy as np


def _out_path(rank):
    return os.environ["TDL_MP_OUT"] + f".rank{rank}"


def _write(rank, payload):
    with open(_out_path(rank), "w") as f:
        json.dump(payload, f)


def _toy_net(seed=7):
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, InputType, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (
        NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2)).list()
        .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
        .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(6))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _global_batch(step, n=16):
    """Deterministic batch keyed by step — identical on every process."""
    rs = np.random.RandomState(1000 + step)
    x = rs.rand(n, 6).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, n)]
    return x, y


def allgather_blobs():
    """SPI smoke: pickled blob allgather over the real process boundary."""
    import jax

    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives

    col = ProcessCollectives()
    rank = col.rank
    blobs = col.allgather("smoke", {"rank": rank, "payload": "x" * (10 + rank * 100)})
    col.barrier("done")
    _write(rank, {
        "world": col.world,
        "global_devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "gathered_ranks": [b["rank"] for b in blobs],
        "lens": [len(b["payload"]) for b in blobs],
    })


def dp_train():
    """2-process data-parallel fit via MultiProcessTrainer; every process
    writes its final params hash + losses; parent asserts cross-process
    equality AND equality with a single-process reference run."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.trainer import MultiProcessTrainer

    col = ProcessCollectives()
    rank, world = col.rank, col.world
    net = _toy_net()
    trainer = MultiProcessTrainer(net, build_mesh(data=-1))

    steps = 6
    losses = []
    for step in range(steps):
        x, y = _global_batch(step)
        # each process feeds its local shard (standard SPMD input pipeline)
        lo = rank * (len(x) // world)
        hi = lo + len(x) // world
        trainer.fit([DataSet(x[lo:hi], y[lo:hi])])
        losses.append(net.score_)

    flat = np.asarray(net.params().numpy(), np.float64)
    _write(rank, {
        "losses": [float(l) for l in losses],
        "param_sum": float(flat.sum()),
        "param_norm": float(np.linalg.norm(flat)),
        "global_devices": jax.device_count(),
    })


def grad_exchange():
    """EncodedGradientsAccumulator across a genuine process boundary."""
    from deeplearning4j_tpu.parallel.compression import EncodedGradientsAccumulator
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives

    col = ProcessCollectives()
    rank = col.rank
    acc = EncodedGradientsAccumulator(col, threshold=0.1)
    rs = np.random.RandomState(42)  # same stream every rank
    g_all = rs.randn(2, 257).astype(np.float32) * 0.3
    mine = g_all[rank]
    upd1 = acc.exchange(mine)
    upd2 = acc.exchange(mine)
    _write(rank, {
        "upd1_sum": float(upd1.sum()),
        "upd2_sum": float(upd2.sum()),
        "residual_norm": float(np.linalg.norm(acc.residual)),
    })


def ckpt_train():
    """Training loop with rotating checkpoints; rank 1 optionally crashes at
    TDL_MP_DIE_AT (simulated preemption). On TDL_MP_RESTORE=1 the run resumes
    from the newest checkpoint instead of a fresh init."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.trainer import MultiProcessTrainer
    from deeplearning4j_tpu.serde.model_serializer import ModelSerializer

    col = ProcessCollectives()
    rank, world = col.rank, col.world
    ckpt_dir = os.environ["TDL_MP_CKPT"]
    die_at = int(os.environ.get("TDL_MP_DIE_AT", "-1"))
    total_steps = int(os.environ.get("TDL_MP_STEPS", "8"))
    every = int(os.environ.get("TDL_MP_CKPT_EVERY", "2"))

    net = _toy_net()
    start = 0
    marker = os.path.join(ckpt_dir, "latest.json")
    if os.environ.get("TDL_MP_RESTORE") == "1" and os.path.exists(marker):
        with open(marker) as f:
            meta = json.load(f)
        restored = ModelSerializer.restore_multi_layer_network(meta["path"], load_updater=True)
        net = restored
        net.iteration = meta["iteration"]
        start = meta["step"]

    trainer = MultiProcessTrainer(net, build_mesh(data=-1))
    losses = []
    for step in range(start, total_steps):
        x, y = _global_batch(step)
        lo = rank * (len(x) // world)
        hi = lo + len(x) // world
        trainer.fit([DataSet(x[lo:hi], y[lo:hi])])
        losses.append(net.score_)
        if (step + 1) % every == 0:
            col.barrier(f"ckpt-{step}")
            if rank == 0:  # process-0 writes (params replicated = identical)
                path = os.path.join(ckpt_dir, f"ckpt-{step}.zip")
                ModelSerializer.write_model(net, path, save_updater=True)
                with open(marker, "w") as f:
                    json.dump({"path": path, "step": step + 1,
                               "iteration": net.iteration}, f)
            col.barrier(f"ckpt-done-{step}")
        if rank == 1 and die_at == step:
            os._exit(17)  # simulated preemption: hard kill, no cleanup

    flat = np.asarray(net.params().numpy(), np.float64)
    _write(rank, {"losses": [float(l) for l in losses],
                  "param_sum": float(flat.sum()),
                  "param_norm": float(np.linalg.norm(flat)),
                  "start": start})


def supervised_train():
    """GangSupervisor worker target: data-parallel training with SHARDED
    checkpoints (``TrainingCheckpointer``) every TDL_MP_CKPT_EVERY steps and
    an unconditional restore-from-latest on start — the supervisor restart
    contract. Heartbeats and fault injection ride the real
    ``ParallelTrainer._fit_core`` hooks (TDL_HEARTBEAT_DIR / TDL_FAULT_SPEC
    env, set by the supervisor / the chaos test)."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.trainer import MultiProcessTrainer
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer

    col = ProcessCollectives()
    rank, world = col.rank, col.world
    total_steps = int(os.environ.get("TDL_MP_STEPS", "10"))
    every = int(os.environ.get("TDL_MP_CKPT_EVERY", "2"))
    incarnation = int(os.environ.get("TDL_GANG_RESTART_COUNT", "0"))

    net = _toy_net()
    ck = TrainingCheckpointer(os.environ["TDL_MP_CKPT"], async_write=False)
    start = 0
    if ck.restore(net):  # empty dir on incarnation 0 → False
        start = int(net.iteration)
    trainer = MultiProcessTrainer(net, build_mesh(data=-1))
    losses = []
    for step in range(start, total_steps):
        x, y = _global_batch(step)
        lo = rank * (len(x) // world)
        hi = lo + len(x) // world
        trainer.fit([DataSet(x[lo:hi], y[lo:hi])])
        losses.append(net.score_)
        if (step + 1) % every == 0:
            # all ranks at the same iteration before anyone writes a shard
            col.barrier(f"ck-{step}")
            ck.save(net)
            col.barrier(f"ck-done-{step}")

    flat = np.asarray(net.params().numpy(), np.float64)
    _write(rank, {"losses": [float(l) for l in losses],
                  "param_sum": float(flat.sum()),
                  "param_norm": float(np.linalg.norm(flat)),
                  "start": start, "incarnation": incarnation,
                  "global_devices": jax.device_count()})


def observability_train():
    """ISSUE 7 acceptance target: a 2-rank gang whose members train
    INDEPENDENTLY (single-rank local mesh, no cross-rank collectives) with a
    per-rank checkpoint every step — so a ``slow_ckpt_io@value=...,rank=1``
    fault makes rank 1 a genuine straggler instead of being hidden by
    lockstep barriers. Each rank's ``ParallelTrainer._fit_core`` drives the
    whole observability plane via the env contracts the supervisor sets:
    heartbeats, flight step events, ``tdl_step_wall_seconds`` (which
    INCLUDES the checkpoint time between fit calls — the skew signal), and
    the metrics spool the parent scrapes as one aggregated /metrics."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.monitoring import aggregate, flight
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer

    col = ProcessCollectives()
    rank = col.rank
    total_steps = int(os.environ.get("TDL_MP_STEPS", "8"))

    net = _toy_net(seed=7 + rank)
    mesh = Mesh(np.array(jax.local_devices()[:1]).reshape(1), ("data",))
    trainer = ParallelTrainer(net, mesh)
    ck = TrainingCheckpointer(os.path.join(os.environ["TDL_MP_CKPT"],
                                           f"rank{rank}"), async_write=False)
    for step in range(total_steps):
        x, y = _global_batch(step)
        trainer.fit([DataSet(x, y)])
        ck.save(net)  # every step: the slow_ckpt_io rank straggles HERE
    aggregate.maybe_spool(force=True)  # final counters for the parent's scrape
    flight.flush()
    col.barrier("obs-done")  # neither rank exits before both spooled
    _write(rank, {"iterations": int(net.iteration), "rank": rank})


def churn_train():
    """ISSUE 10 acceptance target: a 2-rank gang whose FIRST incarnation
    deliberately churns minibatch shapes after marking warmup done — the
    RecompileWatchdog attributes the recompiles per fn, the AlertEngine's
    ``recompiles_after_warmup`` rule fires (alert + compile events land in
    the flight ring), and a crash injected later (TDL_FAULT_SPEC) makes the
    supervisor write a postmortem carrying both. The respawned incarnation
    trains steady-shape to completion, proving compiles stay FLAT after
    warmup when shapes don't churn."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.monitoring import (AlertEngine, RecompileWatchdog,
                                               aggregate, flight)
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    col = ProcessCollectives()
    rank = col.rank
    incarnation = int(os.environ.get("TDL_GANG_RESTART_COUNT", "0"))
    net = _toy_net(seed=7 + rank)
    mesh = Mesh(np.array(jax.local_devices()[:1]).reshape(1), ("data",))
    trainer = ParallelTrainer(net, mesh)
    wd = RecompileWatchdog().install()
    engine = AlertEngine()

    def fit(step, n=16):
        x, y = _global_batch(step, n=n)
        trainer.fit([DataSet(x, y)])

    for step in range(4):  # steady warmup: one signature, one compile
        fit(step)
    engine.mark_warmup_done()
    compiles_at_warmup = dict(wd.stats()["per_fn_compiles"])
    steady_eval = [a for a in engine.evaluate()
                   if a["rule"] == "recompiles_after_warmup"][0]
    churn_firing = False
    if incarnation == 0:
        for step, n in enumerate((6, 7, 9, 11), start=4):  # shape churn
            fit(step, n=n)
        churn_firing = [a for a in engine.evaluate()
                        if a["rule"] == "recompiles_after_warmup"][0]["firing"]
        for step in range(8, 14):  # crash@iter=10,rank=1 fires in here
            fit(step)
    else:
        for step in range(4, 14):  # steady to completion
            fit(step)
    final_compiles = dict(wd.stats()["per_fn_compiles"])
    wd.close()
    aggregate.maybe_spool(force=True)
    flight.flush()
    col.barrier("churn-done")
    _write(rank, {"rank": rank, "incarnation": incarnation,
                  "steady_firing": steady_eval["firing"],
                  "churn_firing": churn_firing,
                  "per_fn_compiles_warmup": compiles_at_warmup,
                  "per_fn_compiles_final": final_compiles})


def etl_train():
    """ISSUE 6 acceptance target: per-rank SHARDED multi-process ETL feeding
    a 2-rank data-parallel gang under GangSupervisor. Each rank's ETL
    service decodes only its ``rank/world`` slice of the batch stream;
    checkpoints carry the iterator position (``TrainingCheckpointer.save(
    net, iterator)``), so a restarted gang replays the exact surviving
    stream — the parent asserts exact param parity with an unfaulted gang
    plus per-step batch-hash equality."""
    import hashlib

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.etl_service import (EtlDataSetIterator,
                                                     ImageEtlSpec)
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, InputType, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.trainer import MultiProcessTrainer
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer

    col = ProcessCollectives()
    rank, world = col.rank, col.world
    total_steps = int(os.environ.get("TDL_MP_STEPS", "8"))
    every = int(os.environ.get("TDL_MP_CKPT_EVERY", "2"))
    incarnation = int(os.environ.get("TDL_GANG_RESTART_COUNT", "0"))

    conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.05)).list()
            .layer(DenseLayer(n_in=24 * 24 * 3, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(24 * 24 * 3))
            .build())
    net = MultiLayerNetwork(conf).init()

    spec = ImageEtlSpec.from_directory(
        os.environ["TDL_ETL_DIR"], 24, 24, batch_size=8, store_pad=8,
        cache_dir=os.environ.get("TDL_ETL_CACHE")).for_rank(rank, world)
    it = EtlDataSetIterator(spec, num_workers=2, zero_copy=False)
    ck = TrainingCheckpointer(os.environ["TDL_MP_CKPT"], async_write=False)
    start = 0
    if ck.restore(net, it):  # also restores the iterator position
        start = int(net.iteration)
    trainer = MultiProcessTrainer(net, build_mesh(data=-1))
    step_hashes = {}
    try:
        for step in range(start, total_steps):
            if not it.has_next():
                it.reset()  # epoch boundary: stream continues seamlessly
            ds = it.next()
            step_hashes[str(step)] = hashlib.sha256(
                ds.features.tobytes() + ds.labels.tobytes()).hexdigest()
            x = (ds.features.reshape(ds.features.shape[0], -1)
                 .astype(np.float32) / 255.0)
            trainer.fit([DataSet(x, ds.labels)])
            if (step + 1) % every == 0:
                col.barrier(f"ck-{step}")
                ck.save(net, it)
                col.barrier(f"ck-done-{step}")
    finally:
        it.close()

    flat = np.asarray(net.params().numpy(), np.float64)
    _write(rank, {"param_sum": float(flat.sum()),
                  "param_tail": [float(v) for v in flat[-8:]],
                  "step_hashes": step_hashes, "start": start,
                  "incarnation": incarnation})


def w2v_shard_train():
    """Cross-process embedding-shard training (SURVEY §2.2 J17 / §2.6 S6):
    syn0/syn1 rows shard over a GLOBAL mesh spanning both processes; the
    epoch executable's gathers/updates compile to cross-process collectives.
    Each rank writes table hashes (cross-process row sync) + a semantic
    check (co-occurring words more similar than non-co-occurring)."""
    import hashlib

    import jax
    from jax.sharding import Mesh

    from deeplearning4j_tpu.nlp.word2vec import Word2Vec
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives

    col = ProcessCollectives()
    rank = col.rank
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("model",))

    # two word clusters that never co-occur: 64 words → V=64 divides the
    # 8-device axis, so the tables genuinely shard 8 ways across processes
    rs = np.random.RandomState(0)
    a_words = [f"a{i}" for i in range(32)]
    b_words = [f"b{i}" for i in range(32)]
    sents = []
    for _ in range(400):
        sents.append(" ".join(rs.choice(a_words, 6)))
        sents.append(" ".join(rs.choice(b_words, 6)))

    w2v = Word2Vec(layer_size=16, window=3, negative=4, epochs=20,
                   learning_rate=0.05, batch_size=256, min_word_frequency=1,
                   seed=3, subsampling=0.0, mesh=mesh)
    w2v.fit(sents)

    def sim(u, v):
        u, v = w2v.get_word_vector(u), w2v.get_word_vector(v)
        return float(u @ v / (np.linalg.norm(u) * np.linalg.norm(v) + 1e-9))

    within = np.mean([sim(f"a{i}", f"a{i+1}") for i in range(0, 30, 2)]
                     + [sim(f"b{i}", f"b{i+1}") for i in range(0, 30, 2)])
    across = np.mean([sim(f"a{i}", f"b{i}") for i in range(0, 32, 2)])
    col.barrier("w2v-done")
    _write(rank, {
        "syn0_hash": hashlib.sha256(np.ascontiguousarray(w2v.syn0)).hexdigest(),
        "syn1_hash": hashlib.sha256(np.ascontiguousarray(w2v.syn1neg)).hexdigest(),
        "within": float(within), "across": float(across),
        "vocab": w2v.vocab.num_words(),
        "global_devices": jax.device_count(),
    })


def fsdp_train():
    """ISSUE 9 acceptance target: a gang training with SHARDED parameters —
    ``MultiProcessTrainer(mesh_layout=SpecLayout(data=1, fsdp=F, tp=T))``
    places params AND optimizer state over the fsdp/tp axes spanning the
    process boundary. Modes (TDL_MP_MODE):

    - ``train``: N steps on deterministic global batches (data axis is 1, so
      every rank feeds the full batch and GSPMD shards the math); layout-
      stamped sharded checkpoints via ``trainer.checkpointer`` when
      TDL_MP_CKPT is set.
    - ``restore``: a FRESH gang restores the sharded checkpoint (each rank
      reads only its shards) and writes the param fingerprint — the parent
      asserts exact parity with the trained gang, and that a mismatched
      TDL_MP_FSDP/TDL_MP_TP gang dies with the layout-mismatch error.
      ``TDL_MP_RESHARD=1`` opts the restore into the ISSUE 14 cross-topology
      path: a DIFFERENT gang shape/layout redistributes the saved chunks
      instead of refusing (each rank still reads only the chunk slices
      overlapping its addressable shards).

    Every rank reports ``tdl_param_bytes_per_rank`` so the parent can assert
    per-rank bytes shrink ~linearly with the fsdp axis size."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.monitoring.partition import partition_metrics
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, InputType, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.partition import Partitioner, SpecLayout
    from deeplearning4j_tpu.parallel.trainer import MultiProcessTrainer

    col = ProcessCollectives()
    rank, world = col.rank, col.world
    data = int(os.environ.get("TDL_MP_DATA", "1"))
    fsdp = int(os.environ.get("TDL_MP_FSDP", "-1"))
    tp = int(os.environ.get("TDL_MP_TP", "1"))
    mode = os.environ.get("TDL_MP_MODE", "train")
    steps = int(os.environ.get("TDL_MP_STEPS", "4"))
    every = int(os.environ.get("TDL_MP_CKPT_EVERY", "2"))

    # every dim divisible by 4 so a 4-way fsdp axis shards EVERY leaf —
    # per-rank bytes then shrink exactly linearly (no replicated remainder)
    conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    net = MultiLayerNetwork(conf).init()
    partitioner = Partitioner(SpecLayout(data=data, fsdp=fsdp, tp=tp))
    trainer = MultiProcessTrainer(net, mesh_layout=partitioner)
    ck = (trainer.checkpointer(os.environ["TDL_MP_CKPT"], async_write=False)
          if "TDL_MP_CKPT" in os.environ else None)

    def batch(step, n=8):
        rs = np.random.RandomState(2000 + step)
        x = rs.rand(n, 8).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, n)]
        return x, y

    losses = []
    if mode == "restore":
        reshard = os.environ.get("TDL_MP_RESHARD") == "1"
        if not ck or not ck.restore(net, reshard=reshard):
            raise RuntimeError("restore mode found no checkpoint")
        trainer._place_net()  # pass-through: shards already placed
    else:
        for step in range(steps):
            x, y = batch(step)
            trainer.fit([DataSet(x, y)])  # data axis =1: full global batch
            losses.append(float(net.score_))
            if ck is not None and (step + 1) % every == 0:
                col.barrier(f"fsdp-ck-{step}")
                ck.save(net)
                col.barrier(f"fsdp-ck-done-{step}")

    # device-side fingerprint (replicated scalars): the flat host view would
    # gather non-addressable shards — exactly what sharded state forbids
    psum = float(sum(jnp.sum(w) for w in jax.tree.leaves(net.params_)))
    pnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(w))
                               for w in jax.tree.leaves(net.params_))))
    m = partition_metrics()
    rep = trainer.partition_report
    col.barrier("fsdp-done")
    _write(rank, {
        "losses": losses, "param_sum": psum, "param_norm": pnorm,
        "iteration": int(net.iteration),
        "bytes_params": m.param_bytes.labels("params").value,
        "bytes_opt": m.param_bytes.labels("opt_state").value,
        "params_bytes_total": rep.params_bytes_total,
        "local_devices": jax.local_device_count(),
        "mesh": {a: int(s) for a, s in trainer.mesh.shape.items()},
        "global_devices": jax.device_count(),
    })


def elastic_train():
    """ISSUE 14 elastic-resize target: a sharded gang that adapts to
    WHATEVER world size the supervisor spawned.

    - layout = ``largest_layout(total devices)`` (fsdp absorbs them all), so
      a resized gang builds a valid smaller mesh without reconfiguration;
    - restore is unconditional with ``reshard=True``: after an elastic
      resize the survivors inherit the bigger gang's checkpoint through the
      cross-topology chunk redistribution;
    - the permanently-dead host is simulated by TDL_MP_DEAD_RANK: that rank
      ``os._exit``s at BOOT (before jax / any heartbeat) in every respawn
      (incarnation >= 1) while the world is still larger than
      TDL_MP_SURVIVORS — exactly a host that never comes back. Once the
      supervisor degrades the gang to the survivor count, the env rank ids
      renumber below the dead one and training continues unattended.
    """
    incarnation = int(os.environ.get("TDL_GANG_RESTART_COUNT", "0"))
    env_rank = int(os.environ.get("TDL_PROCESS_ID", "0"))
    env_world = int(os.environ.get("TDL_NUM_PROCESSES", "1"))
    dead = os.environ.get("TDL_MP_DEAD_RANK")
    survivors = int(os.environ.get("TDL_MP_SURVIVORS", "1"))
    if (dead is not None and env_rank == int(dead) and incarnation >= 1
            and env_world > survivors):
        os._exit(43)  # the "host" is gone: no boot, no heartbeat, ever

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, InputType, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives
    from deeplearning4j_tpu.parallel.partition import (Partitioner,
                                                       largest_layout)
    from deeplearning4j_tpu.parallel.trainer import MultiProcessTrainer

    col = ProcessCollectives()
    rank, world = col.rank, col.world
    steps = int(os.environ.get("TDL_MP_STEPS", "8"))
    every = int(os.environ.get("TDL_MP_CKPT_EVERY", "2"))

    conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    net = MultiLayerNetwork(conf).init()
    partitioner = Partitioner(largest_layout(jax.device_count()))
    trainer = MultiProcessTrainer(net, mesh_layout=partitioner)
    ck = trainer.checkpointer(os.environ["TDL_MP_CKPT"], async_write=False,
                              reshard=True)
    start = 0
    if ck.restore(net):  # cross-topology after a resize; False on a cold dir
        start = int(net.iteration)
        trainer._place_net()

    def batch(step, n=8):
        rs = np.random.RandomState(2000 + step)
        x = rs.rand(n, 8).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, n)]
        return x, y

    for step in range(start, steps):
        x, y = batch(step)
        trainer.fit([DataSet(x, y)])  # data axis = 1: full global batch
        if (step + 1) % every == 0:
            col.barrier(f"el-ck-{step}")
            ck.save(net)
            col.barrier(f"el-ck-done-{step}")

    psum = float(sum(jnp.sum(w) for w in jax.tree.leaves(net.params_)))
    pnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(w))
                               for w in jax.tree.leaves(net.params_))))
    col.barrier("el-done")
    _write(rank, {
        "param_sum": psum, "param_norm": pnorm,
        "iteration": int(net.iteration), "start": start,
        "world": world, "incarnation": incarnation,
        "mesh": {a: int(s) for a, s in trainer.mesh.shape.items()},
        "global_devices": jax.device_count(),
    })


def tp_train():
    """Cross-process TENSOR-parallel numerics (r5 hygiene, VERDICT r4 weak
    #7): a dp×tp transformer step over a global 2-process mesh — the tp
    axis spans the process boundary, so Megatron column/row collectives
    cross it. Each rank writes the loss sequence; the parent asserts
    rank-identical losses AND parity with a single-process dp×tp run."""
    import jax

    from deeplearning4j_tpu.parallel.launcher import ProcessCollectives

    col = ProcessCollectives()
    rank = col.rank
    losses = tp_step_losses(jax.devices())
    col.barrier("tp-done")
    _write(rank, {"losses": losses, "global_devices": jax.device_count()})


def tp_step_losses(devices, steps=3):
    """Shared by the worker and the parent's single-process reference:
    deterministic dp×tp transformer training losses on a 2 x 2 mesh of the
    given four devices, the weights split by the role policy."""
    import jax

    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        batch_specs,
        init_params,
        make_train_step,
    )
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.partition import Partitioner, SpecLayout

    layout = SpecLayout(data=2, fsdp=1, tp=2, data_axis="dp")
    part = Partitioner(layout, mesh=layout.build_mesh(devices))
    mesh = part.mesh
    cfg = TransformerConfig.tiny(dropout=0.0)
    params = init_params(jax.random.key(0), cfg)
    params = part.place(params, part.spec_tree(params))

    def _place(a, spec):
        arr = np.asarray(a)
        sh = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(arr.shape, sh, lambda idx: arr[idx])

    updater = Adam(1e-3)
    opt = updater.init(params)
    step = jax.jit(make_train_step(cfg, updater), donate_argnums=(0, 1))

    rs = np.random.RandomState(5)
    B, T = 8, 64
    bspec = batch_specs(cfg)
    batch = {
        "tokens": rs.randint(0, cfg.vocab_size, (B, T)).astype(np.int32),
        "labels": rs.randint(0, cfg.vocab_size, (B, T)).astype(np.int32),
        "weights": (rs.rand(B, T) < 0.15).astype(np.float32),
    }
    batch = {k: _place(v, bspec[k]) for k, v in batch.items()}
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())

    def _rep_arr(a):
        arr = np.asarray(a)
        return jax.make_array_from_callback(arr.shape, rep, lambda idx: arr[idx])

    rng = jax.random.wrap_key_data(_rep_arr(jax.random.key_data(jax.random.key(9))))
    losses = []
    with jax.sharding.set_mesh(mesh):
        for i in range(steps):
            it = _rep_arr(np.asarray(i, np.int32))
            params, opt, loss = step(params, opt, batch, it, rng)
            losses.append(float(loss))
    return losses
