"""Pipeline parallelism (GPipe-style) over a ``pipe`` mesh axis.

Reference inversion (SURVEY §2.10 PP row): the reference has NO pipeline
parallelism — its distribution story is data-parallel only. The modern-set
mandate is covered here the TPU way: stages are a *sharded leading dim* of a
stacked param tree, the microbatch loop is a ``lax.scan`` inside
``shard_map``, and inter-stage activation transfer is a single
``lax.ppermute`` ring hop per tick — i.e. the schedule compiles into one XLA
program, no host-side stage threads (the reference's analogous machinery
would have been Aeron queues between JVM workers).

Design notes:
- GPipe fill-drain schedule: ``M`` microbatches over ``S`` stages takes
  ``M + S - 1`` ticks; bubble fraction = (S-1)/(M+S-1).
- Every stage must map activations of one shape to the same shape (true for
  transformer blocks / residual stacks). Embedding + head run OUTSIDE the
  pipeline body (they are cheap; GSPMD shards them over dp).
- ``schedule="gpipe"``: backward is automatic — ``ppermute``'s transpose is
  the reverse ring hop, so ``jax.grad`` through :func:`spmd_pipeline` yields
  the reverse fill-drain schedule, with AD stashing every tick's carries.
- ``schedule="1f1b"``: a ``jax.custom_vjp`` whose backward is ONE combined
  scan of ``M + 2S - 1`` ticks interleaving forward recompute and backward
  units, so the activation stash is a circular buffer of
  ``min(M, 2S-1)`` *stage inputs* — in-flight memory is bounded by the
  stage count, not the microbatch count, and per-layer activations are
  rematerialized inside each backward unit's ``jax.vjp``.
- Stage boundaries come from ``monitoring.costmodel.balance_stages`` (min-max
  predicted stage cost over contiguous layer ranges); ragged stages ride a
  padded ``[S, Lmax]`` static index map whose validity mask gates both the
  forward carry and (through the ``where`` transpose) the padded slots'
  cotangents.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..monitoring import aggregate, flight
from .mesh import AXIS_DATA, AXIS_PIPE
from .trainer import ParallelTrainer

SCHEDULES = ("gpipe", "1f1b")


def _squeeze_leading(tree):
    return jax.tree.map(lambda x: jnp.squeeze(x, 0) if x.ndim > 0 and x.shape[0] == 1 else x, tree)


def _pipeline_body(stage_fn, params_local, xs, aux, axis: str):
    """Runs on each pipe-shard: params_local has leading dim n_stages/S==1.

    xs: [M, mb, ...] microbatches (pipe-replicated). aux: optional pytree of
    per-microbatch side inputs [M, ...] that do NOT flow through the ring
    (masks, segment ids): at tick t, stage s is working on microbatch
    (t - s), so each stage indexes its own aux slice. Returns ys [M, mb, ...]
    (pipe-replicated — the last stage's results psum-broadcast over the axis).
    """
    n_stages = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    my_params = _squeeze_leading(params_local)
    M = xs.shape[0]
    total = M + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # stage s works on microbatch (t - s); clamp covers warm-up/drain
        # ticks whose results are never recorded
        mb_idx = jnp.clip(t - stage, 0, M - 1)
        inp = jnp.where(stage == 0, xs[jnp.minimum(t, M - 1)], state)
        aux_t = jax.tree.map(lambda a: a[mb_idx], aux) if aux is not None else None
        out = stage_fn(my_params, inp, aux_t) if aux is not None else stage_fn(my_params, inp)
        # last stage records microbatch (t - S + 1) once it exists; the
        # explicit validity gate (not index arithmetic) keeps warm-up ticks
        # from writing anything
        idx = jnp.maximum(t - (n_stages - 1), 0)
        written = outputs.at[idx].set(out)
        valid = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
        outputs = jnp.where(valid, written, outputs)
        state = jax.lax.ppermute(out, axis, perm)
        return (state, outputs), None

    state0 = jnp.zeros_like(xs[0])
    outputs0 = jnp.zeros_like(xs)
    (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0), jnp.arange(total))
    # broadcast the last stage's outputs to every pipe shard (sum of one
    # valid contribution + zeros); differentiable, unlike a host-side gather
    return jax.lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis)


def _pipeline_body_1f1b_bwd(stage_fn, params_local, xs, aux, dys, axis: str,
                            data_axis: Optional[str] = None):
    """1F1B backward: ONE scan of ``M + 2S - 1`` ticks per pipe-shard.

    Tick ``u`` runs, on stage ``s``: the *backward unit* of microbatch
    ``m_b = u - (2S-1) + s`` (cotangent from stage ``s+1`` arrived on the
    reverse ring at tick ``u-1``; the last stage reads ``dys`` directly) and
    the *forward unit* of microbatch ``m_f = u - s`` (recompute, feeding the
    forward ring exactly like fill-drain). The stage INPUT of each forward
    unit is stashed in a circular buffer of ``R = min(M, 2S-1)`` slots —
    the backward unit rematerializes its per-layer activations from that
    input via ``jax.vjp``. At stage 0 with ``R = 2S-1`` the fwd write and
    the bwd read of one tick share a slot (``m_f - m_b = 2S-1``), so the
    backward unit runs FIRST (read-before-write); all cross-tick reuse
    distances are ≥ the ring size by construction.

    Returns ``(dparams_local, dxs)``: this stage's parameter cotangents
    (leading dim restored to 1 for the pipe out_spec) and the input
    cotangents (written by stage 0, psum-broadcast like the forward outputs).
    """
    n_stages = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    my_params = _squeeze_leading(params_local)
    M = xs.shape[0]
    S = n_stages
    R = int(min(M, 2 * S - 1))
    total = M + 2 * S - 1
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]
    perm_bwd = [(i, (i - 1) % S) for i in range(S)]

    def apply_stage(p, x, a):
        return stage_fn(p, x, a) if aux is not None else stage_fn(p, x)

    def tick(carry, u):
        fstate, bstate, stash, dparams, dxs = carry
        # ---- backward unit (reads the stash BEFORE this tick's fwd write)
        m_b = u - (2 * S - 1) + stage
        b_valid = jnp.logical_and(m_b >= 0, m_b < M)
        m_b_c = jnp.clip(m_b, 0, M - 1)
        x_b = stash[m_b_c % R]
        aux_b = jax.tree.map(lambda a: a[m_b_c], aux) if aux is not None else None
        g_in = jnp.where(stage == S - 1, dys[m_b_c], bstate)
        _, vjp_fn = jax.vjp(lambda p, x: apply_stage(p, x, aux_b), my_params, x_b)
        dp, dx = vjp_fn(g_in)
        # warm-up/drain ticks run on ring garbage — the gate keeps their
        # cotangents (NaNs included: where selects, it doesn't blend) out
        dparams = jax.tree.map(
            lambda acc, d: acc + jnp.where(b_valid, d, jnp.zeros_like(d)),
            dparams, dp)
        rec = jnp.logical_and(b_valid, stage == 0)
        dxs = jnp.where(rec, dxs.at[m_b_c].set(dx), dxs)
        # ---- forward unit (same dataflow as the fill-drain tick)
        m_f = u - stage
        f_valid = jnp.logical_and(m_f >= 0, m_f < M)
        m_f_c = jnp.clip(m_f, 0, M - 1)
        a_in = jnp.where(stage == 0, xs[m_f_c], fstate)
        aux_f = jax.tree.map(lambda a: a[m_f_c], aux) if aux is not None else None
        y = apply_stage(my_params, a_in, aux_f)
        stash = jnp.where(f_valid, stash.at[m_f_c % R].set(a_in), stash)
        fstate = jax.lax.ppermute(y, axis, perm_fwd)
        bstate = jax.lax.ppermute(
            jnp.where(b_valid, dx, jnp.zeros_like(dx)), axis, perm_bwd)
        return (fstate, bstate, stash, dparams, dxs), None

    carry0 = (
        jnp.zeros_like(xs[0]),                                # forward ring
        jnp.zeros_like(xs[0]),                                # backward ring
        jnp.zeros((R,) + xs.shape[1:], xs.dtype),             # input stash
        jax.tree.map(jnp.zeros_like, my_params),              # grad accum
        jnp.zeros_like(xs),                                   # input cotangents
    )
    (_, _, _, dparams, dxs), _ = jax.lax.scan(tick, carry0, jnp.arange(total))
    # stage 0 holds the only real dxs rows; broadcast like the fwd outputs
    dxs = jax.lax.psum(
        jnp.where(stage == 0, dxs, jnp.zeros_like(dxs)), axis)
    if data_axis is not None:
        # each data shard saw only its batch slice, so its dparams is a
        # PARTIAL sum (dxs stays batch-sharded and needs no reduction); the
        # pspec out_spec claims data-replication, which this psum makes true
        dparams = jax.lax.psum(dparams, data_axis)
    return jax.tree.map(lambda x: x[None], dparams), dxs


def _spmd_pipeline_1f1b(stage_fn, stacked_params, xs, mesh, *, pipe_axis,
                        data_axis, aux):
    """custom_vjp wrapper: forward = the fill-drain body (losses are bitwise
    identical to gpipe), backward = the combined 1F1B scan."""
    dp = resolve_data_axis(mesh, data_axis)
    pspec = jax.tree.map(lambda x: P(pipe_axis, *([None] * (x.ndim - 1))), stacked_params)
    xspec = P(None, dp, *([None] * (xs.ndim - 2)))
    aspec = (None if aux is None
             else jax.tree.map(lambda a: P(None, dp, *([None] * (a.ndim - 2))), aux))
    fwd_f = jax.shard_map(
        functools.partial(_pipeline_body, stage_fn, axis=pipe_axis),
        mesh=mesh, in_specs=(pspec, xspec, aspec), out_specs=xspec,
        check_vma=False,
    )
    bwd_f = jax.shard_map(
        functools.partial(_pipeline_body_1f1b_bwd, stage_fn, axis=pipe_axis,
                          data_axis=dp),
        mesh=mesh, in_specs=(pspec, xspec, aspec, xspec),
        out_specs=(pspec, xspec), check_vma=False,
    )

    @jax.custom_vjp
    def run(params, xs_, aux_):
        return fwd_f(params, xs_, aux_)

    def run_fwd(params, xs_, aux_):
        return fwd_f(params, xs_, aux_), (params, xs_, aux_)

    def run_bwd(res, dys):
        params, xs_, aux_ = res
        dparams, dxs = bwd_f(params, xs_, aux_, dys)
        daux = jax.tree.map(jnp.zeros_like, aux_)
        return dparams, dxs, daux

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, xs, aux)


def resolve_data_axis(mesh: Mesh, data_axis) -> Optional[str]:
    """'auto' picks the canonical batch axis present in the mesh ('data' or
    'dp'); an explicit axis missing from the mesh is an error (a silent miss
    would replicate the batch and quietly disable data parallelism)."""
    if data_axis == "auto":
        for cand in (AXIS_DATA, "dp"):
            if cand in mesh.shape:
                return cand
        return None
    if data_axis is not None and data_axis not in mesh.shape:
        raise ValueError(f"data_axis '{data_axis}' not in mesh axes {tuple(mesh.shape)}")
    return data_axis


def spmd_pipeline(stage_fn: Callable[..., Any], stacked_params, xs, mesh: Mesh,
                  *, pipe_axis: str = AXIS_PIPE, data_axis="auto", aux=None,
                  schedule: str = "gpipe"):
    """Pipeline the microbatches ``xs`` through ``n_stages = mesh.shape[pipe_axis]``.

    - ``stacked_params``: pytree whose every leaf has leading dim ``n_stages``
      (stage i's slice is its stage-local params), sharded over ``pipe_axis``.
    - ``xs``: [M, mb, ...] microbatched activations. The microbatch dim M is
      never sharded; the per-microbatch batch dim may be sharded over
      ``data_axis`` (pp×dp composes). ``data_axis='auto'`` uses whichever of
      'data'/'dp' the mesh has.
    - ``stage_fn(stage_params, x) -> y`` with ``y.shape == x.shape`` — or
      ``stage_fn(stage_params, x, aux_mb)`` when ``aux`` (a pytree of
      [M, ...] per-microbatch side inputs, e.g. attention masks) is given.
    - ``schedule``: "gpipe" (fill-drain forward, AD-derived backward) or
      "1f1b" (same forward, custom_vjp backward whose activation stash is
      bounded by the stage count — see :func:`_pipeline_body_1f1b_bwd`).
      Forward values are bitwise identical across schedules; gradients agree
      to float accumulation order.
    """
    if pipe_axis not in mesh.shape:
        raise ValueError(f"mesh has no '{pipe_axis}' axis: {mesh.shape}")
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown pipeline schedule '{schedule}': {SCHEDULES}")
    if schedule == "1f1b":
        return _spmd_pipeline_1f1b(stage_fn, stacked_params, xs, mesh,
                                   pipe_axis=pipe_axis, data_axis=data_axis,
                                   aux=aux)
    dp = resolve_data_axis(mesh, data_axis)
    pspec = jax.tree.map(lambda x: P(pipe_axis, *([None] * (x.ndim - 1))), stacked_params)
    xspec = P(None, dp, *([None] * (xs.ndim - 2)))
    aspec = (None if aux is None
             else jax.tree.map(lambda a: P(None, dp, *([None] * (a.ndim - 2))), aux))
    f = jax.shard_map(
        functools.partial(_pipeline_body, stage_fn, axis=pipe_axis),
        mesh=mesh, in_specs=(pspec, xspec, aspec), out_specs=xspec,
        check_vma=False,
    )
    return f(stacked_params, xs, aux)


def microbatch(x, n_microbatches: int):
    """[B, ...] -> [M, B/M, ...] (static split; B must divide)."""
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    return x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])


def unmicrobatch(x):
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


# ------------------------------------------------------------ stage planning


def uniform_boundaries(n_layers: int, n_stages: int) -> List[Tuple[int, int]]:
    """Even contiguous split; raises loudly on ragged depth (the silent
    historical failure mode — see :func:`pipeline_transformer_params`)."""
    if n_layers % n_stages:
        raise ValueError(
            f"{n_layers} layers do not divide evenly into {n_stages} pipeline "
            f"stages ({n_layers} % {n_stages} = {n_layers % n_stages}); pass "
            "boundaries= from monitoring.costmodel.balance_stages (the cost "
            "partitioner handles ragged depth), or pick a stage count that "
            "divides the layer count")
    c = n_layers // n_stages
    return [(s * c, (s + 1) * c) for s in range(n_stages)]


def stage_index_map(boundaries, n_layers: Optional[int] = None):
    """Static padded view of contiguous stage boundaries.

    Returns ``(idx, valid)`` numpy arrays of shape ``[S, Lmax]``: ``idx`` maps
    each stage's slot to a canonical layer index (padded slots alias layer 0
    — harmless, their outputs are discarded and the validity gate's ``where``
    transpose hands them exactly-zero cotangents), ``valid`` is the 1/0 gate.
    Validates the boundaries cover ``[0, L)`` contiguously with no empty
    stage.
    """
    bs = [(int(a), int(b)) for a, b in boundaries]
    if not bs:
        raise ValueError("empty stage boundaries")
    if bs[0][0] != 0:
        raise ValueError(f"stage boundaries must start at layer 0: {bs}")
    for (_, b), (a2, _) in zip(bs, bs[1:]):
        if a2 != b:
            raise ValueError(f"stage boundaries not contiguous: {bs}")
    for a, b in bs:
        if b <= a:
            raise ValueError(f"empty pipeline stage in boundaries: {bs}")
    L = bs[-1][1]
    if n_layers is not None and L != int(n_layers):
        raise ValueError(
            f"stage boundaries cover {L} layers but the model has {n_layers}")
    S = len(bs)
    Lmax = max(b - a for a, b in bs)
    idx = np.zeros((S, Lmax), np.int32)
    valid = np.zeros((S, Lmax), np.float32)
    for s, (a, b) in enumerate(bs):
        idx[s, : b - a] = np.arange(a, b, dtype=np.int32)
        valid[s, : b - a] = 1.0
    return idx, valid


def transformer_stage_boundaries(cfg, n_stages: int, *, batch: int = 1,
                                 seq: Optional[int] = None,
                                 costs: Optional[Sequence[float]] = None):
    """Min-max-cost contiguous stage split for the flagship transformer,
    from ``models.transformer.layer_costs`` flops (or caller-supplied
    per-layer ``costs``, e.g. measured ones during rebalancing)."""
    from ..monitoring.costmodel import balance_stages

    if costs is None:
        from ..models import transformer as T

        rows = T.layer_costs(cfg, batch, int(seq or min(cfg.max_len, 128)))
        costs = [float(r["flops"]) for r in rows
                 if r["kind"] == "TransformerBlock"]
    return balance_stages(list(costs), n_stages)


def graph_stage_partition(net, batch, n_stages: int):
    """Partition a MultiLayerNetwork / ComputationGraph vertex chain into
    ``n_stages`` contiguous stages minimizing the max predicted stage cost.
    Returns a list of per-stage layer-name lists (the graph analogue of the
    transformer boundaries)."""
    from ..monitoring.costmodel import balance_stages, layer_costs

    rows = layer_costs(net, batch)
    bounds = balance_stages([float(r["flops"]) for r in rows], n_stages)
    return [[rows[i]["layer"] for i in range(a, b)] for a, b in bounds]


# --------------------------------------------------------- transformer wiring


def stack_blocks(block_list):
    """List of per-layer param dicts -> stacked tree with leading layer dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *block_list)


def unstack_blocks(stacked, n_layers: int):
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n_layers)]


def canonical_pp_params(params):
    """models.transformer init_params output -> canonical PP train state:
    blocks stacked ``[L, ...]`` (layer-major), embed/mlm untouched. This is
    the layout :class:`PipelineParallelTrainer` stores and checkpoints —
    stage views are built INSIDE the compiled step from the static index
    map, so re-balancing (or restoring onto a different topology) never
    moves parameters, and a ``pipe``-sharded checkpoint restores bitwise
    onto an ``fsdp`` layout (both shard the same leading layer dim)."""
    blocks = params["blocks"]
    if not isinstance(blocks, list):
        return params  # already canonical
    return {"embed": params["embed"], "blocks": stack_blocks(blocks),
            "mlm": params["mlm"]}


def pipeline_transformer_params(params, n_stages: int, boundaries=None):
    """Convert models.transformer init_params output to the PP layout.

    Without ``boundaries`` the layer count must divide evenly — a ragged
    depth raises a ValueError naming both numbers (it used to be accepted
    silently downstream in manual setups). With ``boundaries`` (from
    :func:`transformer_stage_boundaries` /
    ``monitoring.costmodel.balance_stages``) the canonical ``[L, ...]``
    layout is returned and the (possibly ragged) stage view is built inside
    the loss from the same boundaries."""
    blocks = params["blocks"]
    L = len(blocks)
    if boundaries is not None:
        idx, _ = stage_index_map(boundaries, L)
        if idx.shape[0] != n_stages:
            raise ValueError(
                f"boundaries describe {idx.shape[0]} stages, expected {n_stages}")
        return canonical_pp_params(params)
    uniform_boundaries(L, n_stages)  # raises loudly on ragged depth
    stacked = stack_blocks(blocks)  # [L, ...]
    staged = jax.tree.map(
        lambda x: x.reshape(n_stages, L // n_stages, *x.shape[1:]), stacked)
    return {"embed": params["embed"], "blocks": staged, "mlm": params["mlm"]}


def pipeline_partition_specs(params_pp, *, pipe_axis: str = AXIS_PIPE):
    """Specs for the PP layout: blocks sharded on the stage dim, embed/mlm
    replicated (GSPMD still dp-shards their compute via the batch)."""
    return {
        "embed": jax.tree.map(lambda _: P(), params_pp["embed"]),
        "blocks": jax.tree.map(
            lambda x: P(pipe_axis, *([None] * (x.ndim - 1))), params_pp["blocks"]),
        "mlm": jax.tree.map(lambda _: P(), params_pp["mlm"]),
    }


def transformer_pp_loss_fn(cfg, n_microbatches: int, mesh: Mesh,
                           *, pipe_axis: str = AXIS_PIPE, data_axis="auto",
                           schedule: str = "gpipe", boundaries=None):
    """Build loss(params_pp, batch) running blocks through a pipeline schedule.

    Embedding and the MLM head run outside the pipeline body (dp-sharded by
    GSPMD) via the same ``models.transformer`` helpers the single-device path
    uses; the stacked blocks run inside shard_map with pad_mask traveling as
    a per-microbatch aux input. Deterministic (no dropout) — PP training v1
    matches the reference's inference-mode parity bar; dropout needs
    per-stage rng plumbing (future work).

    ``boundaries=None`` expects the staged ``[S, L/S, ...]`` block layout of
    :func:`pipeline_transformer_params`. With ``boundaries`` the params hold
    canonical ``[L, ...]`` blocks and the (possibly ragged, cost-balanced)
    stage view is built here from the static index map — padded slots are
    masked out of both the forward carry and their cotangents. With
    ``cfg.remat`` the scan body is wrapped in ``jax.checkpoint`` so peak
    activation memory per stage stays flat as depth grows.
    """
    from ..models import transformer as T

    if cfg.dropout and cfg.dropout > 0.0:
        raise ValueError(
            "pipeline-parallel training runs deterministic (per-stage dropout "
            "rng plumbing not implemented); set cfg.dropout=0.0 explicitly — "
            "silently dropping regularization would diverge from the "
            "single-device path")
    if boundaries is not None:
        idx_np, valid_np = stage_index_map(boundaries)
        S, Lmax = valid_np.shape
        if pipe_axis in mesh.shape and mesh.shape[pipe_axis] != S:
            raise ValueError(
                f"boundaries describe {S} stages but mesh axis "
                f"'{pipe_axis}' has {mesh.shape[pipe_axis]} shards")
        flat_idx = jnp.asarray(idx_np.reshape(-1))
        valid_const = jnp.asarray(valid_np)

    def _scan_blocks(stage_blocks, h, pad_mask, vcol=None):
        # stage_blocks: [L/S or Lmax, ...] — scan over the in-stage layers;
        # vcol gates padded slots of a ragged (cost-balanced) stage
        if vcol is None:
            def body(carry, blk):
                return T._block(cfg, blk, carry, pad_mask, None, False), None

            xs_scan = stage_blocks
        else:
            def body(carry, sl):
                blk, v = sl
                out = T._block(cfg, blk, carry, pad_mask, None, False)
                return jnp.where(v > 0.5, out, carry), None

            xs_scan = (stage_blocks, vcol)
        if cfg.remat:
            body = jax.checkpoint(body)
        out, _ = jax.lax.scan(body, h, xs_scan)
        return out

    if boundaries is None:
        def stage_fn(stage_blocks, h, pad_mask):
            return _scan_blocks(stage_blocks, h, pad_mask)
    else:
        def stage_fn(stage_params, h, pad_mask):
            return _scan_blocks(stage_params["b"], h, pad_mask,
                                stage_params["v"])

    def loss(params_pp, batch):
        h = T.embed(params_pp, batch["tokens"], cfg, segments=batch.get("segments"))
        xs = microbatch(h, n_microbatches)
        pm = batch.get("pad_mask")
        aux = None if pm is None else microbatch(pm, n_microbatches)
        if boundaries is None:
            stacked = params_pp["blocks"]  # [S, L/S, ...]
        else:
            # canonical [L, ...] -> padded [S, Lmax, ...] via the static
            # index map; the take's scatter-add transpose routes padded-slot
            # cotangents (exact zeros, thanks to the where gate) to layer 0
            stacked = {
                "b": jax.tree.map(
                    lambda x: jnp.take(x, flat_idx, axis=0).reshape(
                        S, Lmax, *x.shape[1:]),
                    params_pp["blocks"]),
                "v": valid_const,
            }
        if aux is None:
            ys = spmd_pipeline(lambda p, x: stage_fn(p, x, None), stacked,
                               xs, mesh, pipe_axis=pipe_axis,
                               data_axis=data_axis, schedule=schedule)
        else:
            ys = spmd_pipeline(stage_fn, stacked, xs, mesh,
                               pipe_axis=pipe_axis, data_axis=data_axis,
                               aux=aux, schedule=schedule)
        h = unmicrobatch(ys)
        logits = T.mlm_head(params_pp, h, cfg)
        return T.token_ce_loss(logits, batch["labels"], batch.get("weights"))

    return loss


def make_pp_train_step(cfg, updater, n_microbatches: int, mesh: Mesh,
                       *, pipe_axis: str = AXIS_PIPE, data_axis="auto",
                       schedule: str = "gpipe", boundaries=None):
    """Full PP train step: pipeline loss + grads + updater + apply. Grads of
    the stacked blocks land sharded over the pipe axis (each stage's HBM only
    holds its own layers + optimizer state — the PP memory win)."""
    loss_fn = transformer_pp_loss_fn(cfg, n_microbatches, mesh,
                                     pipe_axis=pipe_axis, data_axis=data_axis,
                                     schedule=schedule, boundaries=boundaries)

    def step(params_pp, opt_state, batch, iteration):
        loss, grads = jax.value_and_grad(loss_fn)(params_pp, batch)
        updates, new_opt = updater.apply(grads, opt_state, params_pp, iteration, 0)
        new_params = jax.tree.map(lambda p, u: (p - u).astype(p.dtype), params_pp, updates)
        return new_params, new_opt, loss

    return step


# ------------------------------------------------------------------- trainer


def _stage_forward_probe(cfg, stage_blocks, h):
    """One stage's forward on a probe activation (profiling only)."""
    from ..models import transformer as T

    def body(carry, blk):
        return T._block(cfg, blk, carry, None, None, False), None

    out, _ = jax.lax.scan(body, h, stage_blocks)
    return out


class _PipelineNet:
    """Minimal net-protocol shim: exactly the surface the trainer scaffolding
    (heartbeat/flight/faults/phases) and ``TrainingCheckpointer`` consume —
    ``params_`` / ``updater_state`` / ``bn_state`` / ``iteration`` /
    ``epoch`` / ``score_``."""

    def __init__(self, params_pp, updater_state=None):
        self.params_ = params_pp
        self.updater_state = {} if updater_state is None else updater_state
        self.bn_state = {}
        self.iteration = 0
        self.epoch = 0
        self.score_ = float("nan")  # checkpointer idiom: nan -> no score yet


class PipelineParallelTrainer(ParallelTrainer):
    """Pipeline-parallel trainer for the flagship transformer over a
    ``pipe`` mesh axis (composes with ``data``/``fsdp``/``tp`` via
    :class:`~deeplearning4j_tpu.parallel.partition.SpecLayout`).

    Same config surface as the fsdp/tp path: pass ``mesh_layout=SpecLayout
    (pipe=S, ...)`` (or a pre-built ``PipelinePartitioner``). Parameters are
    stored CANONICALLY — blocks stacked ``[L, ...]``, sharded on the layer
    dim over the pipe axis — and the compiled step builds the stage view
    from a static index map, so:

    - stage boundaries come from the cost model
      (``monitoring.costmodel.balance_stages`` over per-layer predicted
      flops) and re-balancing on measured skew only recompiles the step, it
      never moves parameters;
    - checkpoints ride the generational lineage untouched, and a ``pipe=S``
      checkpoint restores onto an ``fsdp=F`` layout (and back) bitwise via
      ``reshard=True`` — both layouts chunk the same leading layer dim.

    Batches are plain dicts (``tokens``/``labels`` + optional ``pad_mask``/
    ``segments``/``weights``); the inherited ``_fit_core`` provides
    heartbeat, flight recording, fault points, step-phase attribution and
    step metrics.
    """

    _supports_pipe = True

    def __init__(self, params, cfg, updater, mesh_layout, *,
                 n_microbatches: int, schedule: str = "1f1b",
                 boundaries=None, layer_costs=None,
                 rebalance_threshold: float = 1.2, mesh: Optional[Mesh] = None):
        from .partition import PipelinePartitioner, SpecLayout

        if schedule not in SCHEDULES:
            raise ValueError(
                f"unknown pipeline schedule '{schedule}': {SCHEDULES}")
        if isinstance(mesh_layout, SpecLayout):
            mesh_layout = PipelinePartitioner(mesh_layout, mesh=mesh)
            mesh = None
        layout = mesh_layout.layout
        if layout.pipe == 1:
            raise ValueError(
                "PipelineParallelTrainer needs a pipe axis of size >= 2 in "
                "mesh_layout (got pipe=1); for pure data/fsdp/tp training "
                "use ParallelTrainer")
        canonical = canonical_pp_params(params)
        net = _PipelineNet(canonical, updater.init(canonical))
        super().__init__(net, mesh=mesh, mesh_layout=mesh_layout)
        self.cfg = cfg
        self.updater = updater
        self.n_microbatches = int(n_microbatches)
        self.schedule = schedule
        self.rebalance_threshold = float(rebalance_threshold)
        self.n_stages = int(layout.pipe)
        self.n_layers = int(jax.tree.leaves(canonical["blocks"])[0].shape[0])
        self._layer_costs = [float(c) for c in (
            layer_costs if layer_costs is not None
            else self.predicted_layer_costs())]
        if len(self._layer_costs) != self.n_layers:
            raise ValueError(
                f"{len(self._layer_costs)} layer costs for "
                f"{self.n_layers} layers")
        if boundaries is None:
            from ..monitoring.costmodel import balance_stages

            boundaries = balance_stages(self._layer_costs, self.n_stages)
        idx, _ = stage_index_map(boundaries, self.n_layers)
        if idx.shape[0] != self.n_stages:
            raise ValueError(
                f"boundaries describe {idx.shape[0]} stages, layout has "
                f"pipe={self.n_stages}")
        self.boundaries = [(int(a), int(b)) for a, b in boundaries]
        self._pp_step_fn = None
        from ..monitoring.partition import pipe_metrics

        pipe_metrics().stages.set(self.n_stages)

    # -- cost model ---------------------------------------------------------

    def predicted_layer_costs(self) -> List[float]:
        """Per-layer predicted flops from the transformer cost model — the
        input to the min-max stage partitioner."""
        from ..models import transformer as T

        rows = T.layer_costs(self.cfg, 1, min(self.cfg.max_len, 128))
        return [float(r["flops"]) for r in rows
                if r["kind"] == "TransformerBlock"]

    def predicted_stage_costs(self) -> List[float]:
        from ..monitoring.costmodel import stage_costs

        return stage_costs(self._layer_costs, self.boundaries)

    # -- compiled step ------------------------------------------------------

    def _pp_step(self):
        if self._pp_step_fn is None:
            step = make_pp_train_step(
                self.cfg, self.updater, self.n_microbatches, self.mesh,
                pipe_axis=self.partitioner.layout.pipe_axis,
                data_axis=self.data_axis, schedule=self.schedule,
                boundaries=self.boundaries)
            self._pp_step_fn = jax.jit(step, donate_argnums=(0, 1))
        return self._pp_step_fn

    # -- fit ----------------------------------------------------------------

    def fit(self, batches, epochs: int = 1, prefetch: int = 0):
        """``batches``: iterable of dict minibatches (see class docstring).
        ``prefetch`` is accepted for signature parity; dict batches arrive
        host-materialized and are staged per-step."""
        self._place_net()
        try:
            for _ in range(epochs):
                it = iter(batches)
                while True:
                    with self._phases.phase("input"):
                        try:
                            b = next(it)
                        except StopIteration:
                            break
                    self._fit_batch(b)
                self._phases.discard()
                self.net.epoch += 1
        finally:
            aggregate.maybe_spool(force=True)
            flight.flush()
        return self.net

    def _fit_batch(self, batch):
        self._place_net()  # idempotent: direct _fit_batch callers skip fit()
        self._fit_core(dict(batch))

    def _fit_core_inner(self, batch):
        n = self.net
        placed = {k: self._shard(jnp.asarray(v))
                  for k, v in batch.items() if v is not None}
        step = self._pp_step()
        n.params_, n.updater_state, loss = step(
            n.params_, n.updater_state, placed,
            jnp.asarray(n.iteration, jnp.int32))
        n.score_ = loss  # lazy: syncs only when read
        n.iteration += 1

    # -- measured-skew re-balancing -----------------------------------------

    def profile_stages(self, *, seq: Optional[int] = None, batch_size: int = 1,
                       repeats: int = 3) -> List[float]:
        """Measured per-stage forward wall seconds on a probe activation;
        published as ``tdl_pipe_stage_seconds{stage}``. The comparison
        against :meth:`predicted_stage_costs` is what drives
        :meth:`maybe_rebalance`."""
        from ..monitoring.partition import pipe_metrics

        T_ = int(seq or min(self.cfg.max_len, 64))
        h = jnp.zeros((int(batch_size), T_, self.cfg.d_model), jnp.float32)
        blocks = self.net.params_["blocks"]
        pm = pipe_metrics()
        times = []
        for s, (a, b) in enumerate(self.boundaries):
            stage_blocks = jax.tree.map(lambda x: x[a:b], blocks)
            fn = jax.jit(functools.partial(_stage_forward_probe, self.cfg))  # donate-ok: read-only profiling forward, params reused across repeats
            jax.block_until_ready(fn(stage_blocks, h))  # compile outside the clock
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = fn(stage_blocks, h)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / max(1, repeats)
            times.append(dt)
            pm.stage_seconds.labels(str(s)).set(dt)
        return times

    def maybe_rebalance(self, measured_stage_seconds: Optional[Sequence[float]] = None):
        """Re-partition stages when measured skew exceeds the threshold.

        Skew = max(measured) / mean(measured). Above ``rebalance_threshold``
        (default 1.2×) each stage's layers get their predicted costs scaled
        by that stage's measured/predicted ratio, and the min-max partitioner
        re-runs on the corrected costs. A changed split records a
        ``pipe_rebalance`` flight event naming old and new boundaries, bumps
        ``tdl_pipe_rebalances_total``, and invalidates the compiled step
        (canonical storage means nothing else moves). Returns the new
        boundaries, or None when balanced/unchanged."""
        from ..monitoring.costmodel import balance_stages
        from ..monitoring.partition import pipe_metrics

        measured = [float(x) for x in (
            measured_stage_seconds if measured_stage_seconds is not None
            else self.profile_stages())]
        if len(measured) != self.n_stages:
            raise ValueError(
                f"{len(measured)} stage timings for {self.n_stages} stages")
        mean = sum(measured) / self.n_stages
        skew = (max(measured) / mean) if mean > 0 else 1.0
        if skew <= self.rebalance_threshold:
            return None
        predicted = self.predicted_stage_costs()
        costs = list(self._layer_costs)
        for (a, b), meas, pred in zip(self.boundaries, measured, predicted):
            factor = (meas / pred) if pred > 0 else 1.0
            for i in range(a, b):
                costs[i] = self._layer_costs[i] * factor
        new = [(int(a), int(b)) for a, b in
               balance_stages(costs, self.n_stages)]
        self._layer_costs = costs
        if new == self.boundaries:
            return None
        old = self.boundaries
        self.boundaries = new
        self._pp_step_fn = None  # recompile with the new static index map
        pipe_metrics().rebalances.inc()
        flight.record("pipe_rebalance",
                      old_boundaries=[list(x) for x in old],
                      new_boundaries=[list(x) for x in new],
                      skew=float(skew))
        return new
