"""Train-step builders — the paper's technique as a first-class feature.

Three selectable modes (``--decouple``):

  conventional   every device performs every operation (paper Fig. 3a):
                 pure GSPMD jit; XLA all-reduces gradients; the optimizer
                 update runs replicated across data rows.

  decoupled      the paper's strategy (Fig. 3c): the gradient REDUCTION is
                 decoupled onto a reducer service group (alpha rows of the
                 data axis). Compute rows stream raw gradient leaves
                 (optionally int8-compressed with error feedback); the
                 reducer group folds them on arrival, completes the small
                 intra-group aggregation (the paper's master step), and
                 broadcasts the reduced gradient back. Service rows skip
                 fwd/bwd at runtime via role-gated cond. Implemented with
                 partial-auto shard_map: manual over (pod, data), GSPMD
                 over model. With ``analytics_alpha > 0`` the topology is
                 a CHAIN (compute -> reduce -> analytics on one
                 `ServiceGraph`): the reducer streams the reduced
                 gradient onward to an analytics/logging service that
                 computes gradient statistics (norm, abs-max) off the
                 optimizer's critical path and feeds them into metrics.

  overlap        beyond-paper hillclimb: all devices compute; ZeRO-1
                 sharding constraints turn the gradient all-reduce into
                 reduce-scatter + param all-gather, which XLA's scheduler
                 overlaps with the update math. (See EXPERIMENTS.md §Perf.)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ServiceGraph, WireSpec
from repro.core.dataflow import COMPUTE, work_vector
from repro.core.decouple import group_psum
from repro.train import sharding
from repro.train.optimizer import OptConfig, apply_updates, init_opt_state
from repro.utils.compat import partial_shard_map

REDUCE = "reduce"
ANALYTICS = "analytics"


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    mode: str = "conventional"  # conventional | decoupled | overlap
    reduce_alpha: float = 1 / 16
    analytics_alpha: float = 0.0
    # wire codec of the decoupled grad stream: none | int8 | bf16
    # (declared on the ServiceGraph edge; the channel en/decodes)
    compress: str = "none"
    # wire granularity of the grad stream in bytes. None keeps the
    # unchunked whole-payload-per-wave fold (required when grad leaves
    # stay GSPMD-sharded over the model axis — packing would reshard);
    # set it on replicated/fully-manual setups to get the chunked
    # double-buffered schedule.
    wire_chunk_bytes: int | None = None
    zero1: bool = True  # overlap mode
    runtime_skip: bool = True  # cond-gate fwd/bwd off service rows
    # FSDP: shard params over the data axes too (all-gathered per layer
    # inside the scan). "auto" switches on when fp32 params exceed
    # fsdp_threshold bytes per device under model-parallel sharding only.
    fsdp: bool | str = "auto"
    fsdp_threshold: float = 6e9


def _loss_sum_and_count(model, params, batch):
    """Local-sum loss so distributed means combine exactly."""
    loss_mean, metrics = model.loss(params, batch)
    cnt = jnp.sum(batch["mask"])
    return loss_mean * cnt, (cnt, metrics)


def build_conventional_step(model, opt_cfg: OptConfig):
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch
        )
        new_params, new_state = apply_updates(opt_cfg, params, grads, opt_state)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_params, new_state, metrics

    return step


def build_overlap_step(model, opt_cfg: OptConfig, mesh, params_like, data_axes):
    """ZeRO-1: constrain grads/moments to data-sharded specs so XLA
    emits reduce-scatter + all-gather instead of all-reduce, and the
    update math runs on 1/data_size of each tensor per device."""
    model_size = mesh.shape["model"]
    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]
    pspecs = sharding.param_specs(params_like, model_size)
    zspecs = sharding.zero1_specs(params_like, pspecs, tuple(data_axes), data_size)

    def constrain(tree, specs):
        return jax.tree.map(
            lambda x, s: lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
            tree,
            specs,
        )

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch
        )
        grads = constrain(grads, zspecs)  # reduce-scatter point
        new_params, new_state = apply_updates(opt_cfg, params, grads, opt_state)
        new_params = constrain(new_params, pspecs)  # all-gather point
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_params, new_state, metrics

    return step


def train_service_graph(mesh, ts_cfg: TrainStepConfig, axis: str = "data") -> ServiceGraph:
    """The decoupled train topology: compute -> reduce, chained onward
    to an analytics service when ``analytics_alpha > 0`` (Fig. 3c). The
    grad stream's wire (codec + chunk granularity) is declared on the
    compute -> reduce edge — this is the one-argument opt-in."""
    stages = {REDUCE: ts_cfg.reduce_alpha}
    edges = [(COMPUTE, REDUCE)]
    codec = "identity" if ts_cfg.compress in ("none", "") else ts_cfg.compress
    wire = {
        (COMPUTE, REDUCE): WireSpec(
            codec=codec, chunk_bytes=ts_cfg.wire_chunk_bytes
        )
    }
    if ts_cfg.analytics_alpha > 0:
        stages[ANALYTICS] = ts_cfg.analytics_alpha
        edges.append((REDUCE, ANALYTICS))
    return ServiceGraph.build(mesh, stages=stages, edges=edges, axis=axis, wire=wire)


def train_stage_traits(ts_cfg: TrainStepConfig):
    """Calibration traits of the decoupled train chain (core/adapt.py):
    folding one token's gradient contribution costs a small fraction of
    its fwd/bwd, and the grad stream's wire bytes amortize per token."""
    from repro.core.adapt import StageTrait

    traits = [StageTrait(REDUCE, cost_ratio=0.2, bytes_per_item=64.0)]
    if ts_cfg.analytics_alpha > 0:
        traits.append(StageTrait(ANALYTICS, cost_ratio=0.05, bytes_per_item=64.0))
    return tuple(traits)


def build_decoupled_step(
    model,
    opt_cfg: OptConfig,
    graph: ServiceGraph,
    ts_cfg: TrainStepConfig,
    manual_axes: tuple[str, ...],
):
    """The faithful decoupled step (per-device code under shard_map).

    manual_axes is ("data",) on a single pod or ("pod", "data") on the
    multi-pod mesh; streams flow over `gmesh.axis` ("data") within each
    pod, and reducer partial results psum over "pod".
    """
    gmesh = graph.gmesh
    channel = graph.channel(COMPUTE, REDUCE)
    pods = [a for a in manual_axes if a != gmesh.axis]

    def step(params, opt_state, batch):
        row = lax.axis_index(gmesh.axis)
        g = gmesh.compute
        is_compute = (row >= g.start) & (row < g.stop)

        def compute_branch():
            (loss_sum, (cnt, metrics)), grads = jax.value_and_grad(
                functools.partial(_loss_sum_and_count, model), has_aux=True
            )(params, batch)
            return loss_sum, cnt, metrics, grads

        def idle_branch():
            # zeros with the structure of compute_branch's outputs
            zero_g = jax.tree.map(jnp.zeros_like, params)
            out_shape = jax.eval_shape(
                functools.partial(_loss_sum_and_count, model), params, batch
            )
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), out_shape)
            return zeros[0], zeros[1][0], zeros[1][1], zero_g

        if ts_cfg.runtime_skip:
            loss_sum, cnt, metrics, grads = lax.cond(
                is_compute, compute_branch, idle_branch
            )
        else:
            loss_sum, cnt, metrics, grads = compute_branch()

        # ---- the decoupled reduce: stream grad leaves to the reducer group.
        # The channel's wire (declared on the graph edge) owns compression
        # and chunking; raw grads in, decoded fold out.
        acc = channel.stream_fold_tree(grads)
        # master aggregation within the service group (cheap: alpha*P rows)
        acc = group_psum(acc, gmesh, REDUCE)
        for pod_axis in pods:
            acc = jax.tree.map(lambda x: lax.psum(x, pod_axis), acc)
        # token-count normalization (global mean over real tokens)
        total_cnt = lax.psum(cnt, gmesh.axis)
        for pod_axis in pods:
            total_cnt = lax.psum(total_cnt, pod_axis)
        # ---- chained stage: reducer streams the reduced grads onward to the
        # analytics service (paper Fig. 3c inter-group pipelining); the
        # grad-statistics reductions leave the optimizer's critical path
        grad_stats = None
        if graph.has_edge(REDUCE, ANALYTICS):
            a_channel = graph.channel(REDUCE, ANALYTICS)
            arrived = a_channel.stream_fold_tree(
                acc,
                acc_init=jax.tree.map(jnp.zeros_like, acc),
                # reduce rows hold identical post-psum grads: overwrite, not sum
                combine=lambda a, new, ok: jax.tree.map(
                    lambda x, y: jnp.where(ok, y, x), a, new
                ),
            )
            leaves = jax.tree.leaves(arrived)
            gn2 = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
            gmax = jnp.max(
                jnp.stack([jnp.max(jnp.abs(l)) for l in leaves])
            ).astype(jnp.float32)
            grad_stats = graph.broadcast_from(
                ANALYTICS, jnp.stack([jnp.sqrt(gn2), gmax])
            )
        # broadcast the reduced gradient back to every row
        reduced = channel.broadcast_from_consumer(acc)
        reduced = jax.tree.map(lambda x: x / jnp.maximum(total_cnt, 1.0), reduced)

        new_params, new_state = apply_updates(opt_cfg, params, reduced, opt_state)

        loss_tot = lax.psum(loss_sum, gmesh.axis)
        for pod_axis in pods:
            loss_tot = lax.psum(loss_tot, pod_axis)
        # number of compute shards across all pods (for metric means)
        n_compute = lax.psum(jnp.where(is_compute, 1.0, 0.0), gmesh.axis)
        for pod_axis in pods:
            n_compute = lax.psum(n_compute, pod_axis)
        out_metrics = {"loss": loss_tot / jnp.maximum(total_cnt, 1.0)}
        # per-row token counter (adaptive loop's work signal): each row's
        # real-token count gathered into one replicated vector; pods sum
        work_rows = work_vector(gmesh, cnt)
        for pod_axis in pods:
            work_rows = lax.psum(work_rows, pod_axis)
        out_metrics["work_rows"] = work_rows
        if grad_stats is not None:
            # statistics of the token-normalized gradient, computed on
            # the analytics group and broadcast into the metrics
            out_metrics["grad_norm"] = grad_stats[0] / jnp.maximum(total_cnt, 1.0)
            out_metrics["grad_absmax"] = grad_stats[1] / jnp.maximum(total_cnt, 1.0)
        for k, v in metrics.items():
            vv = lax.psum(jnp.where(is_compute, v, 0.0), gmesh.axis)
            for pod_axis in pods:
                vv = lax.psum(vv, pod_axis)
            out_metrics[k] = vv / jnp.maximum(n_compute, 1.0)
        return new_params, new_state, out_metrics

    return step


def make_jitted_step(
    model,
    mesh,
    opt_cfg: OptConfig,
    ts_cfg: TrainStepConfig,
    params_like,
    batch_like,
    *,
    multi_pod: bool = False,
    donate: bool = True,
):
    """Build the jitted train step + shardings for (params, opt, batch)."""
    model_size = mesh.shape["model"]
    data_axes = ("pod", "data") if multi_pod else ("data",)
    batch_axes = data_axes if len(data_axes) > 1 else data_axes[0]
    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]

    pspecs = sharding.param_specs(params_like, model_size)
    # FSDP: big models can't replicate fp32 params across data rows
    param_bytes = sum(
        int(np.prod(l.shape)) * 4 for l in jax.tree.leaves(params_like)
    )
    use_fsdp = (
        param_bytes / model_size > ts_cfg.fsdp_threshold
        if ts_cfg.fsdp == "auto"
        else bool(ts_cfg.fsdp)
    )
    if use_fsdp:
        pspecs = sharding.zero1_specs(params_like, pspecs, tuple(data_axes), data_size)
    opt_like = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), params_like)
    if use_fsdp:
        mspec = pspecs  # moments follow the fsdp param sharding
    elif ts_cfg.mode == "overlap" and ts_cfg.zero1:
        mspec = sharding.zero1_specs(params_like, pspecs, tuple(data_axes), data_size)
    else:
        mspec = pspecs
    ospecs = {"step": P()}
    if "m" in opt_like:
        ospecs["m"] = mspec
    if "v" in opt_like:
        ospecs["v"] = mspec
    bspecs = {k: sharding.batch_specs(batch_axes)[k] for k in batch_like}

    if ts_cfg.mode == "conventional":
        step = build_conventional_step(model, opt_cfg)
    elif ts_cfg.mode == "overlap":
        step = build_overlap_step(model, opt_cfg, mesh, params_like, data_axes)
    elif ts_cfg.mode == "decoupled":
        graph = train_service_graph(mesh, ts_cfg)
        inner = build_decoupled_step(model, opt_cfg, graph, ts_cfg, data_axes)
        # manual over the data axes; model stays GSPMD-auto unless it
        # has one device, where a fully manual region lets the wave fold
        # run the Pallas kernel (Mosaic calls cannot be auto-partitioned)
        manual_axes = data_axes if model_size > 1 else tuple(mesh.axis_names)
        manual_batch = {
            k: P(*((batch_axes,) + (None,) * (len(batch_like[k].shape) - 1)))
            for k in batch_like
        }
        step = partial_shard_map(
            inner,
            mesh,
            (P(), P(), manual_batch),
            (P(), P(), P()),
            manual_axes,
        )
    else:
        raise ValueError(ts_cfg.mode)

    in_sh = (
        sharding.named(mesh, pspecs),
        sharding.named(mesh, ospecs if ts_cfg.mode != "decoupled" else _match_opt(ospecs, opt_like, pspecs)),
        sharding.named(mesh, bspecs),
    )
    out_sh = (in_sh[0], in_sh[1], None)
    jitted = jax.jit(
        step,
        in_shardings=in_sh,
        out_shardings=out_sh,
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, in_sh


def _match_opt(ospecs, opt_like, pspecs):
    # decoupled mode: moments replicated over data rows (consistent by
    # construction: every row applies the same broadcast gradient)
    out = {"step": P()}
    if "m" in opt_like:
        out["m"] = pspecs
    if "v" in opt_like:
        out["v"] = pspecs
    return out
