"""Train-step builders.

Two distribution modes:

  compressed (paper-faithful, Algorithm 1)
      The data (and pod) mesh axes are *manual* (jax.shard_map partial-manual;
      the model/tensor axis stays auto under GSPMD). Each data replica computes
      its local gradient with NO automatic cross-replica reduction, sparsifies
      it per leaf (Q(g), section 3), and the replicas exchange compressed
      messages via repro.comm.sync_tree. Parameters are replicated across the
      data axis inside the step (ZeRO-1 layout: optimizer state may still be
      sharded outside).

  fsdp (baseline / giant models)
      Pure GSPMD: XLA inserts dense reduce-scatter/all-gather. Optionally
      applies Q() to the *averaged* gradient (Algorithm 1, step 7) which is
      sharding-agnostic and keeps unbiasedness.

Both return metrics including the paper's `var` ratio and message-bit
accounting so benchmarks can plot loss-vs-communication.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.sync import sync_tree
from repro.core.api import CompressionConfig, compress_tree
from repro.core.stages import stage
from repro.dist import sharding as shd
from repro.models import transformer
from repro.models.common import split_params
from repro.optim.optimizers import (ControlState, FeedbackState, Optimizer,
                                    init_control, init_feedback,
                                    rescale_feedback)
from repro.train.loss import lm_loss, shift_targets


def make_loss_fn(cfg: transformer.ModelConfig) -> Callable:
    """``loss_fn(params, batch) -> (loss, counters)``: the model's counters
    (``transformer.forward_train_counted``) ride out as the aux of
    ``value_and_grad(..., has_aux=True)`` into the step's metrics."""
    def loss_fn(params, batch):
        logits, aux, counters = transformer.forward_train_counted(
            params, cfg, batch)
        targets, mask = shift_targets(batch["tokens"])
        if "loss_mask" in batch:
            mask = mask * batch["loss_mask"]
        return lm_loss(logits, targets, mask) + aux, counters
    return loss_fn


def _strip_manual(rules: dict, manual: tuple[str, ...]) -> dict:
    """Activation rules usable inside a shard_map where `manual` axes are
    already manual: drop them from every entry."""
    out = {}
    for k, v in rules.items():
        axes = shd._as_tuple(v)
        kept = tuple(a for a in axes if a not in manual)
        out[k] = kept if kept else None
    return out


def mesh_workers(mesh, multi_pod: bool = False) -> int:
    """Global worker count of the compressed step: the product of the manual
    data (and pod) mesh axes — the leading-axis size of the stacked
    per-worker gradient / FeedbackState layout."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes["data"]
    if multi_pod:
        n *= sizes["pod"]
    return n


def init_compressed_feedback(cfg: transformer.ModelConfig,
                             comp: CompressionConfig, mesh,
                             multi_pod: bool = False) -> FeedbackState:
    """Zero FeedbackState in the compressed step's stacked per-worker
    layout (leading axis = mesh_workers(mesh)), structure matching the
    model's gradient tree. With ``comp.resparsify_pods`` on a multi-pod
    mesh the state additionally carries the pod-stage residual (leading
    axis = pod count, replicated over the data axis)."""
    if not comp.error_feedback:
        raise ValueError("init_compressed_feedback with error_feedback=False")
    # shapes only — never materialize (or randomly initialize) the params
    param_sds = jax.eval_shape(lambda k: transformer.init_model(k, cfg),
                               jax.random.key(0))
    vals, _ = split_params(param_sds)
    num_pods = None
    if multi_pod and comp.resparsify_pods:
        num_pods = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]
    return init_feedback(vals, num_workers=mesh_workers(mesh, multi_pod),
                         num_pods=num_pods)


def init_compressed_control(cfg: transformer.ModelConfig,
                            comp: CompressionConfig, mesh,
                            multi_pod: bool = False) -> ControlState:
    """Zero ControlState for the adaptive compressed step: last_sent and
    the per-leaf bound in the stacked per-worker layout (leading axis =
    mesh_workers(mesh)), last_avg params-shaped. Carried and checkpointed
    alongside the FeedbackState."""
    if not comp.adaptive:
        raise ValueError("init_compressed_control with adaptive=False")
    param_sds = jax.eval_shape(lambda k: transformer.init_model(k, cfg),
                               jax.random.key(0))
    vals, _ = split_params(param_sds)
    return init_control(vals, num_workers=mesh_workers(mesh, multi_pod))


def compressed_state_shardings(mesh, state: tuple,
                               multi_pod: bool = False) -> tuple:
    """Where the compressed step keeps its state between steps: params,
    optimizer state and every params-shaped tree replicated; the stacked
    per-worker trees (residual, last_sent, bound) split over the worker
    axes on their leading axis; the pod residual over ``pod``. Placing the
    first step's inputs so means every step runs one compiled program."""
    manual = ("pod", "data") if multi_pod else ("data",)
    rep = NamedSharding(mesh, P())
    worker = NamedSharding(mesh, P(manual if multi_pod else manual[0]))

    def like(tree, sharding):
        return jax.tree.map(lambda _: sharding, tree)

    out = [like(state[0], rep), like(state[1], rep)]
    for s in state[2:]:
        if isinstance(s, FeedbackState):
            pod = (None if s.pod_residual is None
                   else like(s.pod_residual, NamedSharding(mesh, P("pod"))))
            out.append(FeedbackState(residual=like(s.residual, worker),
                                     pod_residual=pod))
        else:
            out.append(ControlState(last_sent=like(s.last_sent, worker),
                                    last_avg=like(s.last_avg, rep),
                                    bound=like(s.bound, worker), step=rep))
    return tuple(out)


def make_compressed_train_step(cfg: transformer.ModelConfig,
                               comp: CompressionConfig,
                               opt: Optimizer,
                               mesh,
                               rules: dict,
                               multi_pod: bool = False,
                               var_adaptive_lr: bool = False,
                               shard_local_sync: bool = True,
                               lr_schedule: Callable | None = None) -> Callable:
    """Algorithm 1 as one jittable step: (params, opt_state, batch, key) ->
    (params, opt_state, metrics).

    With ``comp.error_feedback`` the step additionally carries the
    per-worker residual: (params, opt_state, ef_state, batch, key) ->
    (params, opt_state, ef_state, metrics), where ``ef_state`` is a
    FeedbackState whose leaves live in the same stacked per-worker layout as
    the gradients crossing the sync boundary (build one with
    ``init_compressed_feedback``). The residual rides the same shard_map
    in/out specs as the stacked grads, so it survives the manual-axis
    boundary, scan-over-layers stacking, and checkpointing like any other
    state pytree. With ``comp.resparsify_pods`` on a multi-pod mesh the
    state also carries ``pod_residual`` (leading pod axis, replicated over
    data), threading the pod-stage re-sparsification error through the
    same boundary.

    shard_local_sync: compress each tensor-parallel shard's gradient slice
    locally (nested shard_map over the model axis). Without it the top_k /
    probability computation runs on model-GLOBAL leaves and GSPMD all-gathers
    every gradient across the model axis (measured 465 GB/step/device on
    gemma2-27b train_4k — see EXPERIMENTS.md section Perf iter C2).
    Per-shard sparsification keeps the estimator unbiased (each shard is an
    independent Q over its coordinates).

    With ``comp.adaptive`` the step carries a ControlState after the
    FeedbackState: (params, opt_state, ef_state, ctl_state, batch, key) ->
    (params, opt_state, ef_state, ctl_state, metrics). Build the initial
    state with ``init_compressed_control``; its leaves ride the same
    stacked per-worker specs as the residual (last_avg params-shaped, the
    bound one scalar per worker per leaf).

    lr_schedule: the optimizer's step-size schedule, if any. With error
    feedback this enables the momentum-corrected variant (Karimireddy et
    al. 2019): the carried residual lives in the lr-scaled update domain,
    so it is rescaled by lr_prev/lr_now before each sync. A constant
    schedule (or lr_schedule=None) is a bit-exact no-op."""
    loss_fn = make_loss_fn(cfg)
    manual = ("pod", "data") if multi_pod else ("data",)
    inner_rules = _strip_manual(rules, manual)
    batch_spec = P(tuple(a for a in manual))   # batch dim sharded over manual axes

    # mark scan-over-layers stacks so compression runs per layer (paper 5.2)
    param_tree = jax.eval_shape(lambda k: transformer.init_model(k, cfg),
                                jax.random.key(0))
    vals_sds, param_axes = split_params(param_tree)
    def _is_axes(t):
        return isinstance(t, tuple) and all(isinstance(e, (str, type(None)))
                                            for e in t)
    stacked = jax.tree.map(lambda ax: len(ax) > 0 and ax[0] == "layers",
                           param_axes, is_leaf=_is_axes)
    # per-leaf model-axis specs (for the nested manual sync region)
    grad_specs = jax.tree.map(
        lambda v, ax: shd.resolve_spec(v.shape, ax, inner_rules, mesh),
        vals_sds, param_axes,
        is_leaf=lambda t: _is_axes(t) or hasattr(t, "shape"))

    pod_axis = "pod" if multi_pod else None

    def _spec_with(prefix, spec: P) -> P:
        return P(prefix, *tuple(spec))

    # grads leave the grad region stacked on a leading per-worker axis
    # (sharded over the manual axes); the sync region re-binds data(+pod)
    # AND model as manual, so compression is fully shard-local. SDY forbids
    # nested manual regions over the same axis, hence two sequential maps.
    worker_prefix = tuple(manual) if len(manual) > 1 else manual[0]
    stacked_specs = jax.tree.map(
        lambda s: _spec_with(worker_prefix, s), grad_specs,
        is_leaf=lambda t: isinstance(t, P))

    def grad_fn(params, batch):
        with stage("model"), shd.activation_sharding(inner_rules, mesh):
            (loss, counters), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        with stage("exchange"):
            loss, counters = jax.lax.pmean((loss, counters), manual)
        return loss, counters, jax.tree.map(lambda g: g[None], grads)

    # out_specs of a partial-manual region may only name ITS manual axes;
    # the model-dim sharding of each leaf stays auto here and is re-bound
    # manually by the sync region below.
    grad_out_specs = jax.tree.map(lambda s: P(worker_prefix), grad_specs,
                                  is_leaf=lambda t: isinstance(t, P))
    grad_sharded = jax.shard_map(
        grad_fn, mesh=mesh, in_specs=(P(), batch_spec),
        out_specs=(P(), P(), grad_out_specs),
        axis_names=set(manual), check_vma=False)

    sync_axes = set(manual) | ({"model"} if shard_local_sync else set())
    key_axes = tuple(sorted(sync_axes))   # per-worker RNG fold order
    ef = comp.error_feedback
    hier_ef = ef and comp.resparsify_pods and multi_pod

    @stage("exchange")
    def _reduce_stats(stats):
        if shard_local_sync:
            # each model shard sends its own message: totals sum, ratios avg
            stats = type(stats)(
                bits=jax.lax.psum(stats.bits, "model"),
                dense_bits=jax.lax.psum(stats.dense_bits, "model"),
                wire_bytes=jax.lax.psum(stats.wire_bytes, "model"),
                wire_bytes_intra=jax.lax.psum(stats.wire_bytes_intra, "model"),
                wire_bytes_inter=jax.lax.psum(stats.wire_bytes_inter, "model"),
                density=jax.lax.pmean(stats.density, "model"),
                var_ratio=jax.lax.pmean(stats.var_ratio, "model"),
                overflow=jax.lax.psum(stats.overflow, "model"),
                # the skip decision is model-uniform (sync_tree psums the
                # delta energy over the extra manual axes), so mean == value
                skipped=jax.lax.pmean(stats.skipped, "model"))
        return jax.tree.map(lambda s: jax.lax.pmean(s, manual), stats)

    def sync_fn(grads_stacked, key):
        grads = jax.tree.map(lambda g: g[0], grads_stacked)
        synced, _, stats = sync_tree(comp, key, grads,
                                     data_axis="data", pod_axis=pod_axis,
                                     stacked=stacked, key_axes=key_axes)
        return synced, _reduce_stats(stats)

    def sync_fn_ef(grads_stacked, res_stacked, key):
        # the residual enters/leaves in the same stacked per-worker layout
        # as the grads, so it shards identically across the manual axes
        grads = jax.tree.map(lambda g: g[0], grads_stacked)
        res = jax.tree.map(lambda r: r[0], res_stacked)
        synced, new_fb, stats = sync_tree(comp, key, grads,
                                          data_axis="data",
                                          pod_axis=pod_axis, stacked=stacked,
                                          key_axes=key_axes, feedback=res)
        return (synced, jax.tree.map(lambda r: r[None], new_fb.residual),
                _reduce_stats(stats))

    def sync_fn_adaptive(grads_stacked, res_stacked, ls_stacked, la,
                         b_stacked, stepc, key):
        # last_sent and the bound ride the stacked per-worker layout like
        # the residual; last_avg is params-shaped (every worker holds an
        # identical copy — the receiver side of delta coding); step is a
        # replicated scalar
        grads = jax.tree.map(lambda g: g[0], grads_stacked)
        res = jax.tree.map(lambda r: r[0], res_stacked)
        ctl = ControlState(
            last_sent=jax.tree.map(lambda s: s[0], ls_stacked),
            last_avg=la,
            bound=jax.tree.map(lambda x: x[0], b_stacked),
            step=stepc)
        synced, new_fb, new_ctl, stats = sync_tree(
            comp, key, grads, data_axis="data", pod_axis=pod_axis,
            stacked=stacked, key_axes=key_axes, feedback=res, control=ctl)
        return (synced,
                jax.tree.map(lambda r: r[None], new_fb.residual),
                jax.tree.map(lambda s: s[None], new_ctl.last_sent),
                new_ctl.last_avg,
                jax.tree.map(lambda x: x[None], new_ctl.bound),
                new_ctl.step,
                _reduce_stats(stats))

    def sync_fn_hier_ef(grads_stacked, res_stacked, pod_res_stacked, key):
        # worker residual rides the stacked per-worker layout; the pod
        # residual rides a leading POD axis, replicated over data (the pod
        # stage's input/key/state are data-axis-invariant, so every data
        # worker recomputes the identical new pod residual)
        grads = jax.tree.map(lambda g: g[0], grads_stacked)
        res = jax.tree.map(lambda r: r[0], res_stacked)
        pod_res = jax.tree.map(lambda r: r[0], pod_res_stacked)
        synced, new_fb, stats = sync_tree(
            comp, key, grads, data_axis="data", pod_axis=pod_axis,
            stacked=stacked, key_axes=key_axes,
            feedback=FeedbackState(residual=res, pod_residual=pod_res))
        return (synced, jax.tree.map(lambda r: r[None], new_fb.residual),
                jax.tree.map(lambda r: r[None], new_fb.pod_residual),
                _reduce_stats(stats))

    sync_in_specs = (stacked_specs if shard_local_sync
                     else jax.tree.map(lambda s: _spec_with(worker_prefix, P()),
                                       grad_specs,
                                       is_leaf=lambda t: isinstance(t, P)))
    sync_out_specs = (grad_specs if shard_local_sync
                      else jax.tree.map(lambda s: P(), grad_specs,
                                        is_leaf=lambda t: isinstance(t, P)))
    pod_res_specs = jax.tree.map(
        lambda s: P("pod", *tuple(s)) if shard_local_sync else P("pod"),
        grad_specs, is_leaf=lambda t: isinstance(t, P))
    # per-leaf [W] bound scalars: sharded over the worker axes, replicated
    # over model (the skip decision is uniform across one leaf's shards)
    bound_specs = jax.tree.map(lambda s: P(worker_prefix), grad_specs,
                               is_leaf=lambda t: isinstance(t, P))
    if comp.adaptive:
        sync_sharded = jax.shard_map(
            sync_fn_adaptive, mesh=mesh,
            in_specs=(sync_in_specs, sync_in_specs, sync_in_specs,
                      sync_out_specs, bound_specs, P(), P()),
            out_specs=(sync_out_specs, sync_in_specs, sync_in_specs,
                       sync_out_specs, bound_specs, P(), P()),
            axis_names=sync_axes, check_vma=False)
    elif hier_ef:
        sync_sharded = jax.shard_map(
            sync_fn_hier_ef, mesh=mesh,
            in_specs=(sync_in_specs, sync_in_specs, pod_res_specs, P()),
            out_specs=(sync_out_specs, sync_in_specs, pod_res_specs, P()),
            axis_names=sync_axes, check_vma=False)
    elif ef:
        sync_sharded = jax.shard_map(
            sync_fn_ef, mesh=mesh,
            in_specs=(sync_in_specs, sync_in_specs, P()),
            out_specs=(sync_out_specs, sync_in_specs, P()),
            axis_names=sync_axes, check_vma=False)
    else:
        sync_sharded = jax.shard_map(
            sync_fn, mesh=mesh, in_specs=(sync_in_specs, P()),
            out_specs=(sync_out_specs, P()),
            axis_names=sync_axes, check_vma=False)

    def _finish(loss, counters, grads, stats, opt_state, params):
        var_scale = jnp.maximum(stats.var_ratio, 1.0) if var_adaptive_lr else 1.0
        with stage("optimizer"):
            new_params, new_opt = opt.update(grads, opt_state, params,
                                             var_scale=var_scale)
        grad_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
        metrics = {"loss": loss, "grad_norm": jnp.sqrt(grad_sq),
                   "bits": stats.bits, "density": stats.density,
                   "var_ratio": stats.var_ratio, "wire_bytes": stats.wire_bytes,
                   "wire_bytes_intra": stats.wire_bytes_intra,
                   "wire_bytes_inter": stats.wire_bytes_inter,
                   "overflow": stats.overflow, "dense_bits": stats.dense_bits,
                   "skipped": stats.skipped, **counters}
        return new_params, new_opt, metrics

    def _maybe_rescale(ef_state, opt_state):
        # Karimireddy et al. 2019: the residual was accumulated under the
        # PREVIOUS step's lr — map it into the current step's update domain
        # before compressing. opt.update at count t applies lr_schedule(t+1),
        # so entering update number t the last applied lr was lr_schedule(t)
        # (at t == 0 there is no previous step and the residual is zero).
        if lr_schedule is None:
            return ef_state
        t = opt_state["step"]
        lr_now = lr_schedule(t + 1)
        lr_prev = jnp.where(t > 0, lr_schedule(jnp.maximum(t, 1)), lr_now)
        return rescale_feedback(ef_state, lr_prev, lr_now)

    def train_step(params, opt_state, batch, key):
        loss, counters, grads_stacked = grad_sharded(params, batch)
        grads, stats = sync_sharded(grads_stacked, key)
        return _finish(loss, counters, grads, stats, opt_state, params)

    def train_step_ef(params, opt_state, ef_state, batch, key):
        loss, counters, grads_stacked = grad_sharded(params, batch)
        ef_state = _maybe_rescale(ef_state, opt_state)
        grads, new_res, stats = sync_sharded(grads_stacked,
                                             ef_state.residual, key)
        new_params, new_opt, metrics = _finish(loss, counters, grads, stats,
                                               opt_state, params)
        return new_params, new_opt, FeedbackState(residual=new_res), metrics

    def train_step_hier_ef(params, opt_state, ef_state, batch, key):
        loss, counters, grads_stacked = grad_sharded(params, batch)
        ef_state = _maybe_rescale(ef_state, opt_state)
        grads, new_res, new_pod_res, stats = sync_sharded(
            grads_stacked, ef_state.residual, ef_state.pod_residual, key)
        new_params, new_opt, metrics = _finish(loss, counters, grads, stats,
                                               opt_state, params)
        return (new_params, new_opt,
                FeedbackState(residual=new_res, pod_residual=new_pod_res),
                metrics)

    def train_step_adaptive(params, opt_state, ef_state, ctl_state, batch,
                            key):
        loss, counters, grads_stacked = grad_sharded(params, batch)
        ef_state = _maybe_rescale(ef_state, opt_state)
        grads, new_res, new_ls, new_la, new_b, new_step, stats = sync_sharded(
            grads_stacked, ef_state.residual, ctl_state.last_sent,
            ctl_state.last_avg, ctl_state.bound, ctl_state.step, key)
        new_params, new_opt, metrics = _finish(loss, counters, grads, stats,
                                               opt_state, params)
        return (new_params, new_opt, FeedbackState(residual=new_res),
                ControlState(last_sent=new_ls, last_avg=new_la, bound=new_b,
                             step=new_step),
                metrics)

    if comp.adaptive:
        # adaptive forbids resparsify_pods (config validation), so the
        # hier-ef combination cannot arise here
        return train_step_adaptive
    if hier_ef:
        return train_step_hier_ef
    return train_step_ef if ef else train_step


def make_fsdp_train_step(cfg: transformer.ModelConfig,
                         comp: CompressionConfig | None,
                         opt: Optimizer,
                         mesh,
                         rules: dict) -> Callable:
    """GSPMD baseline; optional Q() on the averaged gradient (Alg. 1 step 7).

    With ``comp.error_feedback`` the step carries a FeedbackState with
    params-shaped leaves (``init_feedback(params)``) and the signature gains
    an ``ef_state`` argument/result, mirroring the compressed step. The
    residual here is of the *averaged* gradient (there is one logical
    compression per step), so it shards like the params under GSPMD."""
    loss_fn = make_loss_fn(cfg)
    param_tree = jax.eval_shape(lambda k: transformer.init_model(k, cfg),
                                jax.random.key(0))
    _, param_axes = split_params(param_tree)
    stacked = jax.tree.map(
        lambda ax: len(ax) > 0 and ax[0] == "layers", param_axes,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            isinstance(e, (str, type(None))) for e in t))
    ef = comp is not None and comp.name != "none" and comp.error_feedback

    def _grads(params, batch):
        with stage("model"), shd.activation_sharding(rules, mesh):
            (loss, counters), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        return loss, counters, grads

    def train_step(params, opt_state, batch, key):
        loss, counters, grads = _grads(params, batch)
        metrics = {"loss": loss, **counters}
        if comp is not None and comp.name != "none":
            with stage("compress"):
                q_tree, _, stats = compress_tree(comp, key, grads,
                                                 stacked=stacked)
            grads = q_tree
            metrics.update(bits=stats.bits, density=stats.density,
                           var_ratio=stats.var_ratio)
        with stage("optimizer"):
            new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, metrics

    def train_step_ef(params, opt_state, ef_state, batch, key):
        loss, counters, grads = _grads(params, batch)
        with stage("compress"):
            q_tree, new_res, stats = compress_tree(
                comp, key, grads, residual=ef_state.residual,
                stacked=stacked)
        metrics = {"loss": loss, "bits": stats.bits, "density": stats.density,
                   "var_ratio": stats.var_ratio, **counters}
        with stage("optimizer"):
            new_params, new_opt = opt.update(q_tree, opt_state, params)
        return (new_params, new_opt, FeedbackState(residual=new_res),
                metrics)

    return train_step_ef if ef else train_step


# ---------------------------------------------------------------------------
# Serving steps (no compression: gradient sparsification is a training method)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: transformer.ModelConfig, mesh=None, rules=None):
    def prefill_step(params, batch, caches):
        ctx = (shd.activation_sharding(rules, mesh)
               if rules is not None else _null_ctx())
        with ctx:
            return transformer.forward_prefill(params, cfg, batch, caches)
    return prefill_step


def make_decode_step(cfg: transformer.ModelConfig, mesh=None, rules=None):
    def decode_step(params, caches, tokens, pos):
        ctx = (shd.activation_sharding(rules, mesh)
               if rules is not None else _null_ctx())
        with ctx:
            return transformer.forward_decode(params, cfg, tokens, caches, pos)
    return decode_step


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
