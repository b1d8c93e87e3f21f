"""Training step factory: grad accumulation over microbatches, remat,
AdamW, optional int8 error-feedback compression of the cross-pod (DCN)
gradient reduction.

Two lowering modes:
  * plain pjit — XLA auto-partitions everything; gradient reduction over
    ("pod","data") is inserted by the partitioner (baseline).
  * pod-manual — shard_map manual on the "pod" axis, auto on (data, model):
    grads come out per-pod; the pod hop is an explicit int8-compressed
    all-reduce (4x fewer DCN bytes), with error feedback carried in the
    optimizer state. This is the beyond-paper distributed-optimization trick
    (DESIGN.md §8) applied to the paper's locality principle.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compat import shard_map

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import ShardCtx
from repro.models import model as M
from repro.train import compression as comp
from repro.train.optimizer import (OptConfig, adamw_update,
                                   clip_by_global_norm, init_opt_state)


class _PodLocalCtx(ShardCtx):
    """Activation constraints inside the pod-manual region. The batch dim is
    left to the partitioner: constraining it to the data axis there crashes
    XLA's SPMD partitioner (a device-group check in
    ExpandDeviceGroupsWithIota). Every ``constrain`` call puts the batch dim
    first."""

    def constrain(self, x, *spec):
        return super().constrain(x, P.UNCONSTRAINED, *spec[1:])


def _split_microbatches(batch: Dict[str, jax.Array], n_mb: int):
    def split(x):
        return jnp.moveaxis(
            x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:]), 0, 0)
    return jax.tree.map(split, batch)


def grads_and_loss(params, cfg: ModelConfig, batch, shape: ShapeConfig,
                   ctx: Optional[ShardCtx], kernel_fn=None):
    """Mean grads over the (possibly microbatched) global batch, in f32."""
    def lf(p, mb):
        loss, metrics = M.loss_fn(p, cfg, mb, remat=shape.remat,
                                  kernel_fn=kernel_fn, ctx=ctx)
        return loss, metrics

    if shape.num_microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
            params, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, loss, metrics

    n_mb = shape.num_microbatches
    mbs = _split_microbatches(batch, n_mb)

    def body(carry, mb):
        g_acc, l_acc = carry
        (loss, _), grads = jax.value_and_grad(lf, has_aux=True)(params, mb)
        g_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32) / n_mb, g_acc, grads)
        return (g_acc, l_acc + loss / n_mb), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (grads, loss), _ = jax.lax.scan(body, (g0, jnp.zeros((), jnp.float32)),
                                    mbs)
    return grads, loss, {"ce": loss, "aux": jnp.zeros((), jnp.float32)}


def make_train_step(cfg: ModelConfig, shape: ShapeConfig, opt: OptConfig,
                    ctx: Optional[ShardCtx] = None, kernel_fn=None,
                    compress_dcn: bool = False
                    ) -> Callable[..., Tuple[Any, Any, Dict[str, jax.Array]]]:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). When compress_dcn and the mesh has a 'pod' axis, the pod-axis
    gradient hop is int8-compressed with error feedback."""

    if not compress_dcn or ctx is None or "pod" not in ctx.mesh.axis_names:
        def train_step(params, opt_state, batch):
            grads, loss, metrics = grads_and_loss(params, cfg, batch, shape,
                                                  ctx, kernel_fn)
            grads, gnorm = clip_by_global_norm(grads, opt.grad_clip)
            params, opt_state, om = adamw_update(params, grads, opt_state, opt)
            return params, opt_state, {"loss": loss, "grad_norm": gnorm, **om}
        return train_step

    mesh = ctx.mesh
    inner_ctx = _PodLocalCtx(mesh=mesh, dp_axes=("data",),
                             fsdp_axis=ctx.fsdp_axis, tp_axis=ctx.tp_axis,
                             sequence_parallel=ctx.sequence_parallel)

    def train_step(params, opt_state, batch):
        def pod_body(params, opt_state, batch):
            # per-pod grads (auto-partitioned over data/model inside)
            grads, loss, metrics = grads_and_loss(
                params, cfg, batch, shape, inner_ctx, kernel_fn)
            # explicit compressed DCN hop with error feedback
            errs = opt_state["dcn_error"]
            flat_g, tdef = jax.tree.flatten(grads)
            flat_e = jax.tree.leaves(errs)
            new_g, new_e = [], []
            for g, e in zip(flat_g, flat_e):
                tgt = g + e
                q, scale = comp.quantize_int8(tgt)
                new_e.append(tgt - comp.dequantize_int8(q, scale))
                qs = jax.lax.all_gather(q, "pod")          # int8 on the wire
                ss = jax.lax.all_gather(scale, "pod")
                red = jnp.tensordot(ss, qs.astype(jnp.float32), axes=(0, 0))
                new_g.append(red / mesh.shape["pod"])
            grads = jax.tree.unflatten(tdef, new_g)
            grads, gnorm = clip_by_global_norm(grads, opt.grad_clip)
            params, new_state, om = adamw_update(params, grads, opt_state, opt)
            # adamw_update builds a fresh state dict: re-attach the error-
            # feedback residuals
            new_state["dcn_error"] = jax.tree.unflatten(tdef, new_e)
            loss = jax.lax.pmean(loss, "pod")
            return params, new_state, {"loss": loss, "grad_norm": gnorm, **om}

        pspec = P()            # params replicated w.r.t. pod (sharded inside)
        batch_spec = jax.tree.map(lambda _: P("pod"), batch)
        fn = shard_map(
            pod_body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: pspec, params),
                      jax.tree.map(lambda _: pspec, opt_state),
                      batch_spec),
            out_specs=(jax.tree.map(lambda _: pspec, params),
                       jax.tree.map(lambda _: pspec, opt_state),
                       {"loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False,
            axis_names={"pod"})      # manual over pod; data/model stay auto
        return fn(params, opt_state, batch)

    return train_step


def init_train_state(key, cfg: ModelConfig, opt: OptConfig,
                     compress_dcn: bool = False):
    params = M.init_model(key, cfg)
    opt_state = init_opt_state(params)
    if compress_dcn:
        opt_state["dcn_error"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return params, opt_state
