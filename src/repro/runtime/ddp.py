"""shard_map data-parallel train step with int8 error-feedback gradient
compression on the DP all-reduce.

Two-phase compressed all-reduce (1-bit-Adam lineage, adapted to XLA
collectives):
  1. each replica quantizes (grad + error feedback) per-tensor to int8,
  2. all_to_all exchanges int8 *shards* (each device collects every
     replica's slice of its own shard),
  3. local dequant-sum over replicas, requantize,
  4. all_gather of the reduced int8 shards + scales.

Wire traffic ≈ 2·n int8 bytes vs ≈ 8·n bytes for a ring f32 all-reduce:
a 4× DP-bandwidth saving, which is what crosses the slow "pod" axis in
the multi-pod mesh. Error feedback accumulates the quantization residual
into the next step so the compression is unbiased over time.

This is the explicit-collective variant of the train step (the pjit path
in runtime.step lets XLA choose collectives); it is exercised at small
scale by tests/examples and is the reference implementation of the
distributed-optimization trick for the 1000+-node posture.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import lm
from repro.models.common import ArchConfig
from repro.optim.adamw import AdamWConfig, adamw_update

Array = jax.Array
AXIS = "data"


def _compressed_allreduce_mean(g: Array, err: Array, n_dev: int):
    """One tensor: returns (mean grad f32, new error buffer)."""
    g32 = g.astype(jnp.float32) + err
    # --- quantize local
    scale = jnp.maximum(jnp.max(jnp.abs(g32)) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    new_err = g32 - q.astype(jnp.float32) * scale

    flat = q.reshape(-1)
    pad = (-flat.shape[0]) % n_dev
    flat = jnp.pad(flat, (0, pad))
    shards = flat.reshape(n_dev, -1)                       # (R, n/R) int8

    # --- phase 1: exchange shards + scales
    recv = jax.lax.all_to_all(shards, AXIS, split_axis=0, concat_axis=0,
                              tiled=False)                 # (R, n/R)
    scales = jax.lax.all_gather(scale, AXIS)               # (R,)
    local_sum = jnp.sum(recv.astype(jnp.float32) *
                        scales[:, None], axis=0)           # (n/R,) f32

    # --- phase 2: requantize the reduced shard, all_gather
    s2 = jnp.maximum(jnp.max(jnp.abs(local_sum)) / 127.0, 1e-12)
    q2 = jnp.clip(jnp.round(local_sum / s2), -127, 127).astype(jnp.int8)
    all_q = jax.lax.all_gather(q2, AXIS)                   # (R, n/R) int8
    all_s = jax.lax.all_gather(s2, AXIS)                   # (R,)
    full = (all_q.astype(jnp.float32) * all_s[:, None]).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(g.shape) / n_dev, new_err


def build_compressed_ddp_step(cfg: ArchConfig, acfg: AdamWConfig,
                              mesh: Mesh, compress: bool = True):
    """(params, opt_state, err_bufs, batch) -> (params', opt', err', metrics).
    Params replicated; batch sharded over "data"."""
    n_dev = mesh.shape[AXIS]

    def local_step(params, opt_state, err, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(cfg, p, batch)[0], has_aux=False)(params), None
        return loss, grads

    def step(params, opt_state, err, batch):
        def loss_fn(p):
            l, _ = lm.loss_fn(cfg, p, batch)
            return l
        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.pmean(loss, AXIS)
        if compress:
            out = jax.tree.map(
                lambda g, e: _compressed_allreduce_mean(g, e, n_dev),
                grads, err)
            grads = jax.tree.map(lambda t: t[0], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
            err = jax.tree.map(lambda t: t[1], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        else:
            grads = jax.lax.pmean(grads, AXIS)
        new_params, new_opt, om = adamw_update(grads, opt_state, params,
                                               acfg)
        return new_params, new_opt, err, {"loss": loss, **om}

    rep = P()
    shd = P(AXIS)
    batch_spec = {"inputs": shd, "labels": shd}
    return jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,
    ))


def init_error_buffers(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
