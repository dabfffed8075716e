"""Step builders: train_step / prefill_step / serve_step.

These are the functions the drivers execute. They take the mesh through
launch.sharding.mesh_context; the model's internal sharding constraints
read it from there.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch import sharding as shlib
from repro.models import model as model_lib
from repro.optim import make_optimizer


# ---------------------------------------------------------------------------
# step functions

def make_train_step(cfg: ModelConfig, unroll: bool = False,
                    with_masks: bool = False, use_kernels: bool = False):
    """Build the (jit-able) train step.

    use_kernels routes the masked FFN matmuls through the differentiable
    Pallas kernels (kernels/masked_ffn.py custom_vjp — forward and backward
    skip dropped 128-blocks, DESIGN.md §10) by tracing the loss under
    sharding.train_kernels_context. Only meaningful with with_masks=True."""
    opt = make_optimizer(cfg.optimizer)
    accum = max(cfg.grad_accum, 1)

    def grads_of(params, batch, masks):
        def lf(p):
            return model_lib.loss_fn(p, cfg, batch, masks=masks,
                                     unroll=unroll)
        with shlib.train_kernels_context(ffn=use_kernels):
            return jax.value_and_grad(lf, has_aux=True)(params)

    def step(params, opt_state, batch, masks=None):
        if accum > 1:
            mb = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
                batch)

            def micro(carry, b):
                acc, loss_acc = carry
                (loss, metrics), g = grads_of(params, b, masks)
                acc = jax.tree.map(lambda a, gg: a + gg.astype(a.dtype),
                                   acc, g)
                return (acc, loss_acc + loss), metrics
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, p.dtype), params)
            (gsum, loss_sum), metrics = jax.lax.scan(
                micro, (zero, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = loss_sum / accum
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = grads_of(params, batch, masks)
        params2, opt_state2 = opt.update(grads, opt_state, params,
                                         cfg.learning_rate)
        metrics = dict(metrics, loss=loss)
        return params2, opt_state2, metrics

    if with_masks:
        return step
    return lambda params, opt_state, batch: step(params, opt_state, batch)


def make_prefill_step(cfg: ModelConfig, unroll: bool = False,
                      window_override: Optional[int] = None,
                      cache_len: Optional[int] = None):
    def step(params, batch):
        logits, caches, _ = model_lib.forward_seq(
            params, cfg, batch, window_override=window_override,
            unroll=unroll, want_cache=True, cache_len=cache_len)
        # return only the last-position logits (next-token) + cache
        return logits[:, -1], caches
    return step


def make_serve_step(cfg: ModelConfig, window_override: Optional[int] = None,
                    mla_absorb: bool = False):
    def step(params, caches, token, pos):
        logits, new_caches = model_lib.decode_step(
            params, cfg, caches, token, pos,
            window_override=window_override, mla_absorb=mla_absorb)
        return logits[:, -1], new_caches
    return step

