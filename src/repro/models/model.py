"""Unified model API over all assigned architectures.

  init_params(cfg, key)                  -> params pytree
  forward_seq(params, cfg, batch, ...)   -> (logits, caches, aux)
  loss_fn(params, cfg, batch, ...)       -> (loss, metrics)
  decode_step(params, cfg, caches, ...)  -> (logits, new_caches)
  cache_specs(cfg, batch, seq_len, ...)  -> pytree of ShapeDtypeStruct
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import shard
from repro.models import encdec, transformer
from repro.models.layers import (cdtype, embed_tokens, init_embed, init_norm,
                                 apply_norm, lm_logits, softmax_xent)

ENC_MEM_LEN = 4096      # encoder memory length used by decode-shape caches


def init_params(cfg: ModelConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"tok": init_embed(k1, cfg), "final_norm": init_norm(cfg)}
    if cfg.is_encdec:
        p["stack"] = encdec.init_encdec_stack(k2, cfg)
        p["enc_norm"] = init_norm(cfg)
    else:
        seg_params, _ = transformer.init_stack(k2, cfg)
        p["stack"] = {f"seg{i}": sp for i, sp in enumerate(seg_params)}
    return p


def _seg_list(params, cfg):
    segs = transformer.build_segments(cfg)
    return [params["stack"][f"seg{i}"] for i in range(len(segs))], segs


def forward_seq(params, cfg: ModelConfig, batch, masks=None,
                window_override=None, unroll=False, want_cache=False,
                cache_len=None):
    """batch: {'tokens': (B,S) i32, optional 'frames': (B,M,d)}.
    Returns (logits, caches, aux)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_tokens(params["tok"], tokens, cfg)

    if cfg.is_encdec:
        mem = encdec.run_encoder(params["stack"], batch["frames"], cfg,
                                 masks=masks["enc"] if masks else None,
                                 unroll=unroll)
        mem = apply_norm(params["enc_norm"], mem, cfg)
        x, caches = encdec.run_decoder_seq(
            params["stack"], x, mem, cfg, positions,
            masks=masks["dec"] if masks else None,
            window_override=window_override, unroll=unroll,
            want_cache=want_cache, cache_len=cache_len)
        aux = jnp.zeros((), jnp.float32)
        caches = [caches]
    else:
        seg_params, segs = _seg_list(params, cfg)
        x, caches, aux = transformer.run_stack_seq(
            seg_params, segs, x, cfg, positions, masks=masks,
            window_override=window_override, unroll=unroll,
            want_cache=want_cache, cache_len=cache_len)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["tok"], x, cfg)
    return logits, (caches if want_cache else None), aux


def loss_fn(params, cfg: ModelConfig, batch, masks=None,
            window_override=None, unroll=False):
    logits, _, aux = forward_seq(params, cfg, batch, masks=masks,
                                 window_override=window_override,
                                 unroll=unroll)
    mask = batch.get("loss_mask")
    xent = softmax_xent(logits, batch["targets"], mask)
    loss = xent + cfg.router_aux_coef * aux
    return loss, {"xent": xent, "aux": aux}


def decode_step(params, cfg: ModelConfig, caches, token, pos, masks=None,
                window_override=None, mla_absorb=False):
    """token: (B,1) i32; pos: (B,) i32. Returns (logits, new_caches)."""
    x = embed_tokens(params["tok"], token, cfg)
    if cfg.is_encdec:
        x, nc = encdec.run_decoder_decode(
            params["stack"], caches[0], x, cfg, pos,
            masks=masks["dec"] if masks else None,
            window_override=window_override)
        new_caches = [nc]
    else:
        seg_params, segs = _seg_list(params, cfg)
        x, new_caches = transformer.run_stack_decode(
            seg_params, segs, caches, x, cfg, pos, masks=masks,
            window_override=window_override, mla_absorb=mla_absorb)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["tok"], x, cfg)
    return logits, new_caches


def cache_specs(cfg: ModelConfig, batch, seq_len, window_override=None):
    if cfg.is_encdec:
        return [encdec.dec_cache_specs(cfg, batch, seq_len, ENC_MEM_LEN,
                                       window_override)]
    return transformer.stack_cache_specs(cfg, batch, seq_len,
                                         window_override)


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
