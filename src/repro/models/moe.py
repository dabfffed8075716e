"""Mixture-of-Experts FFN: top-k router + ragged_dot grouped matmul.

Parallelism: tensor-parallel experts — every device holds *all* experts with a
1/16 slice of the expert hidden dim ("model" axis). Token dispatch (top-k,
sort, ragged grouped matmul) is therefore local to each data shard; the only
collective is the same all-reduce a dense TP FFN needs. This sidesteps
all-to-all dispatch entirely and is implemented with shard_map so ragged_dot
never has to be GSPMD-partitioned.

Invariant-Dropout hooks:
  expert_mask  (E,)   -- 0 drops a whole expert (router logit -> -inf)
  neuron_mask  (E, f) -- 0 drops an expert-hidden unit
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import batch_axes, current_mesh
from repro.models.layers import GATED, cdtype, dense_init, init_ffn, apply_ffn, pdtype

from jax.sharding import PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def init_moe(key, cfg: ModelConfig):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_ff
    ks = jax.random.split(key, 6)
    p = {"router": dense_init(ks[0], d, d, E, dtype=jnp.float32),
         "w_in": dense_init(ks[1], d, E, d, f, dtype=pdtype(cfg)),
         "w_out": dense_init(ks[2], f, E, f, d, dtype=pdtype(cfg))}
    if cfg.ffn_kind in GATED:
        p["w_gate"] = dense_init(ks[3], d, E, d, f, dtype=pdtype(cfg))
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], cfg, d_ff=cfg.n_shared_experts * f)
    if cfg.dense_ff_residual:
        p["dense"] = init_ffn(ks[5], cfg, d_ff=cfg.d_ff)
    return p


CAPACITY_FACTOR = 1.25


def _route(p, x2d, cfg: ModelConfig, expert_mask):
    T, _ = x2d.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    if expert_mask is not None:
        logits = jnp.where(expert_mask[None, :] > 0, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    topv, topi = jax.lax.top_k(probs, k)                        # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    flat_e = topi.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k
    gs = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    w = jnp.take(topv.reshape(T * k), order)
    # load-balance auxiliary loss (Switch-style)
    frac = gs.astype(jnp.float32) / jnp.maximum(T * k, 1)
    aux = E * jnp.sum(frac * probs.mean(axis=0))
    return order, tok, gs, w, jnp.take(flat_e, order), aux


def _expert_act(p, h, g, cfg, dt):
    if g is not None:
        return jax.nn.silu(g) * h
    return jax.nn.gelu(h)


def _moe_tokens(p, x2d, cfg: ModelConfig, neuron_mask, expert_mask,
                stream_axis=None):
    """Local MoE over flat tokens x2d: (T, d).

    Default impl "capacity": tokens are scattered into per-expert buckets of
    size cap = ceil(T*k/E * CAPACITY_FACTOR) and processed with one dense
    (E, cap, d) x (E, d, f) einsum — the XLA-portable grouped matmul
    (overflow tokens drop, standard capacity semantics). impl "ragged" uses
    jax.lax.ragged_dot (efficient on TPU; XLA:CPU expands it densely).
    """
    dt = cdtype(cfg)
    T, d = x2d.shape
    E, k = cfg.n_experts, cfg.top_k
    order, tok, gs, w, row_e, aux = _route(p, x2d, cfg, expert_mask)
    xs = jnp.take(x2d, tok, axis=0)                             # (T*k, d)

    if cfg.moe_impl == "ragged":
        h = jax.lax.ragged_dot(xs, p["w_in"].astype(dt), gs)
        g = (jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), gs)
             if "w_gate" in p else None)
        h = _expert_act(p, h, g, cfg, dt)
        if neuron_mask is not None:
            h = h * jnp.take(neuron_mask, row_e, axis=0).astype(dt)
        out = jax.lax.ragged_dot(h, p["w_out"].astype(dt), gs)  # (T*k, d)
        y = jnp.zeros((T, d), dt).at[tok].add(out * w[:, None].astype(dt))
        return y, aux

    cap = max(int(np.ceil(T * k / E * cfg.moe_capacity_factor)), 1)
    offsets = jnp.cumsum(gs) - gs                               # (E,)
    rank = jnp.arange(T * k, dtype=jnp.int32) - jnp.take(offsets, row_e)
    keep = rank < cap
    buckets = jnp.zeros((E, cap, d), dt)
    buckets = buckets.at[row_e, jnp.where(keep, rank, cap - 1)].set(
        jnp.where(keep[:, None], xs, 0).astype(dt), mode="drop")

    def expert_matmul(bk, wi, wg, wo, nm):
        h = jnp.einsum("ecd,edf->ecf", bk, wi.astype(dt))
        g = (jnp.einsum("ecd,edf->ecf", bk, wg.astype(dt))
             if wg is not None else None)
        h = _expert_act(p, h, g, cfg, dt)
        if nm is not None:
            h = h * nm[:, None, :].astype(dt)
        return jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))

    if stream_axis is not None:
        # Weights arrive (n_shards, ec, d, f_loc) with the shard dim mapped to
        # the FSDP axes: each scan step broadcasts ONE shard's expert chunk
        # (psum of a masked copy) so the resident gathered working set is
        # E/n_shards experts instead of all E (Arctic: 1.7 GiB vs 27 GiB).
        ax_name, nsh = stream_axis
        ec_ = p["w_in"].shape[1]
        if isinstance(ax_name, tuple):
            didx = jnp.zeros((), jnp.int32)
            for a in ax_name:
                didx = didx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        else:
            didx = jax.lax.axis_index(ax_name)

        def sbody(_, s):
            sel = (didx == s)
            def bcast(t):
                return jax.lax.psum(jnp.where(sel, t, jnp.zeros_like(t)),
                                    ax_name)
            wi = bcast(p["w_in"])[0]
            wo = bcast(p["w_out"])[0]
            wg = bcast(p["w_gate"])[0] if "w_gate" in p else None
            nm = (bcast(neuron_mask)[0] if neuron_mask is not None else None)
            bk = jax.lax.dynamic_slice_in_dim(buckets, s * ec_, ec_, axis=0)
            return (), expert_matmul(bk, wi, wg, wo, nm)
        _, out_c = jax.lax.scan(jax.checkpoint(sbody), (),
                                jnp.arange(nsh, dtype=jnp.int32))
        out_b = out_c.reshape(E, cap, d)
        out = out_b[row_e, jnp.clip(rank, 0, cap - 1)]
        out = jnp.where(keep[:, None], out, 0)
        y = jnp.zeros((T, d), dt).at[tok].add(out * w[:, None].astype(dt))
        return y, aux

    ec = cfg.moe_expert_chunk
    if ec and E > ec and E % ec == 0:
        # scan over expert chunks: bounds the gathered-weight working set to
        # ec experts at a time (vital at Arctic scale: 128 experts x 7168 x
        # 4864 would otherwise materialize ~27 GiB per layer)
        nec = E // ec
        wg_r = (p["w_gate"].reshape(nec, ec, d, -1) if "w_gate" in p
                else None)
        nm_r = (neuron_mask.reshape(nec, ec, -1) if neuron_mask is not None
                else None)
        xs_scan = (buckets.reshape(nec, ec, cap, d),
                   p["w_in"].reshape(nec, ec, d, -1),
                   p["w_out"].reshape(nec, ec, -1, d))

        def ebody(_, args):
            bk, wi, wo = args[:3]
            wg = args[3] if wg_r is not None else None
            nm = args[-1] if nm_r is not None else None
            return (), expert_matmul(bk, wi, wg, wo, nm)
        extra = tuple(t for t in (wg_r, nm_r) if t is not None)
        _, out_c = jax.lax.scan(jax.checkpoint(ebody), (), xs_scan + extra)
        out_b = out_c.reshape(E, cap, d)
    else:
        out_b = expert_matmul(buckets, p["w_in"],
                              p.get("w_gate"), p["w_out"], neuron_mask)
    out = out_b[row_e, jnp.clip(rank, 0, cap - 1)]              # (T*k, d)
    out = jnp.where(keep[:, None], out, 0)
    y = jnp.zeros((T, d), dt).at[tok].add(out * w[:, None].astype(dt))
    return y, aux


def _moe_local(p, x, neuron_mask, expert_mask, cfg: ModelConfig,
               axis_names=(), stream_axis=None):
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    T = B * S
    ck = cfg.moe_token_chunk
    if T <= ck:
        y, aux = _moe_tokens(p, x2d, cfg, neuron_mask, expert_mask,
                             stream_axis)
    else:
        while T % ck != 0:
            ck //= 2
        nck = T // ck

        def body(_, xi):
            yi, auxi = _moe_tokens(p, xi, cfg, neuron_mask, expert_mask,
                                   stream_axis)
            return (), (yi, auxi)
        _, (y, auxs) = jax.lax.scan(jax.checkpoint(body), (),
                                    x2d.reshape(nck, ck, d))
        y = y.reshape(T, d)
        aux = auxs.mean()
    y = y.reshape(B, S, d)
    if axis_names:
        if "model" in axis_names:
            y = jax.lax.psum(y, "model")        # partial sums over f shards
        aux = jax.lax.pmean(aux, axis_names)
    if "shared" in p:
        y = y + apply_ffn(p["shared"], x, cfg)
    if "dense" in p:
        y = y + apply_ffn(p["dense"], x, cfg)
    return y, aux


def apply_moe(p, x, cfg: ModelConfig, neuron_mask=None, expert_mask=None):
    """x: (B,S,d). Returns (y, aux_loss)."""
    mesh = current_mesh()
    if mesh is None:
        return _moe_local(p, x, neuron_mask, expert_mask, cfg)

    baxes = batch_axes(mesh)
    names = tuple(mesh.axis_names)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    if x.shape[0] % nb != 0 or x.shape[0] < nb:
        bspec = None    # tiny batch (e.g. long-context decode): replicate
    else:
        bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    xspec = P(bspec, None, None)
    pspecs = {"router": P(None, None),
              "w_in": P(None, None, "model"),
              "w_out": P(None, "model", None)}
    if "w_gate" in p:
        pspecs["w_gate"] = P(None, None, "model")
    for extra in ("shared", "dense"):
        if extra in p:
            pspecs[extra] = {k: (P(None, "model") if k in ("w_in", "w_gate", "b_in", "b_gate")
                                 else P("model", None) if k == "w_out"
                                 else P(None))
                             for k in p[extra]}
            for k in p[extra]:
                if k in ("b_in", "b_gate"):
                    pspecs[extra][k] = P("model")
                elif k == "b_out":
                    pspecs[extra][k] = P(None)
    # shard the grouped-matmul core only; shared/dense FFNs run under GSPMD
    core = {k: p[k] for k in ("router", "w_in", "w_out", "w_gate") if k in p}
    core_specs = {k: pspecs[k] for k in core}
    nm_spec = P(None, "model") if neuron_mask is not None else None
    em_spec = P(None) if expert_mask is not None else None

    E = cfg.n_experts
    fsdp_axes = baxes
    dsz = 1
    for a in fsdp_axes:
        dsz *= mesh.shape[a]
    stream_axis = None
    if (cfg.moe_weight_stream and fsdp_axes
            and E % dsz == 0 and dsz > 1):
        sax = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        stream_axis = (sax, dsz)
        ec = E // dsz
        d = cfg.d_model
        core = dict(core)
        core["w_in"] = core["w_in"].reshape(dsz, ec, d, -1)
        core["w_out"] = core["w_out"].reshape(dsz, ec, -1, d)
        core_specs = dict(core_specs)
        core_specs["w_in"] = P(sax, None, None, "model")
        core_specs["w_out"] = P(sax, None, "model", None)
        if "w_gate" in core:
            core["w_gate"] = core["w_gate"].reshape(dsz, ec, d, -1)
            core_specs["w_gate"] = P(sax, None, None, "model")
        if neuron_mask is not None:
            neuron_mask = neuron_mask.reshape(dsz, ec, -1)
            nm_spec = P(sax, None, "model")

    def fn(cp, xl, nm, em):
        return _moe_local(cp, xl, nm, em, cfg, axis_names=names,
                          stream_axis=stream_axis)

    y, aux = shard_map(
        fn, mesh,
        in_specs=(core_specs, xspec, nm_spec, em_spec),
        out_specs=(xspec, P()),
    )(core, x, neuron_mask, expert_mask)

    if "shared" in p:
        y = y + apply_ffn(p["shared"], x, cfg)
    if "dense" in p:
        y = y + apply_ffn(p["dense"], x, cfg)
    return y, aux
