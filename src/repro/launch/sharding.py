"""Mesh context + sharding rules.

A tiny explicit context (no jax internals) carries the active mesh. Model code
calls ``shard(x, "B", None, "M")`` with symbolic axes:

  "B" -> the batch axes ("pod","data") or ("data",)
  "M" -> the model/tensor axis
  None -> replicated dim

Outside a mesh context (CPU smoke tests) ``shard`` is the identity, so the
same model code runs unsharded.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    prev = current_mesh()
    _STATE.mesh = mesh
    try:
        yield mesh
    finally:
        _STATE.mesh = prev


def decode_cache_mode() -> str:
    """'auto' (let GSPMD propagate) or 'seq' (pin the KV cache sequence axis
    to the model axis inside decode attention — cross-device flash-decode)."""
    return getattr(_STATE, "decode_cache", "auto")


def uniform_pos() -> bool:
    """True => all sequences decode at the same position (synchronized
    batch): the cache update is a single-slot dynamic-update-slice instead
    of a one-hot full-cache rewrite."""
    return getattr(_STATE, "uniform_pos", False)


@contextlib.contextmanager
def uniform_pos_context(on: bool):
    prev = uniform_pos()
    _STATE.uniform_pos = on
    try:
        yield
    finally:
        _STATE.uniform_pos = prev


@contextlib.contextmanager
def decode_cache_context(mode: str):
    prev = decode_cache_mode()
    _STATE.decode_cache = mode
    try:
        yield
    finally:
        _STATE.decode_cache = prev


def serve_kernel_flags() -> dict:
    """Which Pallas serving kernels the decode step should trace in:
    {'ffn': bool, 'attn': bool}. Defaults to all-off — the pure-jnp path.
    Whether a kernel runs compiled or interpreted is decided by the
    platform (kernels/ops.py), not here."""
    return getattr(_STATE, "serve_kernels", {"ffn": False, "attn": False})


@contextlib.contextmanager
def serve_kernels_context(ffn: bool = False, attn: bool = False):
    """Opt the serving decode step into the Pallas kernels
    (kernels/masked_ffn.py masked_ffn_batch, kernels/decode_gqa.py).
    Same thread-local idiom as decode_cache_context/uniform_pos_context:
    model code reads the flags at trace time, so the choice is baked into
    whichever program is being compiled under this context."""
    prev = serve_kernel_flags()
    _STATE.serve_kernels = {"ffn": ffn, "attn": attn}
    try:
        yield
    finally:
        _STATE.serve_kernels = prev


def train_kernel_flags() -> dict:
    """Which Pallas kernels the TRAIN step should trace in:
    {'ffn': bool}. Defaults to off (pure-jnp dense masking). Unlike
    serve_kernel_flags this routes the *differentiable*
    custom_vjp kernels (DESIGN.md §10) — both forward and backward skip
    dropped 128-blocks — so it only applies where a per-layer neuron mask
    is being trained through (launch/steps.py make_train_step
    with_masks=True, use_kernels=True)."""
    return getattr(_STATE, "train_kernels", {"ffn": False})


@contextlib.contextmanager
def train_kernels_context(ffn: bool = False):
    """Opt the train step into the differentiable masked-FFN kernel
    (kernels/masked_ffn.py, custom_vjp). Same trace-time thread-local idiom
    as serve_kernels_context."""
    prev = train_kernel_flags()
    _STATE.train_kernels = {"ffn": ffn}
    try:
        yield
    finally:
        _STATE.train_kernels = prev


def donate_args(*indices: int):
    """Buffer-donation indices for jit'd step functions, gated on backend.

    CPU (and interpret-mode) executables don't support donation — XLA just
    warns and copies — so return () there and the real indices elsewhere.
    Call sites stay declarative: ``donate_argnums=donate_args(0, 1)`` names
    exactly which args are dead after the call (an empty call,
    ``donate_args()``, documents that nothing is donatable).
    """
    if jax.default_backend() == "cpu":
        return ()
    return indices


def batch_axes(mesh: Mesh):
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _resolve(mesh: Mesh, sym):
    if sym is None:
        return None
    if sym == "B":
        ax = batch_axes(mesh)
        return ax if len(ax) > 1 else (ax[0] if ax else None)
    if sym == "M":
        return "model" if "model" in mesh.axis_names else None
    return sym


def pspec(*syms) -> P:
    mesh = current_mesh()
    if mesh is None:
        return P()
    return P(*[_resolve(mesh, s) for s in syms])


def _axes_size(mesh, ax) -> int:
    axs = ax if isinstance(ax, tuple) else (ax,)
    n = 1
    for a in axs:
        n *= mesh.shape[a]
    return n


def guarded_pspec(mesh, shape, syms, strict: bool = False) -> P:
    """pspec with too-small dims demoted to replicated. With strict=False
    (internal with_sharding_constraint) uneven-but-larger dims stay sharded
    (GSPMD pads internally, e.g. 56 heads over 16); strict=True (jit
    in_shardings, where XLA requires divisibility) demotes uneven dims."""
    out = []
    for dim, sym in zip(shape, syms):
        ax = _resolve(mesh, sym)
        if ax is None:
            out.append(None)
            continue
        n = _axes_size(mesh, ax)
        ok = (dim % n == 0 and dim >= n) if strict else dim >= n
        out.append(ax if ok else None)
    return P(*out)


def shard(x, *syms):
    """with_sharding_constraint under the active mesh; identity without one.
    Dims that don't divide their mesh axes are left replicated."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, guarded_pspec(mesh, x.shape, syms)))


def named(spec_syms) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, pspec(*spec_syms))


# ---------------------------------------------------------------------------
# Parameter sharding rules.
#
# Params are nested dicts; keys are joined with "/" and matched against the
# regex table below (first match wins). Leading segment dims (layer stacks)
# are replicated; the rule names the *trailing* dims of the logical weight.

_RULES = [
    # embeddings / lm head: shard vocab
    (r"(^|/)embed$",               ("M", None)),
    (r"(^|/)lm_head$",             (None, "M")),
    # attention projections: shard heads (q) / replicate small kv
    (r"attn/wq$",                  (None, "M", None)),
    (r"attn/wk$",                  (None, "kv", None)),
    (r"attn/wv$",                  (None, "kv", None)),
    (r"attn/wo$",                  ("M", None, None)),
    (r"attn/(bq)$",                ("M", None)),
    (r"attn/(bk|bv)$",             ("kv", None)),
    (r"attn/bo$",                  (None,)),
    # MLA
    (r"mla/w_dq$",                 (None, None)),
    (r"mla/w_uq$",                 (None, "M", None)),
    (r"mla/wq$",                   (None, "M", None)),
    (r"mla/w_dkv$",                (None, None)),
    (r"mla/w_uk$",                 (None, "M", None)),
    (r"mla/w_uv$",                 (None, "M", None)),
    (r"mla/wo$",                   ("M", None, None)),
    # dense FFN: shard hidden
    (r"ffn/w_in$",                 (None, "M")),
    (r"ffn/w_gate$",               (None, "M")),
    (r"ffn/w_out$",                ("M", None)),
    (r"ffn/b_in$",                 ("M",)),
    (r"ffn/b_gate$",               ("M",)),
    (r"ffn/b_out$",                (None,)),
    # MoE: tensor-parallel experts (expert dim replicated, hidden sharded)
    (r"moe/router$",               (None, None)),
    (r"moe/w_in$",                 (None, None, "M")),
    (r"moe/w_gate$",               (None, None, "M")),
    (r"moe/w_out$",                (None, "M", None)),
    # RWKV-6
    (r"rwkv/(w_r|w_k|w_v|w_g)$",   (None, "M")),
    (r"rwkv/w_o$",                 ("M", None)),
    (r"rwkv/(w_decay|w_u)$",       ("M",)),
    (r"rwkv/lora_.*_a$",           (None, None)),
    (r"rwkv/lora_.*_b$",           (None, "M")),
    (r"rwkv/lora_w_b$",            (None, "M")),
    (r"rwkv/mix_.*$",              (None,)),
    (r"rwkv/ln_.*$",               ("M",)),
    (r"cmix/w_in$",                (None, "M")),
    (r"cmix/w_out$",               ("M", None)),
    (r"cmix/mix_.*$",              (None,)),
    # RG-LRU
    (r"rglru/w_x$",                (None, "M")),
    (r"rglru/w_gate$",             (None, "M")),
    (r"rglru/w_out$",              ("M", None)),
    (r"rglru/conv_.*$",            (None, "M")),
    (r"rglru/(a_param|w_a|w_i|b_a|b_i)$", ("M",) ),
    # norms / scalars: replicate
    (r".*",                        None),
]


def _spec_for(path: str, shape, kv_shardable: bool) -> P:
    ndim = len(shape)
    for pat, tail in _RULES:
        if re.search(pat, path):
            if tail is None:
                return P()
            tail = tuple("M" if (t == "kv" and kv_shardable) else
                         (None if t == "kv" else t) for t in tail)
            lead = (None,) * (ndim - len(tail))
            mesh = current_mesh()
            return guarded_pspec(mesh, shape, lead + tail, strict=True)
    return P()


def param_pspecs(params, kv_shardable: bool = True, fsdp: bool = True):
    """PartitionSpec pytree matching a parameter pytree.

    With fsdp=True, the first still-replicated (and divisible) dim of every
    >=2-D weight is additionally sharded over "data" (ZeRO-3 style); the
    leading layer-stack dim of scanned parameters is skipped so per-layer
    slicing stays local.
    """
    mesh = current_mesh()

    def improve(path, shape, spec):
        if mesh is None or "data" not in mesh.axis_names or len(shape) < 2:
            return spec
        fsdp_axes = batch_axes(mesh)         # ("pod","data") or ("data",)
        d = 1
        for a in fsdp_axes:
            d *= mesh.shape[a]
        entries = list(spec) + [None] * (len(shape) - len(spec))
        used = [e for ent in entries if ent
                for e in (ent if isinstance(ent, tuple) else (ent,))]
        if any(a in used for a in fsdp_axes):
            return spec
        start = 1 if ("stack" in path or "seg" in path) else 0
        ax = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        for i in range(start, len(shape)):
            if entries[i] is None and shape[i] % d == 0 and shape[i] >= d:
                entries[i] = ax
                break
        return P(*entries)

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in tree.items()}
        spec = _spec_for(prefix, tree.shape, kv_shardable)
        if fsdp:
            spec = improve(prefix, tree.shape, spec)
        return spec
    return walk(params, "")
