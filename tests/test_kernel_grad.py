"""Gradient equivalence for the differentiable masked kernels (DESIGN.md §10).

`jax.grad` through the custom_vjp Pallas kernels (interpreter mode) must
match `jax.grad` of the dense `mask * params` reference to fp32 tolerance —
for the FFN (gated + ungated), batched per-row masks, and the attention-head
variant, at dropout rates {0, 0.5, all-but-one-block dropped}. Also covers
the structural zero guarantee (dropped-block dW is exactly 0, not just
small), the mask-shape validation errors, and the fleet-level contract:
a `FleetEngine(use_kernels=True)` cohort reproduces the dense cohort's
deltas, sim-times, and aggregate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (BLOCK_NEURONS, masked_attention, masked_ffn,
                               masked_ffn_batch, masked_head_merge,
                               masked_head_proj)
from repro.kernels.ref import (masked_attention_ref, masked_ffn_batch_ref,
                               masked_ffn_ref, masked_head_merge_ref,
                               masked_head_proj_ref)

ATOL = 5e-3      # fp32 interpret-mode kernels vs fp32 dense autodiff

M, D, F = 12, 32, 3 * BLOCK_NEURONS      # 3 maskable blocks
H, HD = 4, 16


def _rng(seed=0):
    return np.random.RandomState(seed)


def _ffn_weights(seed=0):
    r = _rng(seed)
    x = jnp.asarray(r.randn(M, D) * 0.5, jnp.float32)
    win = jnp.asarray(r.randn(D, F) * 0.1, jnp.float32)
    wout = jnp.asarray(r.randn(F, D) * 0.1, jnp.float32)
    wgate = jnp.asarray(r.randn(D, F) * 0.1, jnp.float32)
    return x, win, wout, wgate


# the issue's rate sweep: 0 (all kept), 0.5, and 1-block-kept
BLOCK_MASKS = {"rate0": np.array([1, 1, 1]),
               "rate05": np.array([1, 0, 1]),
               "one_block": np.array([0, 1, 0])}


def _grad_pair(f_kernel, f_ref, args, argnums):
    gk = jax.grad(lambda *a: (f_kernel(*a) ** 2).sum(), argnums=argnums)(*args)
    gr = jax.grad(lambda *a: (f_ref(*a) ** 2).sum(), argnums=argnums)(*args)
    return gk, gr


def _assert_close(gk, gr):
    for a, b in zip(gk, gr):
        err = float(np.abs(np.asarray(a) - np.asarray(b)).max())
        assert err < ATOL, err


@pytest.mark.parametrize("maskname", list(BLOCK_MASKS))
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("act", ["gelu", "silu"])
def test_ffn_grad_matches_dense(maskname, gated, act):
    x, win, wout, wgate = _ffn_weights()
    bmask = jnp.asarray(BLOCK_MASKS[maskname], jnp.int32)
    wg = wgate if gated else None
    argnums = (0, 1, 2) + ((3,) if gated else ())
    gk, gr = _grad_pair(
        lambda *a: masked_ffn(a[0], a[1], a[2], bmask,
                              a[3] if gated else None, act=act),
        lambda *a: masked_ffn_ref(a[0], a[1], a[2], bmask,
                                  a[3] if gated else None, act=act),
        (x, win, wout, wg) if gated else (x, win, wout), argnums)
    _assert_close(gk, gr)
    # forward parity too
    yk = masked_ffn(x, win, wout, bmask, wg, act=act)
    yr = masked_ffn_ref(x, win, wout, bmask, wg, act=act)
    assert float(np.abs(np.asarray(yk) - np.asarray(yr)).max()) < ATOL


def test_ffn_dropped_block_dw_exactly_zero():
    """The §10 structural guarantee: dW of a dropped block is 0.0 — the
    accumulator was never touched — not merely small."""
    x, win, wout, wgate = _ffn_weights()
    bmask = jnp.asarray([0, 1, 0], jnp.int32)
    g = jax.grad(lambda wi, wo, wg: (
        masked_ffn(x, wi, wo, bmask, wg, act="silu") ** 2).sum(),
        argnums=(0, 1, 2))(win, wout, wgate)
    dwin = np.asarray(g[0]).reshape(D, 3, BLOCK_NEURONS)
    dwout = np.asarray(g[1]).reshape(3, BLOCK_NEURONS, D)
    dwgate = np.asarray(g[2]).reshape(D, 3, BLOCK_NEURONS)
    for j in (0, 2):
        assert np.all(dwin[:, j] == 0.0)
        assert np.all(dwout[j] == 0.0)
        assert np.all(dwgate[:, j] == 0.0)
    assert np.any(dwin[:, 1] != 0.0)


@pytest.mark.parametrize("gated", [False, True])
def test_ffn_batch_per_row_grad_matches_dense(gated):
    x, win, wout, wgate = _ffn_weights(1)
    r = _rng(2)
    rmask = (r.rand(M, F) > 0.4).astype(np.float32)
    rmask[3] = 0.0                          # one fully-dropped row
    rmask[:, BLOCK_NEURONS:2 * BLOCK_NEURONS] = 0.0   # one dead tile column
    rm = jnp.asarray(rmask)
    wg = wgate if gated else None
    argnums = (0, 1, 2) + ((3,) if gated else ())
    gk, gr = _grad_pair(
        lambda *a: masked_ffn_batch(a[0], a[1], a[2], rm,
                                    a[3] if gated else None, act="gelu"),
        lambda *a: masked_ffn_batch_ref(a[0], a[1], a[2], rm,
                                        a[3] if gated else None, act="gelu"),
        (x, win, wout, wg) if gated else (x, win, wout), argnums)
    _assert_close(gk, gr)
    # neurons masked in EVERY row never contribute to dW
    dwin = np.asarray(gk[1])
    assert np.all(dwin[:, BLOCK_NEURONS:2 * BLOCK_NEURONS] == 0.0)


HEAD_MASKS = {"rate0": np.ones(H), "rate05": np.array([1, 0, 1, 0]),
              "one_head": np.array([0, 0, 1, 0])}


@pytest.mark.parametrize("maskname", list(HEAD_MASKS))
def test_head_proj_and_merge_grad_matches_dense(maskname):
    r = _rng(3)
    hmask = jnp.asarray(HEAD_MASKS[maskname], jnp.int32)
    x = jnp.asarray(r.randn(M, D) * 0.5, jnp.float32)
    w = jnp.asarray(r.randn(D, H * HD) * 0.2, jnp.float32)
    wo = jnp.asarray(r.randn(H * HD, D) * 0.2, jnp.float32)
    a_in = jnp.asarray(r.randn(M, H * HD) * 0.3, jnp.float32)
    gk, gr = _grad_pair(
        lambda xx, ww: masked_head_proj(xx, ww, hmask),
        lambda xx, ww: masked_head_proj_ref(xx, ww, hmask),
        (x, w), (0, 1))
    _assert_close(gk, gr)
    # dropped-head dW slab exactly zero
    dw = np.asarray(gk[1]).reshape(D, H, HD)
    for j, kept in enumerate(HEAD_MASKS[maskname]):
        if kept == 0:
            assert np.all(dw[:, j] == 0.0)
    gk, gr = _grad_pair(
        lambda aa, ww: masked_head_merge(aa, ww, hmask),
        lambda aa, ww: masked_head_merge_ref(aa, ww, hmask),
        (a_in, wo), (0, 1))
    _assert_close(gk, gr)
    dw = np.asarray(gk[1]).reshape(H, HD, D)
    for j, kept in enumerate(HEAD_MASKS[maskname]):
        if kept == 0:
            assert np.all(dw[j] == 0.0)


@pytest.mark.parametrize("maskname", list(HEAD_MASKS))
def test_masked_attention_grad_matches_dense(maskname):
    r = _rng(4)
    hmask = jnp.asarray(HEAD_MASKS[maskname], jnp.int32)
    B, S = 2, 6
    x = jnp.asarray(r.randn(B, S, D) * 0.5, jnp.float32)
    wq, wk, wv = (jnp.asarray(r.randn(D, H * HD) * 0.2, jnp.float32)
                  for _ in range(3))
    wo = jnp.asarray(r.randn(H * HD, D) * 0.2, jnp.float32)
    gk, gr = _grad_pair(
        lambda *a: masked_attention(*a, hmask, n_heads=H),
        lambda *a: masked_attention_ref(*a, hmask, H),
        (x, wq, wk, wv, wo), (0, 1, 2, 3, 4))
    _assert_close(gk, gr)


def test_shape_validation_errors():
    """The silent-dense footgun fix: unaligned / mis-shaped masks raise
    clear ValueErrors instead of mis-tiling."""
    r = _rng(5)
    x = jnp.asarray(r.randn(4, D), jnp.float32)
    win = jnp.asarray(r.randn(D, F), jnp.float32)
    wout = jnp.asarray(r.randn(F, D), jnp.float32)
    with pytest.raises(ValueError, match="multiple of"):
        masked_ffn(x, win[:, :100], wout[:100], jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="block_mask must be"):
        masked_ffn(x, win, wout, jnp.ones((5,), jnp.int32))
    with pytest.raises(ValueError, match="row_mask must be"):
        masked_ffn_batch(x, win, wout, jnp.ones((4, F + 1), jnp.float32))
    with pytest.raises(ValueError, match="w_out must be"):
        masked_ffn(x, win, wout[:, :D - 1], jnp.ones((3,), jnp.int32))
    w = jnp.asarray(r.randn(D, H * HD), jnp.float32)
    with pytest.raises(ValueError, match="divide evenly"):
        masked_head_proj(x, w, jnp.ones((3,), jnp.int32))
    with pytest.raises(ValueError, match="head_mask must be"):
        masked_attention(x[None], w, w, w, w.T, jnp.ones((3,), jnp.int32),
                         n_heads=H)


# ---------------------------------------------------------------------------
# fleet-level contract


def _fleet_pair(model_cls, keep_maps, seed=0):
    from repro.fl.client import FleetClient
    from repro.fl.fleet import FleetEngine

    r = _rng(seed)
    C, n = 4, 40
    x = r.randn(C * n, 28, 28, 1).astype(np.float32)
    y = r.randint(0, 62, C * n).astype(np.int32)

    def mk():
        return [FleetClient(i, model_cls, x[i * n:(i + 1) * n],
                            y[i * n:(i + 1) * n], speed=10.0, batch_size=10,
                            lr=0.05, local_epochs=1, seed=0)
                for i in range(C)]
    params = model_cls.init(jax.random.PRNGKey(0))
    dense = FleetEngine(model_cls, mk(), model_cls.UNIT_SPECS)
    kern = FleetEngine(model_cls, mk(), model_cls.UNIT_SPECS,
                       use_kernels=True)
    rates = {cid: 0.5 for cid in keep_maps}
    rd = dense.run_cohort(params, keep_maps, rates=rates)
    rk = kern.run_cohort(params, keep_maps, rates=rates)
    return params, rd, rk


@pytest.mark.parametrize("model_name,kept", [
    pytest.param("kernel_mlp", 512, id="kernel_mlp"),
    pytest.param("kernel_attn", None, id="kernel_attn"),
    # the KernelMLP's 1024-wide FFN at dropout rates 0, 0.25 and 0.75
    pytest.param("kernel_mlp", 1024, id="kernel_mlp-r0"),
    pytest.param("kernel_mlp", 768, id="kernel_mlp-r0.25"),
    pytest.param("kernel_mlp", 256, id="kernel_mlp-r0.75"),
])
def test_fleet_use_kernels_matches_dense(model_name, kept):
    """Acceptance gate: `use_kernels=True` cohort == dense cohort —
    deltas, sim-times, and aggregation (interpret mode)."""
    from repro.models.kernel_models import KERNEL_MODELS
    model_cls = KERNEL_MODELS[model_name]
    if model_name == "kernel_mlp":
        keep_maps = {0: {"ffn": np.arange(kept)}, 1: {"ffn": np.arange(kept)}}
    else:
        keep_maps = {0: {"heads": np.arange(2), "ffn": np.arange(128)},
                     1: {"heads": np.arange(2), "ffn": np.arange(128)}}
    params, rd, rk = _fleet_pair(model_cls, keep_maps)
    for a, b in zip(jax.tree.leaves(rd.deltas), jax.tree.leaves(rk.deltas)):
        assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) < 1e-4
    assert rd.sim_times == rk.sim_times
    for a, b in zip(jax.tree.leaves(rd.aggregate(params)),
                    jax.tree.leaves(rk.aggregate(params))):
        assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) < 1e-4


def test_fleet_use_kernels_requires_kernel_model():
    from repro.fl.client import FleetClient
    from repro.fl.fleet import FleetEngine
    from repro.models.small import FemnistCNN

    r = _rng(0)
    c = [FleetClient(0, FemnistCNN, r.randn(20, 28, 28, 1).astype(np.float32),
                     r.randint(0, 62, 20).astype(np.int32), speed=10.0)]
    with pytest.raises(ValueError, match="apply_kernels"):
        FleetEngine(FemnistCNN, c, FemnistCNN.UNIT_SPECS, use_kernels=True)


def test_unit_major_expand_and_stats():
    """The tile<0 (unit-major) grammar: expand_indices gives contiguous
    per-unit slabs and invariant stats reduce over them."""
    from repro.core.invariant import neuron_stats_for_group
    from repro.core.submodel import expand_indices

    idx = expand_indices(np.array([0, 2]), -16, 4)
    expect = np.concatenate([np.arange(0, 16), np.arange(32, 48)])
    assert np.array_equal(idx, expect)
    # stats: wq (D, H*HD) unit-major; bump head 1's slab only
    r = _rng(6)
    w0 = {"attn": {"wq": jnp.asarray(r.randn(D, H * HD), jnp.float32)}}
    bump = np.zeros((D, H * HD), np.float32)
    bump[:, HD:2 * HD] = 1.0
    w1 = {"attn": {"wq": w0["attn"]["wq"] + jnp.asarray(bump)}}
    g = {"name": "heads", "size": H,
         "out": [("attn/wq", 1, -HD)], "in": []}
    stats = np.asarray(neuron_stats_for_group(w0, w1, g))
    assert stats.shape == (H,)
    assert stats[1] > 0.0
    assert np.allclose(stats[[0, 2, 3]], 0.0)


def test_train_step_use_kernels_matches_dense():
    """launch/steps.py make_train_step(use_kernels=True): identical fp32
    loss and matching masked-FFN gradients vs the dense train step."""
    from repro.configs import get_config
    from repro.core import transformer_hooks as hooks
    from repro.launch.steps import make_train_step
    from repro.models import model as M
    from repro.optim import make_optimizer

    cfg = (get_config("stablelm-12b").smoke()
           .with_overrides(grad_accum=1, dtype="float32",
                           param_dtype="float32"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = {"tokens": jnp.full((B, S), 3, jnp.int32),
             "targets": jnp.ones((B, S), jnp.int32)}
    masks = hooks.full_masks(cfg)

    def drop_half(m):
        m = np.asarray(m).copy()
        m[..., m.shape[-1] // 2:] = 0.0
        return jnp.asarray(m)
    masks = jax.tree.map(drop_half, masks)
    opt = make_optimizer(cfg.optimizer)
    opt_state = opt.init(params)
    sd = jax.jit(make_train_step(cfg, with_masks=True))
    sk = jax.jit(make_train_step(cfg, with_masks=True, use_kernels=True))
    pd, _, md = sd(params, opt_state, batch, masks)
    pk, _, mk = sk(params, opt_state, batch, masks)
    assert abs(float(md["loss"]) - float(mk["loss"])) < 1e-5
    # post-Adam params agree to optimizer-rescaled fp tolerance
    for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(pk)):
        assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) < 1e-3
