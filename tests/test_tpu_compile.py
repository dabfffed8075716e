"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed beside the CPU backend, compiles
each program for one chip of a `v5e:2x2` topology that is described, not
attached. What it refuses here (a block not aligned to the lane tiling,
more scoped VMEM than a kernel may take) it would refuse on the chip. Each
case asserts that the compiled program contains the Mosaic kernel
(`tpu_custom_call`) rather than an interpreted loop.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and pytest-xdist
workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_gqa import decode_gqa
from repro.kernels.masked_ffn import masked_ffn, masked_ffn_batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def compiled_kernels(monkeypatch, no_compile_cache):
    """Steer the kernels' one interpret decision to the chip's value: the
    process's backend is the CPU, the compile target is the TPU."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("workload", ["femnist_kernel", "femnist_attn"])
def test_fleet_cohort_program_compiles(one_chip, compiled_kernels, workload):
    """The fleet cohort program (masked SGD mapped over the clients,
    through the differentiable kernels, on batches gathered from the
    clients' resident samples) for KernelMLP and KernelAttnClassifier."""
    from repro.fl.fleet import _cohort_fn
    from repro.fl.simulation import WORKLOADS
    from repro.models.kernel_models import KERNEL_MODELS

    model_cls = KERNEL_MODELS[WORKLOADS[workload][1]]
    C, steps, bs, K, rows = 8, 2, 10, 2, 30
    params = jax.eval_shape(model_cls.init, jax.random.PRNGKey(0))

    def put(tree, lead=()):
        return jax.tree.map(
            lambda a: _sds(one_chip, lead + a.shape, a.dtype), tree)
    run = _cohort_fn(model_cls, use_kernels=True)
    compiled = run.lower(
        put(params), put(params, (K,)), _sds(one_chip, (C,), jnp.int32),
        _sds(one_chip, (C, rows) + model_cls.input_shape),
        _sds(one_chip, (C, steps, bs), jnp.int32),
        _sds(one_chip, (C, steps, bs), jnp.int32),
        _sds(one_chip, (C, steps, bs)), _sds(one_chip, (C,)),
        n_steps=steps).compile()
    _assert_kernel(compiled)


def test_masked_ffn_grad_zoo_width(one_chip, no_compile_cache):
    """The masked train-step route at stablelm-12b's FFN (d=5120,
    F=13824, SwiGLU, bf16): forward, dx and dW kernels fit scoped VMEM."""
    d, F, M = 5120, 13824, 1024
    bf = jnp.bfloat16

    def step(x, wi, wo, wg, rm):
        def loss(x, wi, wo, wg):
            y = masked_ffn_batch(x, wi, wo, rm, w_gate=wg, act="silu",
                                 interpret=False)
            return y.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, wi, wo, wg)
    compiled = jax.jit(step).lower(
        _sds(one_chip, (M, d), bf), _sds(one_chip, (d, F), bf),
        _sds(one_chip, (F, d), bf), _sds(one_chip, (d, F), bf),
        _sds(one_chip, (M, F), bf)).compile()
    _assert_kernel(compiled)


def test_masked_ffn_refuses_vmem_overflow():
    """A width whose dW blocks exceed the kernel's VMEM cap is refused
    before tracing, never sent to the compiler or a dense fallback."""
    d, F = 32768, 256
    x = jax.ShapeDtypeStruct((128, d), jnp.float32)
    w = jax.ShapeDtypeStruct((d, F), jnp.float32)
    with pytest.raises(ValueError, match="scoped VMEM"):
        jax.eval_shape(
            lambda x, wi, wo: masked_ffn(x, wi, wo, jnp.ones((2,)),
                                         w_gate=wi, interpret=True),
            x, w, jax.ShapeDtypeStruct((F, d), jnp.float32))


def test_decode_gqa_zoo_shape(one_chip, no_compile_cache):
    """The serving opt-in at stablelm-12b's decode shape (H=32, KV=8,
    hd=128, C=2048, bf16) with the budget-sized cache block."""
    B, H, KV, hd, C = 8, 32, 8, 128, 2048
    bf = jnp.bfloat16
    compiled = jax.jit(
        lambda q, k, v, n: decode_gqa(q, k, v, n, interpret=False)).lower(
        _sds(one_chip, (B, H, hd), bf), _sds(one_chip, (B, C, KV, hd), bf),
        _sds(one_chip, (B, C, KV, hd), bf),
        _sds(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)
