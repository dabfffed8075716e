"""models/small._pool against jax.lax.reduce_window's max-pool.

`_pool` carries its own gradient (a first-max selection written as selects)
so that the training step holds no select_and_scatter. Forward and gradient
must equal reduce_window's exactly, ties included, on every pool shape the
paper's two CNNs run and on an odd-sized input that VALID crops.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.small import _pool


def _ref_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _loss(pool):
    # a weighted sum, so each output position sends back its own gradient
    def f(x, w):
        return jnp.sum(pool(jax.nn.relu(x)) * w)
    return f


def _input(shape, ties, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    if ties:
        # constant 2x2 blocks, a third of them negative (all-zero ties after
        # relu); then single positions lowered, so the windows hold one to
        # four tied maxima in every arrangement
        b, h, w, c = shape
        blocks = rng.standard_normal((b, h // 2 + 1, w // 2 + 1, c))
        blocks = np.where(rng.random(blocks.shape) < 0.3, -np.abs(blocks),
                          blocks)
        x = np.repeat(np.repeat(blocks, 2, 1), 2, 2)[:, :h, :w]
        x = np.where(rng.random(shape) < 0.3, x - 1.0, x).astype(np.float32)
    return jnp.asarray(x)


SHAPES = {
    "femnist_pool1": (10, 28, 28, 16),
    "femnist_pool2": (10, 14, 14, 64),
    "vgg9_pool1": (20, 32, 32, 32),
    "vgg9_pool2": (20, 16, 16, 64),
    "vgg9_pool3": (20, 8, 8, 128),
    "odd": (3, 7, 9, 5),
}


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_pool_and_grad_equal_reduce_window(name, ties):
    shape = SHAPES[name]
    x = _input(shape, ties, seed=len(name))
    out = _ref_pool(x).shape
    w = jnp.asarray(np.random.default_rng(7).standard_normal(out),
                    jnp.float32)

    fwd, ref_fwd = jax.jit(_pool), jax.jit(_ref_pool)
    np.testing.assert_array_equal(fwd(x), ref_fwd(x))

    grad = jax.jit(jax.grad(_loss(_pool)))
    ref_grad = jax.jit(jax.grad(_loss(_ref_pool)))
    g, ref_g = grad(x, w), ref_grad(x, w)
    assert g.shape == x.shape and g.dtype == x.dtype
    np.testing.assert_array_equal(g, ref_g)

    # inside jax.lax.map, as the fleet's cohort program runs each client
    xs, ws = jnp.stack([x, -x]), jnp.stack([w, 2 * w])
    mapped = jax.jit(lambda a, b: jax.lax.map(
        lambda t: jax.grad(_loss(_pool))(*t), (a, b)))
    ref_mapped = jax.jit(lambda a, b: jax.lax.map(
        lambda t: jax.grad(_loss(_ref_pool))(*t), (a, b)))
    np.testing.assert_array_equal(mapped(xs, ws), ref_mapped(xs, ws))


def test_tied_window_gradient_goes_to_first_max():
    """All four tie: the first position takes it. Later ties: the first of
    them in row-major window order. Cropped rows and columns get zero."""
    x = jnp.asarray([[1., 1., 0.],
                     [1., 1., 5.],
                     [9., 9., 9.]])[None, :, :, None]
    g = jax.grad(lambda a: _pool(a).sum())(x)[0, :, :, 0]
    np.testing.assert_array_equal(g, [[1., 0., 0.], [0., 0., 0.],
                                      [0., 0., 0.]])
    x = jnp.asarray([[0., 3.], [3., 3.]])[None, :, :, None]
    g = jax.grad(lambda a: _pool(a).sum())(x)[0, :, :, 0]
    np.testing.assert_array_equal(g, [[0., 1.], [0., 0.]])
