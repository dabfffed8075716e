"""The configurations' FLOP and parameter counts against XLA's own count of
the extracted sub-model's gradient, and against the program's models."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT
from bench.reference import seq_cnn

CONFIGS = ("femnist_cnn", "cifar_vgg9")


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("rate", [1.0, 0.75])
@pytest.mark.parametrize("name", CONFIGS)
def test_train_flops_match_xla(name, rate):
    """XLA counts the sub-model's gradient at batch 4 within 3% above the
    required FLOPs: the excess is the elementwise work (bias, ReLU,
    pooling, softmax) that the count leaves out on purpose."""
    cfg = _config(name)
    params = seq_cnn.init(cfg, jax.random.PRNGKey(0))
    keep = {n: np.arange(seq_cnn.keep_count(s, rate))
            for n, s in seq_cnn.droppable(cfg)}
    sub = seq_cnn.extract(cfg, params, keep) if rate < 1 else params
    x = jnp.ones((4, *cfg["input_shape"]))

    def loss(p):
        logits = seq_cnn.forward(cfg, p, x)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[:, 0])
    cost = jax.jit(jax.grad(loss)).lower(sub).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = cost["flops"] / (4 * seq_cnn.train_flops(cfg, rate))
    assert 1.0 <= ratio <= 1.03, ratio


@pytest.mark.parametrize("name", CONFIGS)
def test_param_counts(name):
    """Sub-model sizes agree with the extracted trees, and the full model
    with the program's own model of the same name and the file's count."""
    from repro.models.small import MODELS
    cfg = _config(name)
    params = seq_cnn.init(cfg, jax.random.PRNGKey(0))
    prog = MODELS[name].init(jax.random.PRNGKey(0))
    assert (jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, prog))
    assert seq_cnn.param_count(cfg) == cfg["params"]
    keep = {n: np.arange(seq_cnn.keep_count(s, 0.75))
            for n, s in seq_cnn.droppable(cfg)}
    sub = seq_cnn.extract(cfg, params, keep)
    assert (sum(a.size for a in jax.tree.leaves(sub))
            == seq_cnn.param_count(cfg, 0.75))


def test_forward_matches_program():
    """The reference forward pass is the program's model on the same
    weights, and an extracted sub-model is the masked full model."""
    from repro.core import submodel
    from repro.models.small import FemnistCNN
    cfg = _config("femnist_cnn")
    params = seq_cnn.init(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 28, 28, 1))
    np.testing.assert_allclose(seq_cnn.forward(cfg, params, x),
                               FemnistCNN.apply(params, x), atol=1e-5)
    keep = {n: np.sort(np.random.RandomState(3).choice(
        s, seq_cnn.keep_count(s, 0.75), replace=False))
        for n, s in seq_cnn.droppable(cfg)}
    mask = submodel.keep_mask(params, FemnistCNN.UNIT_SPECS, keep)
    np.testing.assert_allclose(
        seq_cnn.forward(cfg, seq_cnn.extract(cfg, params, keep), x),
        FemnistCNN.apply(submodel.apply_mask(params, mask), x), atol=1e-5)
