"""Plain reference of a sequential CNN described by a configuration file.

This module is one model family of the benchmark. A configuration names
its family (`"family": "<name>"`), and everything the benchmark does that
depends on the model goes through `bench/reference/<name>.py`: the
harness (`bench/run.py`, `bench/harness/`) and the round reference
(`bench/reference/fluid_ref.py`) import no family by name. A family
provides:

  init(cfg, key)                      full-width float32 parameters
  forward(cfg, params, x, precision)  the model's output on a batch
  example_loss(cfg, params, x, y, precision) -> (B,) float32
                                      each example's loss; the round
                                      reference's SGD takes its mean, the
                                      correctness check its sum
  rows(cfg, rng, n_clients, samples_per_client, writers_per_client)
                                      -> (x, y), a fixed cohort's data
                                      drawn from a np.random.Generator,
                                      client after client
  droppable(cfg), keep_count(size, rate)
                                      the unit groups a sub-model may
                                      thin and the units it keeps
  extract(cfg, params, keep), embed(cfg, full_like, sub_delta, keep)
                                      a sub-model's weights, and its delta
                                      back in full coordinates with a mask
  unit_stats(cfg, prev, new)          per group, per unit: its relative
                                      update (invariant-dropout statistics)
  param_count(cfg, rate), train_flops(cfg, rate), sample_bytes(cfg)
                                      the work counts of the metrics

A family may take any input and label shapes and dtypes: the round
reference keeps `x`'s and `y`'s as `rows` makes them (token ids stay
integers, a label per position stays one).

A configuration lists its layers in order: `conv` layers (k x k kernel,
stride 1, SAME padding, ReLU, an optional 2x2 max-pool) and then `dense`
layers (ReLU on every one but the last). Convolution outputs are flattened
in NHWC order, channel fastest, before the first dense layer. Weights are
HWIO for convolutions and (in, out) for dense layers, each with a bias, and
are drawn as normal / sqrt(fan_in) with zero biases.

A sub-model keeps a subset of the units (channels or neurons) of every
droppable layer. `extract` gathers its weights physically, so the sub-model
is a smaller network of the same family; `embed` scatters a sub-model's
delta back to full coordinates with its 0/1 mask. Costs follow the same
description: `train_flops` counts the multiply-adds of the forward pass,
the weight gradient and the input gradient of every layer but the first,
at the kept widths.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _shapes(cfg):
    """[(layer, in_width, out_width, spatial_in, spatial_out)] at full width.
    spatial is the (H, W) a conv reads and writes; dense layers carry the
    flattened input length in in_width."""
    h, w, c = cfg["input_shape"]
    out = []
    conv_done = False
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            out.append((layer, c, layer["width"], (h, w), (h, w)))
            c = layer["width"]
            if layer.get("pool"):
                h, w = h // 2, w // 2
        else:
            if not conv_done:
                conv_done = True
                out.append((layer, h * w * c, layer["width"], (h, w), None))
            else:
                out.append((layer, c, layer["width"], None, None))
            c = layer["width"]
    return out


def droppable(cfg):
    """[(name, size)] of the layers whose units a sub-model may drop."""
    return [(l["name"], l["width"]) for l in cfg["layers"]
            if l.get("droppable", True)]


def keep_count(size: int, rate: float) -> int:
    """Units a sub-model of size `rate` keeps in a layer of `size` units."""
    return max(1, int(round(size * rate)))


def init(cfg, key):
    """Full-width parameters, {layer: {"w", "b"}}, float32."""
    shapes = _shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (layer, cin, cout, _, _) in zip(keys, shapes):
        if layer["kind"] == "conv":
            kk = layer["kernel"]
            shape, fan_in = (kk, kk, cin, cout), kk * kk * cin
        else:
            shape, fan_in = (cin, cout), cin
        params[layer["name"]] = {
            "w": jax.random.normal(k, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in)),
            "b": jnp.zeros((cout,), jnp.float32)}
    return params


def forward(cfg, params, x, precision=jax.lax.Precision.HIGHEST):
    """Logits of a batch x (B, H, W, C). Widths are read from the params, so
    the same function runs an extracted sub-model."""
    n = len(cfg["layers"])
    for i, layer in enumerate(cfg["layers"]):
        p = params[layer["name"]]
        if layer["kind"] == "conv":
            x = jax.lax.conv_general_dilated(
                x, p["w"].astype(x.dtype), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision) + p["b"].astype(x.dtype)
            x = jax.nn.relu(x)
            if layer.get("pool"):
                x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                          (1, 2, 2, 1), (1, 2, 2, 1),
                                          "VALID")
        else:
            if x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            x = jnp.dot(x, p["w"].astype(x.dtype),
                        precision=precision) + p["b"].astype(x.dtype)
            if i < n - 1:
                x = jax.nn.relu(x)
    return x


def example_loss(cfg, params, x, y, precision=jax.lax.Precision.HIGHEST):
    """(B,) float32 cross-entropy of each example's logits against its
    class label."""
    logits = forward(cfg, params, x, precision).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return lse - gold


def rows(cfg, rng, n_clients: int, samples_per_client: int,
         writers_per_client: int):
    """(x, y): float32 images and int32 class labels of a fixed cohort,
    `samples_per_client` rows a client, client after client. A row is a
    class prototype, plus the style of one of the client's own
    `writers_per_client` writers, plus noise; labels are uniform, so the
    skew between clients is by writer, as in LEAF's FEMNIST split."""
    c, s, wpc = n_clients, samples_per_client, writers_per_client
    shape = tuple(cfg["input_shape"])
    k = int(cfg["num_classes"])
    protos = rng.standard_normal((k, *shape), dtype=np.float32)
    styles = 0.6 * rng.standard_normal((c * wpc, *shape), dtype=np.float32)
    y = rng.integers(0, k, size=c * s).astype(np.int32)
    writer = (np.repeat(np.arange(c), s) * wpc
              + np.tile(np.arange(s) % wpc, c))
    x = protos[y]
    x += 1.2 * rng.standard_normal(x.shape, dtype=np.float32)
    x += styles[writer]
    return x, y


def kept_axes(cfg, keep):
    """{layer: {"w": [idx or None per axis], "b": idx or None}} for a keep
    map {layer: kept unit indices}; None keeps the whole axis."""
    out = {}
    prev = None                    # kept units of the previous layer
    prev_width = cfg["input_shape"][2]
    for layer, _, _, sp_in, _ in _shapes(cfg):
        name = layer["name"]
        own = (np.asarray(keep[name]) if layer.get("droppable", True)
               else None)
        if layer["kind"] == "conv":
            w = [None, None, prev, own]
        elif sp_in is not None and prev is not None:
            # first dense layer after the convolutions: rows are
            # (position, channel) with the channel fastest
            pos = np.arange(sp_in[0] * sp_in[1])
            w = [(pos[:, None] * prev_width + prev[None, :]).reshape(-1),
                 own]
        else:
            w = [prev, own]
        out[name] = {"w": w, "b": own}
        prev = own
        prev_width = layer["width"]
    return out


def _take(a, idx_per_axis):
    for ax, idx in enumerate(idx_per_axis):
        if idx is not None:
            a = jnp.take(a, jnp.asarray(idx), axis=ax)
    return a


def extract(cfg, params, keep):
    """The physically smaller sub-model that keeps `keep`."""
    axes = kept_axes(cfg, keep)
    return {name: {"w": _take(p["w"], axes[name]["w"]),
                   "b": _take(p["b"], [axes[name]["b"]])}
            for name, p in params.items()}


def embed(cfg, full_like, sub_delta, keep):
    """(full-coordinate delta, 0/1 mask) of a sub-model's delta."""
    axes = kept_axes(cfg, keep)
    delta, mask = {}, {}
    for name, p in full_like.items():
        delta[name], mask[name] = {}, {}
        for leaf, idx in (("w", axes[name]["w"]), ("b", [axes[name]["b"]])):
            shape = p[leaf].shape
            grid = np.ix_(*[np.arange(n) if i is None else np.asarray(i)
                            for n, i in zip(shape, idx)])
            d = np.zeros(shape, np.float32)
            d[grid] = np.asarray(sub_delta[name][leaf], np.float32)
            m = np.zeros(shape, np.float32)
            m[grid] = 1.0
            delta[name][leaf], mask[name][leaf] = d, m
    return delta, mask


def unit_stats(cfg, prev, new):
    """Per layer, per unit: ||new - prev|| / (||prev|| + 1e-8) over the
    weights and bias that produce the unit (its output axis)."""
    out = {}
    for name, _ in droppable(cfg):
        num = den = 0.0
        for leaf in ("w", "b"):
            w0 = np.asarray(prev[name][leaf], np.float32)
            w1 = np.asarray(new[name][leaf], np.float32)
            size = w0.shape[-1]
            num = num + np.square(w1 - w0).reshape(-1, size).sum(axis=0)
            den = den + np.square(w0).reshape(-1, size).sum(axis=0)
        out[name] = (np.sqrt(num) / (np.sqrt(den) + np.float32(1e-8))
                     ).astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# costs

def _widths(cfg, rate: float):
    return {name: keep_count(size, rate) if rate < 1.0 else size
            for name, size in droppable(cfg)}


def _taps(n: int, k: int) -> int:
    """Kernel taps that land inside the input, summed over the n outputs of
    one axis of a stride-1 SAME convolution (taps on padding multiply
    zeros and are not work)."""
    lo = (k - 1) // 2
    return sum(min(i + k - lo, n) - max(i - lo, 0) for i in range(n))


def _layer_macs(cfg, rate: float):
    """[(macs per sample of the layer's forward, param count)] at `rate`."""
    kept = _widths(cfg, rate)
    out = []
    prev_w = cfg["input_shape"][2]
    for layer, cin, cout, sp_in, sp_out in _shapes(cfg):
        w_out = kept.get(layer["name"], cout)
        if layer["kind"] == "conv":
            kk = layer["kernel"]
            taps = _taps(sp_out[0], kk) * _taps(sp_out[1], kk)
            macs = taps * prev_w * w_out
            n_par = kk * kk * prev_w * w_out + w_out
        elif sp_in is not None:
            rows = sp_in[0] * sp_in[1] * prev_w
            macs, n_par = rows * w_out, rows * w_out + w_out
        else:
            macs, n_par = prev_w * w_out, prev_w * w_out + w_out
        out.append((macs, n_par))
        prev_w = w_out
    return out


def param_count(cfg, rate: float = 1.0) -> int:
    """Parameters of the sub-model of size `rate` (1.0: the full model)."""
    return sum(n for _, n in _layer_macs(cfg, rate))


def train_flops(cfg, rate: float = 1.0) -> float:
    """FLOPs one training sample requires at sub-model size `rate`:
    forward, weight gradient, and input gradient of every layer but the
    first (the input needs none), two per multiply-add. Convolution taps
    that fall on the zero padding, bias adds, ReLU, pooling and the softmax
    are not counted."""
    macs = [m for m, _ in _layer_macs(cfg, rate)]
    return 2.0 * (2 * sum(macs) + sum(macs[1:]))


def sample_bytes(cfg) -> int:
    """Bytes of one float32 input sample and its int32 label."""
    h, w, c = cfg["input_shape"]
    return 4 * h * w * c + 4
