"""Paper-scale models for the faithful FLuID reproduction.

CNN (FEMNIST), VGG-9 (CIFAR10), 2-layer LSTM (Shakespeare) — exactly the
model families of the paper's evaluation (Section 6), in pure JAX.

Each model exposes:
  init(key)        -> params (nested dict)
  apply(params, x) -> logits
  UNIT_SPECS       -> droppable neuron groups for core/submodel.py

Unit-spec grammar: a group is
  {"name": str, "size": n,
   "out": [(path, axis, tile_factor)],   # producer arrays (weights making the neuron)
   "in":  [(path, axis, tile_factor)]}   # consumer arrays (weights reading it)
tile_factor handles structured axes: conv->FC flatten (channel-fastest, factor
= #spatial positions) and LSTM gate blocks (factor=4). Axis length must equal
size * tile_factor; kept indices expand to {t*size + i}.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dense(key, fan_in, shape):
    return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


@jax.custom_vjp
def _pool(x):
    """2x2, stride 2, VALID max-pool of NHWC input.

    Its gradient is the one jax.lax.reduce_window's max gives, bit for bit:
    each window's incoming gradient goes to the first position, in row-major
    window order, that holds the window's max (select_and_scatter's `ge`
    rule). Written as compares and selects, it runs as elementwise passes,
    where XLA lowers reduce_window's own gradient to a windowed scatter
    (select_and_scatter) that a TPU v5e runs at about twice the time of an
    elementwise pass over the same tensor."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _pool_fwd(x):
    return _pool(x), x


def _pool_bwd(x, g):
    b, h, w, c = x.shape
    ho, wo = h // 2, w // 2
    # (B, H/2, 2, W/2, 2, C): each window on axes 2 and 4. H and W are
    # major dims under the TPU's NHWC layout, so the split moves no data.
    xw = x[:, :2 * ho, :2 * wo].reshape(b, ho, 2, wo, 2, c)
    x00, x01, x10, x11 = (xw[:, :, i, :, j] for i in (0, 1) for j in (0, 1))
    y = jnp.maximum(jnp.maximum(x00, x01), jnp.maximum(x10, x11))
    first = jnp.where(x00 == y, 0, jnp.where(
        x01 == y, 1, jnp.where(x10 == y, 2, 3))).astype(jnp.int32)
    pos = (2 * jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2, 1, 2, 1), 2)
           + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2, 1, 2, 1), 4))
    dx = jnp.where(first[:, :, None, :, None, :] == pos,
                   g[:, :, None, :, None, :], 0)
    # materialized, as select_and_scatter's output was: fused into the
    # consumers instead, the selection changes their fusions and with them
    # the rounding of the convolution gradients on a TPU v5e
    dx = jax.lax.optimization_barrier(dx.reshape(b, 2 * ho, 2 * wo, c))
    # rows and columns the VALID pool cropped get zero
    return (jnp.pad(dx, ((0, 0), (0, h - 2 * ho), (0, w - 2 * wo), (0, 0))),)


_pool.defvjp(_pool_fwd, _pool_bwd)


# ---------------------------------------------------------------------------
# FEMNIST CNN: 2x [5x5 conv + 2x2 maxpool], FC-120, softmax-62 (paper §6)

class FemnistCNN:
    num_classes = 62
    input_shape = (28, 28, 1)

    UNIT_SPECS = [
        {"name": "conv1", "size": 16,
         "out": [("conv1/w", 3, 1), ("conv1/b", 0, 1)],
         "in": [("conv2/w", 2, 1)]},
        {"name": "conv2", "size": 64,
         "out": [("conv2/w", 3, 1), ("conv2/b", 0, 1)],
         "in": [("fc1/w", 0, 49)]},          # 7x7 spatial positions
        {"name": "fc1", "size": 120,
         "out": [("fc1/w", 1, 1), ("fc1/b", 0, 1)],
         "in": [("out/w", 0, 1)]},
    ]

    @staticmethod
    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "conv1": {"w": _dense(ks[0], 25, (5, 5, 1, 16)),
                      "b": jnp.zeros((16,), jnp.float32)},
            "conv2": {"w": _dense(ks[1], 25 * 16, (5, 5, 16, 64)),
                      "b": jnp.zeros((64,), jnp.float32)},
            "fc1": {"w": _dense(ks[2], 7 * 7 * 64, (7 * 7 * 64, 120)),
                    "b": jnp.zeros((120,), jnp.float32)},
            "out": {"w": _dense(ks[3], 120, (120, 62)),
                    "b": jnp.zeros((62,), jnp.float32)},
        }

    @staticmethod
    def apply(params, x):
        x = jax.nn.relu(_conv(x, params["conv1"]["w"], params["conv1"]["b"]))
        x = _pool(x)
        x = jax.nn.relu(_conv(x, params["conv2"]["w"], params["conv2"]["b"]))
        x = _pool(x)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        return x @ params["out"]["w"] + params["out"]["b"]


# ---------------------------------------------------------------------------
# VGG-9 for CIFAR10 (paper §6: 6 conv 3x3 [32,32,64,64,128,128] + FC512 + FC256)

class Vgg9:
    num_classes = 10
    input_shape = (32, 32, 3)

    _CONVS = [("c1a", 3, 32), ("c1b", 32, 32), ("c2a", 32, 64),
              ("c2b", 64, 64), ("c3a", 64, 128), ("c3b", 128, 128)]

    UNIT_SPECS = (
        [{"name": n, "size": co,
          "out": [(f"{n}/w", 3, 1), (f"{n}/b", 0, 1)],
          "in": [(f"{nx}/w", 2, 1)]}
         for (n, ci, co), (nx, _, _) in zip(_CONVS[:-1], _CONVS[1:])]
        + [{"name": "c3b", "size": 128,
            "out": [("c3b/w", 3, 1), ("c3b/b", 0, 1)],
            "in": [("fc1/w", 0, 16)]},       # 4x4 spatial positions
           {"name": "fc1", "size": 512,
            "out": [("fc1/w", 1, 1), ("fc1/b", 0, 1)],
            "in": [("fc2/w", 0, 1)]},
           {"name": "fc2", "size": 256,
            "out": [("fc2/w", 1, 1), ("fc2/b", 0, 1)],
            "in": [("out/w", 0, 1)]}])

    @staticmethod
    def init(key):
        ks = jax.random.split(key, 9)
        p = {}
        for i, (n, ci, co) in enumerate(Vgg9._CONVS):
            p[n] = {"w": _dense(ks[i], 9 * ci, (3, 3, ci, co)),
                    "b": jnp.zeros((co,), jnp.float32)}
        p["fc1"] = {"w": _dense(ks[6], 4 * 4 * 128, (4 * 4 * 128, 512)),
                    "b": jnp.zeros((512,), jnp.float32)}
        p["fc2"] = {"w": _dense(ks[7], 512, (512, 256)),
                    "b": jnp.zeros((256,), jnp.float32)}
        p["out"] = {"w": _dense(ks[8], 256, (256, 10)),
                    "b": jnp.zeros((10,), jnp.float32)}
        return p

    @staticmethod
    def apply(params, x):
        for i, (n, _, _) in enumerate(Vgg9._CONVS):
            x = jax.nn.relu(_conv(x, params[n]["w"], params[n]["b"]))
            if i % 2 == 1:
                x = _pool(x)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
        return x @ params["out"]["w"] + params["out"]["b"]


# ---------------------------------------------------------------------------
# Shakespeare 2-layer LSTM classifier, 128 hidden units (paper §6)

class ShakespeareLSTM:
    vocab = 80
    embed_dim = 8
    hidden = 128
    num_classes = 80
    seq_len = 20

    UNIT_SPECS = [
        {"name": "lstm1", "size": 128,
         "out": [("lstm1/W", 1, 4), ("lstm1/U", 1, 4), ("lstm1/b", 0, 4)],
         "in": [("lstm1/U", 0, 1), ("lstm2/W", 0, 1)]},
        {"name": "lstm2", "size": 128,
         "out": [("lstm2/W", 1, 4), ("lstm2/U", 1, 4), ("lstm2/b", 0, 4)],
         "in": [("lstm2/U", 0, 1), ("out/w", 0, 1)]},
    ]

    @staticmethod
    def init(key):
        ks = jax.random.split(key, 6)
        V, E, H = (ShakespeareLSTM.vocab, ShakespeareLSTM.embed_dim,
                   ShakespeareLSTM.hidden)
        return {
            "embed": _dense(ks[0], E, (V, E)),
            "lstm1": {"W": _dense(ks[1], E, (E, 4 * H)),
                      "U": _dense(ks[2], H, (H, 4 * H)),
                      "b": jnp.zeros((4 * H,), jnp.float32)},
            "lstm2": {"W": _dense(ks[3], H, (H, 4 * H)),
                      "U": _dense(ks[4], H, (H, 4 * H)),
                      "b": jnp.zeros((4 * H,), jnp.float32)},
            "out": {"w": _dense(ks[5], H, (H, V)),
                    "b": jnp.zeros((V,), jnp.float32)},
        }

    @staticmethod
    def _lstm(p, xs):
        """xs: (B,S,in). Hidden size inferred from U (supports sub-models)."""
        H = p["U"].shape[0]
        B = xs.shape[0]

        def step(carry, x):
            h, c = carry
            z = x @ p["W"] + h @ p["U"] + p["b"]
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h
        init = (jnp.zeros((B, H), xs.dtype), jnp.zeros((B, H), xs.dtype))
        (_, _), hs = jax.lax.scan(step, init, xs.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2)

    @staticmethod
    def apply(params, x):
        """x: (B,S) int32 char ids -> logits for next char (last position)."""
        e = jnp.take(params["embed"], x, axis=0)
        h = ShakespeareLSTM._lstm(params["lstm1"], e)
        h = ShakespeareLSTM._lstm(params["lstm2"], h)
        return h[:, -1] @ params["out"]["w"] + params["out"]["b"]


# ---------------------------------------------------------------------------
# Population-scale probe model: 32-dim vector in, one droppable hidden layer.
# Small on purpose — a 5k-client cohort's stacked deltas stay a few hundred
# MB short of anything interesting, so population runs can sample cohorts
# of thousands from a 100k-client store on one host.

class SynthMLP:
    num_classes = 10
    input_shape = (32,)

    UNIT_SPECS = [
        {"name": "fc1", "size": 64,
         "out": [("fc1/w", 1, 1), ("fc1/b", 0, 1)],
         "in": [("out/w", 0, 1)]},
    ]

    @staticmethod
    def init(key):
        ks = jax.random.split(key, 2)
        return {
            "fc1": {"w": _dense(ks[0], 32, (32, 64)),
                    "b": jnp.zeros((64,), jnp.float32)},
            "out": {"w": _dense(ks[1], 64, (64, 10)),
                    "b": jnp.zeros((10,), jnp.float32)},
        }

    @staticmethod
    def apply(params, x):
        h = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        return h @ params["out"]["w"] + params["out"]["b"]


MODELS = {"femnist_cnn": FemnistCNN, "cifar_vgg9": Vgg9,
          "shakespeare_lstm": ShakespeareLSTM, "synth_mlp": SynthMLP}
