"""Plain reference of DiT-XL/2 at 256x256, and its seeded weights.

This file stands beside ``dit-xl-2-256.json`` and belongs to the
benchmark: it imports nothing of the system under test.  It follows
Peebles & Xie 2023 (arXiv:2212.09748, §3.2) and ``facebookresearch/DiT``
``models.py``:

* the latent (B, H, W, C) through a p x p convolution of stride p (the
  patch embedding), plus the fixed 2-D sin-cos position embedding
  (``get_2d_sincos_pos_embed``: the first half of the width encodes the
  column, the second the row);
* c = t_emb + y_emb: the timestep as ``[cos, sin]`` of 256 frequencies
  through Linear-SiLU-Linear, and the null label's row, the last of a
  table of ``num_classes + 1`` rows (``class_label`` in the file);
* ``depth`` adaLN-Zero blocks: six vectors of Linear(SiLU(c)) shift,
  scale and gate a multi-head self-attention (one qkv projection,
  softmax over the scaled scores, an output projection) and a tanh-GELU
  MLP, each after a LayerNorm without affine at epsilon 1e-6;
* the final layer: an adaLN shift and scale, a linear to p*p*2C
  (``learn_sigma``), unpatchified; the noise is the first C channels.

Every linear keeps its bias, as published.  Departures, listed under
``assumed`` in the configuration file: every weight and bias is drawn
from the seed (DiT starts the adaLN and final layers at zero, which
makes every block the identity and the output 0, and all biases at 0);
attention is plain softmax over the whole 256 x 256 score matrix.

The blocks run one after another in a Python loop over the stacked
weights.  ``make_params`` builds the weights on the device in one jitted
call; the harness hands one copy to the system and the reference makes
its own.  ``forward`` is straightforward ``jax.numpy`` in one dtype:
float32 under ``jax.default_matmul_precision("highest")`` for the
reference, bfloat16 throughout for the control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6


def layout(sz: dict) -> dict:
    """The weight tree as ``{path: (shape, kind)}``; ``kind`` is ``w``
    (a weight, fan-in scaled normal), ``b`` (a bias) or ``table`` (the
    label embedding).  ``blocks/*`` stack the ``depth`` blocks' weights
    on a leading axis."""
    D, p, L = sz["hidden_size"], sz["patch_size"], sz["depth"]
    F = int(D * sz["mlp_ratio"])
    C = sz["in_channels"]
    co = 2 * C  # the noise and the variance (learn_sigma, as published)
    out = {"patch/w": ((p, p, C, D), "w"), "patch/b": ((D,), "b"),
           "y_table": ((sz["num_classes"] + 1, D), "table")}

    def dense(path, din, dout, lead=()):
        out[path + "/w"] = (lead + (din, dout), "w")
        out[path + "/b"] = (lead + (dout,), "b")

    dense("t_mlp1", sz["frequency_embedding_size"], D)
    dense("t_mlp2", D, D)
    dense("blocks/ada", D, 6 * D, (L,))
    dense("blocks/qkv", D, 3 * D, (L,))
    dense("blocks/proj", D, D, (L,))
    dense("blocks/fc1", D, F, (L,))
    dense("blocks/fc2", F, D, (L,))
    dense("final/ada", D, 2 * D)
    dense("final/linear", D, p * p * co)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        parts = path.split("/")
        for q in parts[:-1]:
            node = node.setdefault(q, {})
        node[parts[-1]] = v
    return tree


def _fan_in(path: str, shape: tuple) -> int:
    return math.prod(shape[:-1]) if path == "patch/w" else shape[-2]


@functools.lru_cache(maxsize=None)
def _maker(sz_items: tuple, dtype_name: str):
    sz = dict(sz_items)
    lay = sorted(layout(sz).items())
    dtype = jnp.dtype(dtype_name)

    def make(seed):
        key = jax.random.PRNGKey(0)
        key = jax.random.fold_in(jax.random.fold_in(key, seed[0]), seed[1])
        flat = {}
        for i, (path, (shape, kind)) in enumerate(lay):
            v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "w":
                v = v / math.sqrt(_fan_in(path, shape))
            elif kind == "b":
                v = 0.1 * v
            flat[path] = v.astype(dtype)
        return _nest(flat)
    return jax.jit(make)


def make_params(sz: dict, seed: int, dtype="float32"):
    """DiT's weights from ``seed``, made on the device in one jitted
    call, one leaf at a time.  Weight matrices (the adaLN and final
    layers' too) are fan-in scaled standard normals, so each layer keeps
    unit scale and the predicted noise is of order one; biases are
    0.1 N(0, 1); the label table N(0, 1).  ``seed`` may exceed 32
    bits."""
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in sz.items()))
    seed = int(seed) % (1 << 62)
    parts = jnp.asarray([seed >> 31, seed & 0x7FFFFFFF], jnp.uint32)
    return _maker(items, jnp.dtype(dtype).name)(parts)


# -- forward -----------------------------------------------------------

def _sincos_1d(dim: int, pos: np.ndarray) -> np.ndarray:
    omega = 1.0 / 10000 ** (np.arange(dim // 2, dtype=np.float64)
                            / (dim / 2.0))
    out = np.einsum("m,d->md", pos.reshape(-1), omega)
    return np.concatenate([np.sin(out), np.cos(out)], axis=1)


def pos_embed(dim: int, grid: int) -> np.ndarray:
    """``get_2d_sincos_pos_embed(dim, grid)``, (grid*grid, dim)."""
    g = np.stack(np.meshgrid(np.arange(grid, dtype=np.float32),
                             np.arange(grid, dtype=np.float32)), axis=0)
    return np.concatenate([_sincos_1d(dim // 2, g[0]),
                           _sincos_1d(dim // 2, g[1])], axis=1)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _layer_norm(x):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None, :]) + shift[:, None, :]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def _attention(x, p, heads):
    B, N, D = x.shape
    hd = D // heads
    qkv = (x @ p["qkv"]["w"] + p["qkv"]["b"]).reshape(B, N, 3, heads, hd)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    att = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd),
                         axis=-1)
    o = (att @ v).transpose(0, 2, 1, 3).reshape(B, N, D)
    return o @ p["proj"]["w"] + p["proj"]["b"]


def _mlp(x, p):
    h = _gelu_tanh(x @ p["fc1"]["w"] + p["fc1"]["b"])
    return h @ p["fc2"]["w"] + p["fc2"]["b"]


def _timestep_embedding(t, dim, dtype):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)],
                           axis=-1).astype(dtype)


def forward(sz: dict, params, x, t):
    """Predicted noise for latents ``x`` (B, H, W, C) at per-row
    timesteps ``t`` (B,), computed in the dtype of ``params``."""
    dtype = jax.tree.leaves(params)[0].dtype
    x = x.astype(dtype)
    B, H, W, C = x.shape
    p = sz["patch_size"]
    h = jax.lax.conv_general_dilated(
        x, params["patch"]["w"], (p, p), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    gh, gw, D = h.shape[1:]
    h = h.reshape(B, gh * gw, D) + params["patch"]["b"]
    h = h + jnp.asarray(pos_embed(D, gh), dtype)
    temb = _timestep_embedding(t, sz["frequency_embedding_size"], dtype)
    temb = _silu(temb @ params["t_mlp1"]["w"] + params["t_mlp1"]["b"])
    temb = temb @ params["t_mlp2"]["w"] + params["t_mlp2"]["b"]
    c = _silu(temb + params["y_table"][sz["num_classes"]][None])
    blocks = params["blocks"]
    for i in range(sz["depth"]):
        blk = jax.tree.map(lambda a: a[i], blocks)
        mod = c @ blk["ada"]["w"] + blk["ada"]["b"]
        (s_msa, sc_msa, g_msa,
         s_mlp, sc_mlp, g_mlp) = (mod[:, j * D:(j + 1) * D] for j in range(6))
        h = h + g_msa[:, None, :] * _attention(
            _modulate(_layer_norm(h), s_msa, sc_msa), blk, sz["num_heads"])
        h = h + g_mlp[:, None, :] * _mlp(
            _modulate(_layer_norm(h), s_mlp, sc_mlp), blk)
    fin = params["final"]
    mod = c @ fin["ada"]["w"] + fin["ada"]["b"]
    shift, scale = mod[:, :D], mod[:, D:]
    h = _modulate(_layer_norm(h), shift, scale)
    h = h @ fin["linear"]["w"] + fin["linear"]["b"]
    co = h.shape[-1] // (p * p)
    h = h.reshape(B, gh, gw, p, p, co)
    img = jnp.einsum("nhwpqc->nhpwqc", h).reshape(B, H, W, co)
    return img[..., :C]
