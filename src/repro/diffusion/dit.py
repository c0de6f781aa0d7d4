"""DiT: an adaLN-Zero transformer denoiser over latent patches, in JAX.

Peebles & Xie 2023, "Scalable Diffusion Models with Transformers"
(arXiv:2212.09748, §3.2), as ``facebookresearch/DiT`` ``models.py``
builds it.  A latent ``(B, H, W, C)`` is cut into p x p patches, each
embedded linearly, plus a fixed 2-D sin-cos position embedding.  Every
sample carries its own conditioning c = t_emb + y_emb, and every block
modulates its two LayerNorms with it (adaLN-Zero):

    (shift, scale, gate) x 2 = Linear(SiLU(c))
    x += gate_msa * Attn(LN(x) * (1 + scale_msa) + shift_msa)
    x += gate_mlp * MLP(LN(x) * (1 + scale_mlp) + shift_mlp)

with LayerNorm without affine (eps 1e-6) and a tanh-GELU MLP.  The final
layer is an adaLN pair and a linear to p*p*out_channels, unpatchified
back to ``(B, H, W, out_channels)``.  Per-sample t is what batch
denoising rests on: one forward advances rows at different timesteps.

The blocks' weights are stacked on a leading ``depth`` axis and the
forward runs them with ``lax.scan``, fully unrolled: on one TPU v5e the
rolled loop made XLA hoist a bfloat16 copy of every block's weights out
of the loop, 1.35 GB written and read again each step, and took 13.1 /
120.3 ms a step at bucket 2 / 32 against 8.2 / 112.0 ms unrolled, for
2-3.5 s to compile a bucket program against 14-22 s.  Attention is
``kernels.flash_attention.ops.mha_whole`` on q, k and v as the one qkv
projection leaves them, ``(B, 1, N, D)`` with the heads side by side on
the lanes: on a TPU one whole-sequence Pallas call a block, fed with no
relayout copy.  A row has to fit VMEM (256 tokens of 1152 do);
``attention_path`` names the path and refuses a longer sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.ddim_cifar10 import UNetConfig
from repro.diffusion.unet import timestep_embedding
from repro.kernels.flash_attention.ops import mha_whole, mha_whole_path
from repro.models.params import P

LN_EPS = 1e-6


def out_channels(cfg: UNetConfig) -> int:
    """The noise and the variance (``learn_sigma``), as published."""
    return 2 * cfg.in_channels


def mlp_width(cfg: UNetConfig) -> int:
    return int(cfg.hidden_size * cfg.mlp_ratio)


def _dense(din, dout, lead=()):
    return {"w": P(lead + (din, dout), (None,) * (len(lead) + 2)),
            "b": P(lead + (dout,), (None,) * (len(lead) + 1), init="zeros")}


def schema(cfg: UNetConfig):
    """The weight tree: ``blocks`` holds each block weight stacked over
    ``depth``; ``patch/w`` is a p x p x C x D (HWIO) kernel."""
    D, p, L = cfg.hidden_size, cfg.patch_size, (cfg.depth,)
    return {
        "patch": {"w": P((p, p, cfg.in_channels, D), (None,) * 4,
                         scale=(p * p * cfg.in_channels) ** -0.5),
                  "b": P((D,), (None,), init="zeros")},
        "t_mlp1": _dense(cfg.frequency_embedding_size, D),
        "t_mlp2": _dense(D, D),
        "y_table": P((cfg.num_classes + 1, D), (None, None), init="embed"),
        "blocks": {
            "ada": _dense(D, 6 * D, L),
            "qkv": _dense(D, 3 * D, L),
            "proj": _dense(D, D, L),
            "fc1": _dense(D, mlp_width(cfg), L),
            "fc2": _dense(mlp_width(cfg), D, L),
        },
        "final": {
            "ada": _dense(D, 2 * D),
            "linear": _dense(D, p * p * out_channels(cfg)),
        },
    }


@functools.lru_cache(maxsize=None)
def pos_embed(hidden: int, grid: int) -> np.ndarray:
    """DiT's fixed 2-D sin-cos position embedding, ``(grid*grid,
    hidden)``: half the width encodes the column, half the row, each
    as ``[sin, cos]`` at 10000^(-i / (hidden/4))."""
    def one_d(dim, pos):
        omega = 1.0 / 10000 ** (np.arange(dim // 2, dtype=np.float64)
                                / (dim / 2.0))
        out = np.outer(pos.reshape(-1), omega)
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)
    col, row = np.meshgrid(np.arange(grid, dtype=np.float64),
                           np.arange(grid, dtype=np.float64))
    return np.concatenate([one_d(hidden // 2, col), one_d(hidden // 2, row)],
                          axis=1).astype(np.float32)


def attention_path(cfg: UNetConfig) -> str:
    """The attention every forward of ``cfg`` runs, ``"whole"`` or
    ``"xla"`` (``ops.mha_whole_path``, which ``mha_whole`` itself
    follows)."""
    tokens = (cfg.image_size // cfg.patch_size) ** 2
    return mha_whole_path(tokens, cfg.hidden_size)


def _linear(p, x):
    return x @ p["w"] + p["b"]


def _layer_norm(x):
    mu = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mu).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None]) + shift[:, None]


def _block(cfg: UNetConfig, x, silu_c, p):
    B, N, D = x.shape
    with jax.named_scope("dit.block"):
        (s_msa, sc_msa, g_msa,
         s_mlp, sc_mlp, g_mlp) = jnp.split(_linear(p["ada"], silu_c), 6, -1)
        with jax.named_scope("dit.attn"):
            h = _modulate(_layer_norm(x), s_msa, sc_msa)
            q, k, v = jnp.split(_linear(p["qkv"], h), 3, -1)
            q, k, v = (a.reshape(B, 1, N, D) for a in (q, k, v))
            o = mha_whole(q, k, v, num_heads=cfg.num_heads).reshape(B, N, D)
            x = x + g_msa[:, None] * _linear(p["proj"], o)
        with jax.named_scope("dit.mlp"):
            h = _modulate(_layer_norm(x), s_mlp, sc_mlp)
            h = jax.nn.gelu(_linear(p["fc1"], h), approximate=True)
            x = x + g_mlp[:, None] * _linear(p["fc2"], h)
    return x


def forward(cfg: UNetConfig, params, x, t):
    """x: (B, H, W, C) noisy latents; t: (B,) per-sample timesteps.
    Returns the predicted noise, the first ``in_channels`` of the
    network's outputs, shape of x.  Every row is conditioned on the null
    label, row ``cfg.num_classes`` of the label table (unconditional)."""
    B, Hs, Ws, C = x.shape
    p = cfg.patch_size
    gh, gw = Hs // p, Ws // p
    with jax.named_scope("dit.embed"):
        tok = x.reshape(B, gh, p, gw, p, C).transpose(0, 1, 3, 2, 4, 5)
        tok = tok.reshape(B, gh * gw, p * p * C)
        w = params["patch"]["w"].reshape(p * p * C, -1)
        h = tok @ w + params["patch"]["b"] + pos_embed(cfg.hidden_size, gh)
        temb = timestep_embedding(t, cfg.frequency_embedding_size)
        temb = _linear(params["t_mlp2"],
                       jax.nn.silu(_linear(params["t_mlp1"], temb)))
        c = temb + params["y_table"][cfg.num_classes]
        silu_c = jax.nn.silu(c)

    def body(h, blk):
        return _block(cfg, h, silu_c, blk), None

    h, _ = jax.lax.scan(body, h, params["blocks"], unroll=True)
    with jax.named_scope("dit.final"):
        shift, scale = jnp.split(_linear(params["final"]["ada"], silu_c),
                                 2, -1)
        h = _linear(params["final"]["linear"],
                    _modulate(_layer_norm(h), shift, scale))
        co = out_channels(cfg)
        h = h.reshape(B, gh, gw, p, p, co).transpose(0, 1, 3, 2, 4, 5)
        return h.reshape(B, Hs, Ws, co)[..., :C]
