"""DDPM++-style U-Net for CIFAR-scale image diffusion, in pure JAX.

Supports *per-sample* timestep conditioning — the property batch denoising
relies on: one batched forward can mix denoising tasks of different
services at different step indices (STACKING's batches are exactly such
mixtures).

Layout: NHWC.  GroupNorm+SiLU chains are the compute hot spot the
kernels/groupnorm_silu Pallas kernel targets on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.ddim_cifar10 import UNetConfig
from repro.models.params import P


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def conv2d(x, w, b=None, stride=1):
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if b is not None:
        out = out + b
    return out


def group_norm(x, scale, bias, num_groups: int, eps: float = 1e-6):
    B, H, W, C = x.shape
    G = min(num_groups, C)
    while C % G:
        G -= 1
    xg = x.reshape(B, H, W, G, C // G).astype(jnp.float32)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    out = xg.reshape(B, H, W, C) * scale + bias
    return out.astype(x.dtype)


def gn_silu_xla(x, scale, bias, num_groups: int):
    """GroupNorm+SiLU through XLA: the CPU path and the reference."""
    return jax.nn.silu(group_norm(x, scale, bias, num_groups))


def gn_silu(x, scale, bias, num_groups: int):
    """Fused GroupNorm+SiLU; dispatches to the Pallas kernel on TPU (or
    under REPRO_FORCE_PALLAS=1) — the U-Net's HBM hot spot."""
    from repro.kernels import use_pallas
    mode = use_pallas()
    if mode in ("tpu", "interpret"):
        from repro.kernels.groupnorm_silu.kernel import groupnorm_silu_pallas
        return groupnorm_silu_pallas(x, scale, bias, num_groups,
                                     interpret=(mode == "interpret"))
    return gn_silu_xla(x, scale, bias, num_groups)


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    """t: (B,) float timesteps -> (B, dim) sinusoidal embedding."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def _conv_p(kh, kw, cin, cout, scale=None):
    return P((kh, kw, cin, cout), (None, None, None, None), scale=scale)


def _res_block_schema(cin, cout, temb_dim):
    return {
        "gn1_s": P((cin,), (None,), init="ones"),
        "gn1_b": P((cin,), (None,), init="zeros"),
        "conv1": _conv_p(3, 3, cin, cout),
        "temb": P((temb_dim, cout), (None, None)),
        "gn2_s": P((cout,), (None,), init="ones"),
        "gn2_b": P((cout,), (None,), init="zeros"),
        "conv2": _conv_p(3, 3, cout, cout, scale=0.05),
        **({"skip": _conv_p(1, 1, cin, cout)} if cin != cout else {}),
    }


def _attn_schema(ch):
    return {
        "gn_s": P((ch,), (None,), init="ones"),
        "gn_b": P((ch,), (None,), init="zeros"),
        "wq": P((ch, ch), (None, None)),
        "wk": P((ch, ch), (None, None)),
        "wv": P((ch, ch), (None, None)),
        "wo": P((ch, ch), (None, None), scale=0.05),
    }


def schema(cfg: UNetConfig):
    ch = cfg.base_channels
    temb = 4 * ch
    s = {
        "temb1": P((ch, temb), (None, None)),
        "temb2": P((temb, temb), (None, None)),
        "conv_in": _conv_p(3, 3, cfg.in_channels, ch),
        "gn_out_s": P((ch,), (None,), init="ones"),
        "gn_out_b": P((ch,), (None,), init="zeros"),
        "conv_out": _conv_p(3, 3, ch, cfg.in_channels, scale=1e-10),
    }
    res = cfg.image_size
    cin = ch
    downs, chans = [], [(cin, res)]
    for li, mult in enumerate(cfg.channel_mults):
        cout = ch * mult
        level = {"res": []}
        for bi in range(cfg.num_res_blocks):
            blk = {"res": _res_block_schema(cin, cout, temb)}
            if res in cfg.attn_resolutions:
                blk["attn"] = _attn_schema(cout)
            level["res"].append(blk)
            cin = cout
            chans.append((cin, res))
        if li != len(cfg.channel_mults) - 1:
            level["down"] = _conv_p(3, 3, cin, cin)
            res //= 2
            chans.append((cin, res))
        downs.append(level)
    s["downs"] = downs
    s["mid1"] = _res_block_schema(cin, cin, temb)
    s["mid_attn"] = _attn_schema(cin)
    s["mid2"] = _res_block_schema(cin, cin, temb)

    ups = []
    for li, mult in reversed(list(enumerate(cfg.channel_mults))):
        cout = ch * mult
        level = {"res": []}
        for bi in range(cfg.num_res_blocks + 1):
            skip_c, skip_res = chans.pop()
            blk = {"res": _res_block_schema(cin + skip_c, cout, temb)}
            if skip_res in cfg.attn_resolutions:
                blk["attn"] = _attn_schema(cout)
            level["res"].append(blk)
            cin = cout
        if li != 0:
            level["up"] = _conv_p(3, 3, cin, cin)
            res *= 2
        ups.append(level)
    s["ups"] = ups
    return s


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _res_block(cfg, p, x, temb, norm):
    h = norm(x, p["gn1_s"], p["gn1_b"], cfg.num_groups)
    h = conv2d(h, p["conv1"])
    h = h + (jax.nn.silu(temb) @ p["temb"])[:, None, None, :]
    h = norm(h, p["gn2_s"], p["gn2_b"], cfg.num_groups)
    h = conv2d(h, p["conv2"])
    skip = conv2d(x, p["skip"]) if "skip" in p else x
    return skip + h


def _attn_block(cfg, p, x):
    B, H, W, C = x.shape
    h = group_norm(x, p["gn_s"], p["gn_b"], cfg.num_groups)
    flat = h.reshape(B, H * W, C)
    q, k, v = flat @ p["wq"], flat @ p["wk"], flat @ p["wv"]
    attn = jax.nn.softmax(
        jnp.einsum("bqc,bkc->bqk", q, k) / jnp.sqrt(C), axis=-1)
    out = jnp.einsum("bqk,bkc->bqc", attn, v) @ p["wo"]
    return x + out.reshape(B, H, W, C)


def forward(cfg: UNetConfig, params, x, t, norm=gn_silu):
    """x: (B, H, W, C) noisy images; t: (B,) per-sample timesteps.
    Returns predicted noise eps, same shape as x.  ``norm`` is the
    GroupNorm+SiLU implementation (``reference_forward`` passes the XLA
    one)."""
    temb = timestep_embedding(t, cfg.base_channels)
    temb = jax.nn.silu(temb @ params["temb1"]) @ params["temb2"]

    h = conv2d(x, params["conv_in"])
    skips = [h]
    for level in params["downs"]:
        for blk in level["res"]:
            h = _res_block(cfg, blk["res"], h, temb, norm)
            if "attn" in blk:
                h = _attn_block(cfg, blk["attn"], h)
            skips.append(h)
        if "down" in level:
            h = conv2d(h, level["down"], stride=2)
            skips.append(h)

    h = _res_block(cfg, params["mid1"], h, temb, norm)
    h = _attn_block(cfg, params["mid_attn"], h)
    h = _res_block(cfg, params["mid2"], h, temb, norm)

    for level in params["ups"]:
        for blk in level["res"]:
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = _res_block(cfg, blk["res"], h, temb, norm)
            if "attn" in blk:
                h = _attn_block(cfg, blk["attn"], h)
        if "up" in level:
            B, H, W, C = h.shape
            h = jax.image.resize(h, (B, 2 * H, 2 * W, C), "nearest")
            h = conv2d(h, level["up"])

    h = norm(h, params["gn_out_s"], params["gn_out_b"], cfg.num_groups)
    return conv2d(h, params["conv_out"])


def reference_forward(cfg: UNetConfig, params, x, t):
    """Plain float32 reference for ``forward``: the XLA GroupNorm (no
    Pallas kernel) and every matmul and convolution at full float32
    precision, whatever the device's default matmul precision is."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(forward, cfg, norm=gn_silu_xla))(
            params, x, t)
