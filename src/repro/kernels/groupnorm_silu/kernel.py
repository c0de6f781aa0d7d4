"""Fused GroupNorm+SiLU Pallas TPU kernel — the diffusion U-Net hot spot.

The U-Net applies GN->SiLU->conv twice per residual block; unfused, each
GN materializes mean/var intermediates and a normalized tensor in HBM.
Fused: one VMEM pass per image computes group statistics and writes the
activated output directly.

Tiling: grid = (B,), block = one full image flattened to (H*W, C), so
channels sit on the lane axis and pixels on sublanes.  The kernel never
splits the lane axis: it reduces over rows to per-channel sums (1, C),
folds them into per-group sums with a (C, G) one-hot matmul and spreads
the group statistics back with its (G, C) transpose.  Those matmuls run
at ``Precision.HIGHEST`` (fp32 contraction on the MXU), so the result
matches ``groupnorm_silu_ref`` to float32 rounding.  Statistics are
two-pass (mean, then mean of squared deviations) like the reference.

At CIFAR scale the largest block, (32*32, 512) f32, is 2 MB, and the
kernel compiles for v5e within the default scoped VMEM at every width
of the published U-Net (tests/test_tpu_compile.py).  Larger resolutions
would tile H*W with a two-pass reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST


def _kernel(x_ref, s_ref, b_ref, o_ref, *, groups: int, eps: float):
    x = x_ref[0].astype(jnp.float32)                # (H*W, C)
    HW, C = x.shape
    cg = C // groups
    # one-hot channel->group maps, (C, G) and (G, C), from compares only
    c = jax.lax.broadcasted_iota(jnp.int32, (C, groups), 0)
    g = jax.lax.broadcasted_iota(jnp.int32, (C, groups), 1) * cg
    to_g = ((c >= g) & (c < g + cg)).astype(jnp.float32)
    c = jax.lax.broadcasted_iota(jnp.int32, (groups, C), 1)
    g = jax.lax.broadcasted_iota(jnp.int32, (groups, C), 0) * cg
    to_c = ((c >= g) & (c < g + cg)).astype(jnp.float32)
    inv_n = 1.0 / (HW * cg)

    def group_mean(v):                               # (H*W, C) -> (1, C)
        per_c = jnp.sum(v, axis=0, keepdims=True)
        per_g = jnp.dot(per_c, to_g, precision=_HI,
                        preferred_element_type=jnp.float32) * inv_n
        return jnp.dot(per_g, to_c, precision=_HI,
                       preferred_element_type=jnp.float32)

    xc = x - group_mean(x)
    inv_std = jax.lax.rsqrt(group_mean(xc * xc) + eps)
    out = xc * inv_std * s_ref[...] + b_ref[...]
    o_ref[0] = (out * jax.nn.sigmoid(out)).astype(o_ref.dtype)


def groupnorm_silu_pallas(x, scale, bias, num_groups: int,
                          eps: float = 1e-6, interpret: bool = False):
    B, H, W, C = x.shape
    G = min(num_groups, C)
    while C % G:
        G -= 1
    HW = H * W
    out = pl.pallas_call(
        functools.partial(_kernel, groups=G, eps=eps),
        out_shape=jax.ShapeDtypeStruct((B, HW, C), x.dtype),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
            pl.BlockSpec((1, C), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, HW, C), lambda b: (b, 0, 0)),
        interpret=interpret,
        name="groupnorm_silu",
    )(x.reshape(B, HW, C), scale.reshape(1, C).astype(jnp.float32),
      bias.reshape(1, C).astype(jnp.float32))
    return out.reshape(B, H, W, C)
