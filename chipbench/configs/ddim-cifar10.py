"""Plain reference of the DDPM/DDIM CIFAR-10 U-Net, and its seeded weights.

This file stands beside ``ddim-cifar10.json`` and belongs to the
benchmark: it imports nothing of the system under test.  It follows the
published architecture (Ho et al. 2020, arXiv:2006.11239 App. B; the
``ermongroup/ddim`` CIFAR-10 model) as the system lays it out: NHWC,
GroupNorm then SiLU before every 3x3 convolution of a residual block, a
sinusoidal timestep embedding through a two-layer MLP, single-head
self-attention after the residual blocks at resolution 16, nearest
upsampling.  Departures from the published model, shared with the
system: no dropout (sampling only) and no bias on convolutions, dense
layers or attention projections (GroupNorm keeps its shift).

``make_params`` builds the weights from a seed on the device in one
jitted call; the harness hands one copy to the system and the
reference makes its own.  ``forward`` is straightforward ``jax.numpy``
in one dtype: float32 under ``jax.default_matmul_precision("highest")``
for the reference, bfloat16 throughout for the control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

GN_EPS = 1e-6


def _groups(num_groups: int, c: int) -> int:
    g = min(num_groups, c)
    while c % g:
        g -= 1
    return g


def layout(sz: dict) -> dict:
    """The weight tree as ``{path: (shape, kind)}``; ``kind`` is
    ``conv``/``dense`` (fan-in scaled normal), ``gn_s`` or ``gn_b``."""
    ch = sz["base_channels"]
    temb = 4 * ch
    out = {}

    def conv(path, kh, cin, cout):
        out[path] = ((kh, kh, cin, cout), "conv")

    def res(path, cin, cout):
        out[path + "/gn1_s"] = ((cin,), "gn_s")
        out[path + "/gn1_b"] = ((cin,), "gn_b")
        conv(path + "/conv1", 3, cin, cout)
        out[path + "/temb"] = ((temb, cout), "dense")
        out[path + "/gn2_s"] = ((cout,), "gn_s")
        out[path + "/gn2_b"] = ((cout,), "gn_b")
        conv(path + "/conv2", 3, cout, cout)
        if cin != cout:
            conv(path + "/skip", 1, cin, cout)

    def attn(path, c):
        out[path + "/gn_s"] = ((c,), "gn_s")
        out[path + "/gn_b"] = ((c,), "gn_b")
        for w in ("wq", "wk", "wv", "wo"):
            out[f"{path}/{w}"] = ((c, c), "dense")

    out["temb1"] = ((ch, temb), "dense")
    out["temb2"] = ((temb, temb), "dense")
    conv("conv_in", 3, sz["in_channels"], ch)
    res_now, cin = sz["image_size"], ch
    chans = [(cin, res_now)]
    mults = sz["channel_mults"]
    for li, m in enumerate(mults):
        cout = ch * m
        for bi in range(sz["num_res_blocks"]):
            res(f"downs/{li}/res/{bi}/res", cin, cout)
            if res_now in sz["attn_resolutions"]:
                attn(f"downs/{li}/res/{bi}/attn", cout)
            cin = cout
            chans.append((cin, res_now))
        if li != len(mults) - 1:
            conv(f"downs/{li}/down", 3, cin, cin)
            res_now //= 2
            chans.append((cin, res_now))
    res("mid1", cin, cin)
    attn("mid_attn", cin)
    res("mid2", cin, cin)
    for ui, li in enumerate(reversed(range(len(mults)))):
        cout = ch * mults[li]
        for bi in range(sz["num_res_blocks"] + 1):
            skip_c, skip_res = chans.pop()
            res(f"ups/{ui}/res/{bi}/res", cin + skip_c, cout)
            if skip_res in sz["attn_resolutions"]:
                attn(f"ups/{ui}/res/{bi}/attn", cout)
            cin = cout
        if li != 0:
            conv(f"ups/{ui}/up", 3, cin, cin)
    out["gn_out_s"] = ((ch,), "gn_s")
    out["gn_out_b"] = ((ch,), "gn_b")
    conv("conv_out", 3, ch, sz["in_channels"])
    return out


def _nest(flat: dict) -> dict:
    """``{"a/0/b": v}`` -> ``{"a": [{"b": v}]}`` (digit keys are list
    positions), the tree the system's U-Net indexes."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    def fix(node):
        if not isinstance(node, dict):
            return node
        node = {k: fix(v) for k, v in node.items()}
        if node and all(k.isdigit() for k in node):
            return [node[str(i)] for i in range(len(node))]
        return node
    return fix(tree)


@functools.lru_cache(maxsize=None)
def _maker(sz_items: tuple, dtype_name: str):
    sz = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in sz_items}
    lay = layout(sz)
    dtype = jnp.dtype(dtype_name)

    def make(seed):
        key = jax.random.PRNGKey(0)
        key = jax.random.fold_in(jax.random.fold_in(key, seed[0]), seed[1])
        leaves = sorted(lay.items())
        sizes = [math.prod(shape) for _, (shape, _) in leaves]
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        flat, off = {}, 0
        for (path, (shape, kind)), n in zip(leaves, sizes):
            v = z[off:off + n].reshape(shape)
            off += n
            if kind == "gn_s":
                v = 1.0 + 0.1 * v
            elif kind == "gn_b":
                v = 0.1 * v
            else:
                v = v / math.sqrt(math.prod(shape[:-1]))
            flat[path] = v.astype(dtype)
        return _nest(flat)
    return jax.jit(make)


def make_params(sz: dict, seed: int, dtype="float32"):
    """The U-Net's weights from ``seed``, made on the device in one
    jitted call.  Every weight matrix and kernel is a fan-in scaled
    standard normal (so each layer keeps unit scale and the predicted
    noise is of order one), GroupNorm scales are 1 + 0.1 N(0, 1) and
    shifts 0.1 N(0, 1).  ``seed`` may exceed 32 bits."""
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in sz.items()))
    seed = int(seed) % (1 << 62)
    parts = jnp.asarray([seed >> 31, seed & 0x7FFFFFFF], jnp.uint32)
    return _maker(items, jnp.dtype(dtype).name)(parts)


# -- forward -----------------------------------------------------------

def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, s, b, num_groups):
    B, H, W, C = x.shape
    G = _groups(num_groups, C)
    xg = x.reshape(B, H, W, G, C // G)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + GN_EPS)
    return xg.reshape(B, H, W, C) * s + b


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _res_block(sz, p, x, temb):
    h = _conv(_silu(_group_norm(x, p["gn1_s"], p["gn1_b"],
                                sz["num_groups"])), p["conv1"])
    h = h + (_silu(temb) @ p["temb"])[:, None, None, :]
    h = _conv(_silu(_group_norm(h, p["gn2_s"], p["gn2_b"],
                                sz["num_groups"])), p["conv2"])
    skip = _conv(x, p["skip"]) if "skip" in p else x
    return skip + h


def _attn_block(sz, p, x):
    B, H, W, C = x.shape
    h = _group_norm(x, p["gn_s"], p["gn_b"], sz["num_groups"])
    flat = h.reshape(B, H * W, C)
    q, k, v = flat @ p["wq"], flat @ p["wk"], flat @ p["wv"]
    logits = jnp.einsum("bqc,bkc->bqk", q, k) / math.sqrt(C)
    out = jnp.einsum("bqk,bkc->bqc", jax.nn.softmax(logits, axis=-1), v)
    return x + (out @ p["wo"]).reshape(B, H, W, C)


def _temb(t, dim, dtype):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)],
                           axis=-1).astype(dtype)


def forward(sz: dict, params, x, t):
    """Predicted noise for images ``x`` (B, H, W, C) at per-row
    timesteps ``t`` (B,), computed in the dtype of ``params``."""
    dtype = jax.tree.leaves(params)[0].dtype
    x = x.astype(dtype)
    temb = _temb(t, sz["base_channels"], dtype)
    temb = _silu(temb @ params["temb1"]) @ params["temb2"]
    h = _conv(x, params["conv_in"])
    skips = [h]
    for level in params["downs"]:
        for blk in level["res"]:
            h = _res_block(sz, blk["res"], h, temb)
            if "attn" in blk:
                h = _attn_block(sz, blk["attn"], h)
            skips.append(h)
        if "down" in level:
            h = _conv(h, level["down"], stride=2)
            skips.append(h)
    h = _res_block(sz, params["mid1"], h, temb)
    h = _attn_block(sz, params["mid_attn"], h)
    h = _res_block(sz, params["mid2"], h, temb)
    for level in params["ups"]:
        for blk in level["res"]:
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = _res_block(sz, blk["res"], h, temb)
            if "attn" in blk:
                h = _attn_block(sz, blk["attn"], h)
        if "up" in level:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = _conv(h, level["up"])
    h = _silu(_group_norm(h, params["gn_out_s"], params["gn_out_b"],
                          sz["num_groups"]))
    return _conv(h, params["conv_out"])
