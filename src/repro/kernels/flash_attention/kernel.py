"""Flash attention (forward) Pallas TPU kernel — GQA, causal, windowed.

Tiling (DESIGN.md §3: HBM->VMEM->MXU):
  grid = (B, H, Sq/bq, Skv/bk); the kv axis is innermost, so on TPU the
  grid executes kv blocks sequentially per (b, h, iq) and the VMEM
  scratch accumulators (m, l, acc) implement the online softmax across
  those iterations.  Block shapes are MXU-aligned: bq x D and bk x D
  tiles with D padded to >= 128 by the caller (all assigned archs have
  head_dim in {64, 128, 192, 384}; 64 still maps onto the MXU via lane
  packing — we keep D whole in VMEM).

  Causal masking skips *entire* kv blocks past the diagonal with
  @pl.when (no wasted MXU work — this is the "causal chunk skip" the
  pure-XLA chunked_attention path lacks; see EXPERIMENTS.md §Perf).

``mha_whole_pallas`` is a separate kernel for short, non-causal
sequences (the DiT's 256 tokens): one grid step a row, q, k and v whole
in VMEM as [S, H*Dh] with the heads side by side on the lanes, so the
caller feeds it the qkv projection's output with no relayout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, scale: float, causal: bool, window: int,
            q_offset: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + iq * bq
    k_start = ik * bk

    # whole-block causal/window skip
    needed = jnp.bool_(True)
    if causal:
        needed &= k_start <= q_start + bq - 1
    if window:
        needed &= (k_start + bk - 1) >= (q_start - window + 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal or window:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            mask = jnp.ones((bq, bk), jnp.bool_)
            if causal:
                mask &= qpos >= kpos
            if window:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int = 0, bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / (D ** 0.5)
    q_offset = Skv - Sq                      # align sequence ends

    # layout: (B, H, S, D) blocks
    qt = jnp.swapaxes(q, 1, 2)               # (B, H, Sq, D)
    kt = jnp.swapaxes(k, 1, 2)               # (B, KV, Skv, D)
    vt = jnp.swapaxes(v, 1, 2)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window,
                          q_offset=q_offset, nk=nk),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)


def _mha_whole_kernel(q_ref, k_ref, v_ref, o_ref, *, num_heads: int,
                      head_dim: int, scale: float):
    for h in range(num_heads):
        lanes = pl.ds(h * head_dim, head_dim)
        q = q_ref[0, 0, :, lanes].astype(jnp.float32)     # (S, Dh)
        k = k_ref[0, 0, :, lanes].astype(jnp.float32)
        v = v_ref[0, 0, :, lanes].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (S, S)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        o = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, 0, :, lanes] = (o / p.sum(axis=-1, keepdims=True)
                                 ).astype(o_ref.dtype)


def mha_whole_pallas(q, k, v, *, num_heads: int, interpret: bool = False):
    """Non-causal multi-head attention over whole, short sequences.

    q, k, v: (B, 1, S, H*Dh), heads side by side on the lanes, as a
    fused qkv projection leaves them -> (B, 1, S, H*Dh).  The grid runs
    over rows; each step holds one row's q, k and v whole in VMEM and
    takes the heads one after another as static lane slices, with one
    softmax over all S keys: no tiling and no online rescale.  The
    dispatcher, ``ops.mha_whole``, checks that a row fits."""
    B, one, S, W = q.shape
    assert one == 1 and k.shape == v.shape == q.shape
    assert W % num_heads == 0
    head_dim = W // num_heads
    block = pl.BlockSpec((1, 1, S, W), lambda b: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_mha_whole_kernel, num_heads=num_heads,
                          head_dim=head_dim, scale=1.0 / (head_dim ** 0.5)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(B,),
        in_specs=[block, block, block],
        out_specs=block,
        interpret=interpret,
    )(q, k, v)
