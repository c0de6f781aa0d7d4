"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True)
vs. the pure-jnp ref oracle."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import (flash_attention_pallas,
                                                  mha_whole_pallas)
from repro.kernels.flash_attention.ops import mha_whole_fits
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.groupnorm_silu.kernel import groupnorm_silu_pallas
from repro.kernels.groupnorm_silu.ref import groupnorm_silu_ref
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.layers import chunked_attention, decode_attention
from repro.models.ssm import ssd_chunked


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(4, 64), (3, 7, 96), (2, 5, 3, 128),
                                   (1, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype)
    got = rmsnorm_pallas(x, s, interpret=True, block_rows=4)
    want = rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,Sq,Skv,H,KV,D", [
    (1, 64, 64, 2, 2, 32),       # MHA
    (2, 64, 64, 4, 2, 64),       # GQA
    (1, 32, 128, 4, 1, 64),      # MQA, longer kv (prefill continuation)
    (1, 128, 128, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Skv, H, KV, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, bq=32, bk=32,
                                 interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [16, 48])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 bq=32, bk=32, interpret=True)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,Dh", [
    (2, 256, 16, 72),    # the DiT's own heads, 72 lanes wide
    (3, 64, 2, 64),
    (1, 128, 4, 32),
])
def test_mha_whole_matches_ref(B, S, H, Dh):
    """The whole-sequence kernel on q, k, v laid out [B, 1, S, H*Dh],
    against the oracle on the same values as [B, S, H, Dh]."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (B, 1, S, H * Dh)) for kk in ks)
    got = mha_whole_pallas(q, k, v, num_heads=H, interpret=True)
    assert got.shape == q.shape
    with jax.default_matmul_precision("highest"):
        want = attention_ref(*(a.reshape(B, S, H, Dh) for a in (q, k, v)),
                             causal=False)
    np.testing.assert_allclose(np.asarray(got).reshape(B, S, H, Dh),
                               np.asarray(want), atol=2e-5, rtol=2e-5)


def test_mha_whole_fits_by_row_size():
    """A row's q, k, v and output, double-buffered, plus one head's
    scores: 9.7 MB at the DiT's 256 tokens of 1152 fits the 16 MiB
    scoped VMEM, 38 MB at 1024 tokens (DiT-XL/2 at 512x512) does not."""
    assert mha_whole_fits(256, 1152)
    assert not mha_whole_fits(1024, 1152)
    assert not mha_whole_fits(256, 2048)


def test_chunked_attention_matches_ref_nondivisible_kv():
    """XLA-path attention with kv padding (vision cross-attn: 1601 toks)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 40, 4, 32))
    k = jax.random.normal(ks[1], (2, 101, 2, 32))
    v = jax.random.normal(ks[2], (2, 101, 2, 32))
    got = chunked_attention(q, k, v, causal=False, q_chunk=16, kv_chunk=32)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,KV,D,bs", [
    (2, 256, 4, 2, 64, 64),
    (1, 128, 8, 8, 32, 32),      # MHA
    (3, 512, 4, 1, 128, 128),    # MQA
])
@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, H, KV, D, bs, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    vc = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    cur = jnp.asarray(np.random.default_rng(0).integers(1, S + 1, B),
                      jnp.int32)
    got = decode_attention_pallas(q, kc, vc, cur, window=window, bs=bs,
                                  interpret=True)
    want = decode_attention_ref(q, kc, vc, cur, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # the jnp model path agrees too
    model = decode_attention(q.astype(jnp.float32),
                             kc.astype(jnp.float32),
                             vc.astype(jnp.float32), cur, window=window)
    np.testing.assert_allclose(np.asarray(model),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 64, 3, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 32, 1, 8, 8, 32),       # single chunk
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.2
    bm = jax.random.normal(ks[2], (B, S, N)) * 0.3
    cm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    h0 = jax.random.normal(ks[4], (B, H, P, N)) * 0.1
    yr, hr = ssd_scan_ref(x, a, bm, cm, h0)
    yp, hp = ssd_scan_pallas(x, a, bm, cm, h0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr),
                               atol=3e-5, rtol=3e-5)
    yc, hc = ssd_chunked(x, a, bm, cm, h0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yr),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hr),
                               atol=3e-5, rtol=3e-5)


def test_ssd_chunked_per_head_bc():
    """mLSTM uses per-head B/C (ndim-4 path of ssd_chunked)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, S, H, P, N = 2, 48, 2, 8, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.2
    bm = jax.random.normal(ks[2], (B, S, H, N)) * 0.3
    cm = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    h0 = jnp.zeros((B, H, P, N))
    # oracle: run ref per head with shared-BC shapes
    ys = []
    hs = []
    for h in range(H):
        yr, hr = ssd_scan_ref(x[:, :, h:h + 1], a[:, :, h:h + 1],
                              bm[:, :, h], cm[:, :, h], h0[:, h:h + 1])
        ys.append(yr)
        hs.append(hr)
    want_y = jnp.concatenate(ys, axis=2)
    want_h = jnp.concatenate(hs, axis=1)
    got_y, got_h = ssd_chunked(x, a, bm, cm, h0, chunk=16)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,H,W,C,G", [
    (2, 8, 8, 32, 8), (1, 16, 16, 24, 6), (3, 4, 4, 16, 16),
    # H*W not a multiple of 8 (sublane padding); published channel
    # widths with 12- and 4-channel groups
    (2, 5, 5, 384, 32), (1, 3, 7, 128, 32), (2, 4, 4, 384, 32),
    (1, 8, 8, 512, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_groupnorm_silu_sweep(B, H, W, C, G, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (B, H, W, C), dtype)
    s = jax.random.normal(ks[1], (C,), jnp.float32)
    b = jax.random.normal(ks[2], (C,), jnp.float32)
    got = groupnorm_silu_pallas(x, s, b, G, interpret=True)
    want = groupnorm_silu_ref(x, s, b, G)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_groupnorm_silu_offset_statistics():
    """Per-group statistics stay exact when groups sit at very different
    offsets and scales (a kernel mixing channels across groups, or
    losing precision in the one-hot group matmuls, fails here)."""
    B, H, W, C, G = 2, 4, 4, 128, 32
    x = jax.random.normal(jax.random.PRNGKey(7), (B, H, W, C))
    offs = jnp.repeat(jnp.arange(G, dtype=jnp.float32) * 100.0, C // G)
    gain = jnp.repeat(1.0 + jnp.arange(G, dtype=jnp.float32), C // G)
    x = x * gain + offs
    s = jnp.ones((C,))
    b = jnp.zeros((C,))
    got = groupnorm_silu_pallas(x, s, b, G, interpret=True)
    want = groupnorm_silu_ref(x, s, b, G)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_use_pallas_raises_on_backend_error(monkeypatch):
    """A backend that fails to initialize is an error, not a silent
    switch to the reference path."""
    from repro import kernels

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        kernels.use_pallas()


def test_force_pallas_applies_only_off_the_chip(monkeypatch):
    from repro import kernels

    class _Dev:
        def __init__(self, platform):
            self.platform = platform

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu")])
    assert kernels.use_pallas() == "tpu"
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu")])
    assert kernels.use_pallas() == "interpret"
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    assert kernels.use_pallas() == "ref"
