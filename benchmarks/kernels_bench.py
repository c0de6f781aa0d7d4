"""Kernel micro-bench: XLA ref-path timings on CPU (the Pallas variants
target TPU; interpret-mode timings are not meaningful performance)."""

import time

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.models.layers import chunked_attention
from repro.models.ssm import ssd_chunked


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def run(csv_rows):
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (8, 128, 512))
    s = jnp.ones((512,))
    csv_rows.append(("kern_rmsnorm_ref", _time(jax.jit(rmsnorm_ref), x, s),
                     "8x128x512"))

    q = jax.random.normal(ks[1], (1, 512, 8, 64))
    k = jax.random.normal(ks[2], (1, 512, 2, 64))
    v = jax.random.normal(ks[3], (1, 512, 2, 64))
    f_naive = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    f_chunk = jax.jit(lambda q, k, v: chunked_attention(
        q, k, v, causal=True, q_chunk=128, kv_chunk=128))
    csv_rows.append(("kern_attn_naive", _time(f_naive, q, k, v), "S=512"))
    csv_rows.append(("kern_attn_chunked", _time(f_chunk, q, k, v), "S=512"))

    qd = jax.random.normal(ks[4], (4, 1, 8, 64))
    kc = jax.random.normal(ks[5], (4, 2048, 2, 64))
    vc = jax.random.normal(ks[6], (4, 2048, 2, 64))
    cur = jnp.full((4,), 2048, jnp.int32)
    f_dec = jax.jit(lambda q, k, v, c: decode_attention_ref(q, k, v, c))
    csv_rows.append(("kern_decode_attn", _time(f_dec, qd, kc, vc, cur),
                     "S=2048"))

    B, S, H, P, N = 2, 512, 4, 32, 16
    xs = jax.random.normal(ks[7], (B, S, H, P))
    a = -jnp.abs(jax.random.normal(ks[0], (B, S, H))) * 0.1
    bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
    cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
    h0 = jnp.zeros((B, H, P, N))
    f_ssd = jax.jit(lambda *t: ssd_chunked(*t, chunk=128))
    csv_rows.append(("kern_ssd_chunked", _time(f_ssd, xs, a, bm, cm, h0),
                     "S=512"))

    # batched DDIM step at the served session's power-of-two batch
    # buckets (SMOKE U-Net, gather->step->scatter pool program) — the
    # per-step cost Fig. 1a's delay model is fit over
    from repro.configs.ddim_cifar10 import SMOKE
    from repro.diffusion import unet
    from repro.diffusion.executor import BatchDenoisingExecutor
    from repro.models.params import init_params
    params = init_params(unet.schema(SMOKE), jax.random.PRNGKey(1))
    ex = BatchDenoisingExecutor(SMOKE, params)
    curve = ex.measure_delay_curve(ks[3], batch_sizes=(2, 4, 8),
                                   reps=3)
    compile_s = sum(s for _, s in ex.last_compile_log)
    for X, best in curve:
        csv_rows.append((f"kern_ddim_step_b{X}", best * 1e6,
                         f"bucketed pool step, SMOKE unet, "
                         f"compile_total={compile_s:.2f}s separate"))
