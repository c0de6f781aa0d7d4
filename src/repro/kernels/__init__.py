"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three files:
  kernel.py  -- pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ref.py     -- pure-jnp oracle (also the XLA path used on CPU / dry-run)
  ops.py     -- jit'd dispatch wrapper: pallas on TPU; off the chip
                interpret=True when forced via REPRO_FORCE_PALLAS=1,
                ref otherwise

Kernels: rmsnorm, flash_attention (prefill/train), decode_attention
(flash-decode over a KV cache), ssd_scan (Mamba2/mLSTM chunk recurrence),
groupnorm_silu (diffusion U-Net hot spot).
"""

import os


def use_pallas() -> str:
    """Dispatch mode: 'tpu' whenever the default device is a TPU, so
    nothing can hide the chip; off the chip 'interpret' when forced via
    REPRO_FORCE_PALLAS=1 (tests), else 'ref'.  A backend that fails to
    initialize raises here rather than selecting the reference path."""
    import jax
    if jax.devices()[0].platform == "tpu":
        return "tpu"
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return "interpret"
    return "ref"
