"""Dispatching wrappers for flash attention and whole-sequence attention."""

from __future__ import annotations

from repro import kernels
from repro.kernels.flash_attention.kernel import (flash_attention_pallas,
                                                  mha_whole_pallas)
from repro.kernels.flash_attention.ref import attention_ref

# Mosaic's default scoped VMEM a kernel may use on TPU v4 and v5e
SCOPED_VMEM_BYTES = 16 * 2**20


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    mode = kernels.use_pallas()
    if mode == "tpu":
        return flash_attention_pallas(q, k, v, causal=causal, window=window)
    if mode == "interpret":
        bq = min(128, q.shape[1])
        bk = min(128, k.shape[1])
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      bq=bq, bk=bk, interpret=True)
    return attention_ref(q, k, v, causal=causal, window=window)


def mha_whole_fits(seq: int, width: int, itemsize: int = 4) -> bool:
    """Whether ``mha_whole_pallas`` can hold a row of ``seq`` tokens of
    ``width`` = H*Dh lanes: its q, k, v and output blocks, each
    double-buffered, and one head's S x S float32 scores within the
    scoped-VMEM default.  Shapes alone decide."""
    blocks = 2 * 4 * seq * width * itemsize
    return blocks + seq * seq * 4 <= SCOPED_VMEM_BYTES


def mha_whole_path(seq: int, width: int) -> str:
    """The path ``mha_whole`` takes on rows of ``seq`` tokens of
    ``width`` lanes, from the shapes and the kernel mode alone:
    ``"whole"``, the whole-sequence kernel (on a TPU, or interpreted
    when forced off the chip), or ``"xla"``, one XLA softmax.  Raises
    where the kernel is due but a row does not fit VMEM: no tiled
    fallback is wired in."""
    if kernels.use_pallas() == "ref":
        return "xla"
    if not mha_whole_fits(seq, width):
        raise ValueError(
            f"mha_whole: a row of {seq} tokens x {width} lanes does not "
            f"fit {SCOPED_VMEM_BYTES >> 20} MiB of scoped VMEM")
    return "whole"


def mha_whole(q, k, v, *, num_heads: int):
    """Non-causal attention, q, k, v: (B, 1, S, H*Dh) -> (B, 1, S, H*Dh),
    by the path ``mha_whole_path`` names."""
    B, _, S, W = q.shape
    if mha_whole_path(S, W) == "whole":
        return mha_whole_pallas(
            q, k, v, num_heads=num_heads,
            interpret=kernels.use_pallas() == "interpret")

    def heads(a):
        return a.reshape(B, S, num_heads, W // num_heads)

    o = attention_ref(heads(q), heads(k), heads(v), causal=False)
    return o.reshape(q.shape)
