"""Kernel: the flash-attention Pallas kernel's share of its roofline.

Its calls are found among the trace's Pallas calls by their signature,
as ``gn_silu_roofline`` finds its kernel's: a ``tpu_custom_call`` that
takes three float32 operands ``[B, H, S, D]`` (q, k and v, heads before
positions) and returns one of the same shape.  Without a mask each call
must do the scores and the weighted values, 4 B H S^2 D operations, and
read q, k and v and write the output once.  The least time a call could
take is the larger of its operations over the chip's peak and its bytes
over HBM bandwidth; that, summed over the traced window's calls, over
their summed device time."""

import re

CALL = re.compile(
    r"^%\S+ = f32\[(\d+),(\d+),(\d+),(\d+)\]\S* custom-call\("
    r"f32\[\1,\2,\3,\4\]\S* %\S+, f32\[\1,\2,\3,\4\]\S* %\S+, "
    r"f32\[\1,\2,\3,\4\]\S* %\S+\), custom_call_target=\"tpu_custom_call\"")


def call_flops(b: int, h: int, s: int, d: int) -> float:
    """Operations of one unmasked call: q k^T and p v, 2 each a MAC."""
    return 4.0 * b * h * s * s * d


def call_bytes(b: int, h: int, s: int, d: int, itemsize: int = 4) -> float:
    """HBM bytes one call must move: q, k, v in and the output out."""
    return 4.0 * b * h * s * d * itemsize


def calls(pallas):
    """``(b, h, s, d, seconds)`` of each flash-attention call among the
    trace's Pallas calls."""
    for text, seconds in pallas:
        m = CALL.match(text)
        if m:
            yield (*(int(g) for g in m.groups()), seconds)


def read(obs):
    need, took = 0.0, 0.0
    for b, h, s, d, seconds in calls(obs.trace.pallas):
        need += max(call_flops(b, h, s, d) / obs.peaks["flops_per_s"],
                    call_bytes(b, h, s, d) / obs.peaks["hbm_bytes_per_s"])
        took += seconds
    if took <= 0:
        return None
    return 100.0 * need / took
