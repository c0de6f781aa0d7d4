"""Kernel: the GroupNorm+SiLU Pallas kernel's share of its roofline.

The kernel carries no name of its own in the trace, so its calls are
found by their signature: a Pallas call (``tpu_custom_call``) that takes
a float32 activation ``[B, H*W, C]`` and a per-channel scale and shift
``[1, C]``, and returns ``[B, H*W, C]``.  Each call must read its
activation and write its output once, and read its scale and shift;
those bytes over the chip's HBM bandwidth is the least time the call
could take (its operations are a few per element, so memory bounds it).
That, summed over the traced window's calls, over their summed device
time."""

import re

from chipbench import yardstick

CALL = re.compile(
    r"^%\S+ = f32\[(\d+),(\d+),(\d+)\]\S* custom-call\("
    r"f32\[\1,\2,\3\]\S* %\S+, f32\[1,\3\]\S* %\S+, "
    r"f32\[1,\3\]\S* %\S+\), custom_call_target=\"tpu_custom_call\"")


def calls(pallas):
    """``(rows, hw, c, seconds)`` of each GroupNorm+SiLU call among the
    trace's Pallas calls."""
    for text, seconds in pallas:
        m = CALL.match(text)
        if m:
            yield (*(int(g) for g in m.groups()), seconds)


def read(obs):
    need, took = 0.0, 0.0
    for b, hw, c, seconds in calls(obs.trace.pallas):
        need += yardstick.gn_silu_call_bytes(b, hw, c)
        took += seconds
    if took <= 0:
        return None
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / took
