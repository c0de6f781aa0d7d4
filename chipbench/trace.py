"""From a profiler trace to device busy time, program and kernel time, and
idle gaps attributed to what the harness was doing.

``jax.profiler`` writes an ``.xplane.pb`` file; ``ProfileData`` reads it
with nothing but JAX.  A TPU's plane is ``/device:TPU:<n>``: its line
``XLA Ops`` holds one event per operation the chip ran.  The harness's own spans
(``jax.profiler.TraceAnnotation``, names starting ``chipbench.``) are on
the host's plane.  Both are on one clock.

The traced window runs from the start of the first ``chipbench.round``
span to the end of the last.  Busy time is the union of the operations'
intervals inside it, per chip, averaged over chips.  Each stretch of the
window in which no operation ran is an idle gap; it is labelled by the
harness span around its middle: a span of the session's calls inside the
round (``chipbench.batch``: dispatch and the wait for the result), else
the round after the last such span that ended before it
(``chipbench.round>chipbench.batch`` is host work in a round after a
batch returned: the closed loop, a replan, building the next lanes;
``chipbench.round`` alone is planning before the session opened).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

SPAN_PREFIX = "chipbench."
ROUND_SPAN = "chipbench.round"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PALLAS = 'custom_call_target="tpu_custom_call"'
_OUT_SHAPE = re.compile(r"^%\S+ = (\w+)\[([\d,]*)\]")


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir``, read."""
    import jax
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over chips
    chips: int
    op_s: Dict[str, float]             # device time by operation
    gap_s: Dict[str, float]            # idle time by host label
    pallas: List[Tuple[str, float]]    # Pallas calls: HLO text, seconds

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e.name, e.start_ns, e.start_ns + e.duration_ns


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class _Spans:
    """The harness's host spans, for looking up what ran at an instant:
    the round spans, and inside each the spans of the session's calls."""

    def __init__(self, spans):
        tops = sorted((s, e, n) for n, s, e in spans if n == ROUND_SPAN)
        self.tops = tops
        self.top_starts = [s for s, _, _ in tops]
        self.kids = [[] for _ in tops]
        for n, s, e in spans:
            if n == ROUND_SPAN:
                continue
            i = bisect.bisect_right(self.top_starts, s) - 1
            if i >= 0 and e <= tops[i][1]:
                self.kids[i].append((s, e, n))
        for k in self.kids:
            k.sort()
        self.kid_starts = [[s for s, _, _ in k] for k in self.kids]

    def label(self, t: float) -> str:
        i = bisect.bisect_right(self.top_starts, t) - 1
        if i < 0 or t >= self.tops[i][1]:
            return "outside"
        j = bisect.bisect_right(self.kid_starts[i], t) - 1
        if j < 0:
            return ROUND_SPAN
        s, e, n = self.kids[i][j]
        return n if t < e else f"{ROUND_SPAN}>{n}"


def _op_key(name: str) -> str:
    """An operation's instruction name and output shape, from its HLO
    text (``fusion.13 f32[128,32,32,256]``)."""
    m = _OUT_SHAPE.match(name)
    if not m:
        return name[:80]
    instr = name.split(" = ", 1)[0].lstrip("%")
    kind = " pallas" if PALLAS in name else ""
    return f"{instr} {m.group(1)}[{m.group(2)}]{kind}"


def reduce(pd) -> Summary:
    """Reduce a read trace to the numbers the per-layer metrics use."""
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    rounds = [(s, e) for n, s, e in spans if n == ROUND_SPAN]
    if not rounds:
        raise ValueError("the trace holds no chipbench.round span")
    lo = min(s for s, _ in rounds)
    hi = max(e for _, e in rounds)
    lookup = _Spans(spans)
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    pallas: List[Tuple[str, float]] = []
    busy = 0.0
    for plane in devices:
        ops = []
        for name, s, e in _events(plane, OPS_LINE):
            if e > lo and s < hi:
                ops.append((s, e))
                key = _op_key(name)
                op_s[key] = op_s.get(key, 0.0) + (min(e, hi)
                                                 - max(s, lo)) / 1e9
                if PALLAS in name:
                    pallas.append((name, (e - s) / 1e9))
        merged = _union(_clip(ops, lo, hi))
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                label = lookup.label((s + e) / 2)
                gap_s[label] = gap_s.get(label, 0.0) + (e - s) / 1e9
    chips = max(len(devices), 1)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / chips,
                   chips=chips, op_s=op_s, gap_s=gap_s,
                   pallas=pallas)


@dataclasses.dataclass
class Observation:
    """What a per-layer metric's reader may read: the traced rounds, the
    trace's summary, the configuration's sizes, the chip's peaks and the
    programs compiled during the window."""
    rounds: list
    trace: Summary
    sizes: dict
    peaks: dict
    compiles: int
