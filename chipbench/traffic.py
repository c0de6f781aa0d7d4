"""One general generator of provisioning rounds, driven by a mix file.

A mix (``chipbench/mixes/<traffic>.json``) fixes the parameters of the
paper's Sec. IV round: services per round, the range of deadlines in
milliseconds, the range of spectral efficiencies, the content size and
the total bandwidth.  Round ``r`` of a run with ``seed`` is drawn from
both, so one seed gives the same rounds every time.  Each service's
deadline and spectral efficiency are drawn i.i.d. uniform over their
ranges, as the paper's Sec. IV draws them.
"""

from __future__ import annotations

import numpy as np

_MASK64 = (1 << 64) - 1


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a stream tag."""
    return np.random.default_rng([int(seed) & _MASK64, *stream])


def round_scenario(mix: dict, seed: int, r: int, stream: int = 1):
    """The scenario of round ``r`` of ``stream`` (1 for the window, 2
    for warming up): K services that all arrive at the round's start."""
    from repro.core.service import Scenario, ServiceRequest
    g = rng(seed, 1, stream, r)
    k = int(mix["services_per_round"])
    lo, hi = mix["deadline_ms"]
    deadlines = g.uniform(lo / 1e3, hi / 1e3, size=k)
    lo, hi = mix["spectral_eff"]
    etas = g.uniform(lo, hi, size=k)
    services = [ServiceRequest(id=i, deadline=float(d), spectral_eff=float(e))
                for i, (d, e) in enumerate(zip(deadlines, etas))]
    return Scenario(services=services,
                    total_bandwidth_hz=float(mix["total_bandwidth_hz"]),
                    content_bits=float(mix["content_bits"]))


def jax_key(seed: int, *stream: int):
    """A JAX PRNG key for ``seed`` (any whole number) and a stream tag."""
    import jax
    s = int(seed) & _MASK64
    key = jax.random.PRNGKey(0)
    for part in (s >> 32, s & 0xFFFFFFFF, *stream):
        key = jax.random.fold_in(key, int(part) & 0xFFFFFFFF)
    return key
