"""The check that decides ``correct``: served images against the reference.

Each served image is the end of a DDIM chain (Song et al. 2021,
arXiv:2010.02502, eta = 0): start from the service's seeded noise, and at
each step the system ran for it, predict the noise with the U-Net and
move to the next timestep.  The reference redoes the chains of a sample
of the window's services on its own:

* the noise from the round's key, as the session seeds it: one key per
  service from ``jax.random.split`` in sorted-id order;
* the timesteps from its own copy of the schedule's rules: the evenly
  spaced T-step subsequence of the 1000 training steps, and after a
  replan that changes a service's total, an evenly spaced chain from the
  next timestep down to 0;
* which step ran when from the session's calls, as the harness recorded
  them (batches and retargets), not from anything the session computed;
* the U-Net from the configuration's plain reference and its own copy of
  the weights, made again from the seed.

It runs after the window, once the system's state is freed, in blocks of
``rows`` images.
"""

from __future__ import annotations

import numpy as np

T_TRAIN = 1000


def alphas_cumprod(n: int = T_TRAIN) -> np.ndarray:
    """DDPM's linear beta schedule, 1e-4 to 0.02 over ``n`` steps."""
    return np.cumprod(1.0 - np.linspace(1e-4, 0.02, n, dtype=np.float64))


def ddim_timesteps(T: int, n: int = T_TRAIN) -> list:
    """The evenly spaced T-step subsequence, descending."""
    if T >= n:
        return list(range(n - 1, -1, -1))
    step = n / T
    return [int(v) for v in (np.arange(T) * step).round()[::-1]]


def retarget_timesteps(t_start: int, T: int) -> list:
    """An evenly spaced T-step chain from ``t_start`` down to 0."""
    return [int(v) for v in np.round(np.linspace(float(t_start), 0.0, T))]


def chains(initial: dict, events: list) -> dict:
    """Each service's executed ``(t_now, t_next)`` pairs, replayed from
    its planned total and the session calls ``events``: ``("batch",
    ids)`` advances each id one step, ``("retarget", {id: total})``
    re-aims it.  ``t_next`` is -1 where the chain ends."""
    rem = {k: ddim_timesteps(T) if T > 0 else [] for k, T in initial.items()}
    done = {k: 0 for k in initial}
    out = {k: [] for k in initial}
    for kind, arg in events:
        if kind == "batch":
            for k in arg:
                r = rem[k]
                if not r:
                    raise ValueError(f"service {k} stepped past its chain")
                out[k].append((r[0], r[1] if len(r) > 1 else -1))
                r.pop(0)
                done[k] += 1
        else:
            for k, total in arg.items():
                extra = int(total) - done[k]
                if extra < 0 or (extra > 0 and done[k] > 0 and not rem[k]):
                    raise ValueError(f"service {k}: retarget to {total} "
                                     f"after {done[k]} steps")
                if extra == 0:
                    rem[k] = []
                elif done[k] == 0:
                    rem[k] = ddim_timesteps(extra)
                else:
                    rem[k] = retarget_timesteps(rem[k][0], extra)
    return out


def initial_noise(key, n_services: int, position: int, shape):
    """The noise the session starts service number ``position`` (in
    sorted-id order) from."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(key, max(n_services, 1))
    return jax.random.normal(keys[position], shape, jnp.float32)


def run_chains(ref, sizes: dict, seed: int, starts, chain_list,
               dtype="float32", precision="highest", rows: int = 16):
    """Final images of the chains ``chain_list`` from the noise
    ``starts`` (N, H, W, C), computed by the reference ``ref`` in
    ``dtype`` throughout (weights, activations and the DDIM update) at
    matmul ``precision``."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    params = ref.make_params(sizes, seed, dt)
    fwd = jax.jit(lambda p, x, t: ref.forward(sizes, p, x, t))
    acp = jnp.asarray(alphas_cumprod(), dt)
    one = jnp.asarray(1.0, dt)

    @jax.jit
    def update(x, eps, t_now, t_next):
        a_now = acp[jnp.maximum(t_now, 0)][:, None, None, None]
        a_next = jnp.where((t_next < 0)[:, None, None, None], one,
                           acp[jnp.maximum(t_next, 0)][:, None, None, None])
        x0 = (x - jnp.sqrt(one - a_now) * eps) / jnp.sqrt(a_now)
        return jnp.sqrt(a_next) * x0 + jnp.sqrt(one - a_next) * eps

    xs = [jnp.asarray(s).astype(dt) for s in starts]
    length = max((len(c) for c in chain_list), default=0)
    shape = xs[0].shape if xs else ()
    for j in range(length):
        live = [i for i, c in enumerate(chain_list) if len(c) > j]
        for b in range(0, len(live), rows):
            block = live[b:b + rows]
            pad = rows - len(block)
            x = jnp.stack([xs[i] for i in block]
                          + [jnp.zeros(shape, dt)] * pad)
            tn = np.array([chain_list[i][j][0] for i in block] + [0] * pad,
                          np.int32)
            tx = np.array([chain_list[i][j][1] for i in block] + [0] * pad,
                          np.int32)
            with jax.default_matmul_precision(precision):
                eps = fwd(params, x, jnp.asarray(tn, jnp.float32))
            y = update(x, eps.astype(dt), jnp.asarray(tn), jnp.asarray(tx))
            for n, i in enumerate(block):
                xs[i] = y[n]
    return [np.asarray(x.astype(jnp.float32), np.float64) for x in xs]


def rel_err(got, want) -> float:
    """||got - want|| / ||want||, in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                   1e-300))
