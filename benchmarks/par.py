"""Process-parallel fan-out for benchmark grids.

``parallel_map(fn, items, workers)`` runs ``fn`` over ``items`` in a
``ProcessPoolExecutor`` when ``workers > 1`` and serially otherwise,
always returning results in item order — so a suite's output is
byte-identical at any worker count (every grid cell is an independent,
seeded simulation).  ``fn`` must be a module-level function and every
item picklable (the suites pass registry *names*, not callables).

Wired into ``benchmarks/run.py --workers N``: suites whose ``run``
accepts a ``workers`` keyword (churn, multiserver) fan their
rate x deadline x seed grids out across cores.

``workers > 1`` refuses to run when JAX's backend is a TPU: a chip
belongs to one process, so forked workers that touch JAX would fail or
hang on it.  Fan-out is for CPU hosts; on the chip run ``--workers 1``.
"""

from __future__ import annotations

from typing import Callable, List, Sequence


def parallel_map(fn: Callable, items: Sequence, workers: int = 1) -> List:
    """``[fn(x) for x in items]``, fanned out over ``workers``
    processes when that actually buys anything."""
    items = list(items)
    if workers <= 1 or len(items) <= 1:
        return [fn(x) for x in items]
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"parallel_map(workers={workers}) would fork processes that "
            f"share this process's TPU; run with workers=1 on the chip")
    from concurrent.futures import ProcessPoolExecutor
    with ProcessPoolExecutor(max_workers=min(workers, len(items))) as ex:
        return list(ex.map(fn, items))
