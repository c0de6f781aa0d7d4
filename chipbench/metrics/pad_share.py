"""Executor: share of the rows the bucketed step programs ran that were
padding (the traced rounds' dispatches by bucket against the services
their batches held)."""


def read(obs):
    rows = sum(int(b) * int(n) for r in obs.rounds
               for b, n in r.telemetry.get("by_bucket", {}).items())
    useful = sum(sum(r.batch_sizes) for r in obs.rounds)
    if rows <= 0:
        return None
    return 100.0 * (1.0 - useful / rows)
