"""Closed loop: share of the traced rounds' wall time that falls outside
the batches the loop timed (planning, replans, building lanes, opening
the session, delivering the images)."""


def read(obs):
    wall = sum(r.wall_s for r in obs.rounds)
    if wall <= 0:
        return None
    batches = sum(sum(r.measured_s) for r in obs.rounds)
    return 100.0 * (1.0 - batches / wall)
