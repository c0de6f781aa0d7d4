"""Planner: share of the rounds' wall time spent before the program
opened the round's session: the initial STACKING plan and bandwidth
allocation for the round's services."""


def read(obs):
    wall = sum(r.wall_s for r in obs.rounds)
    if wall <= 0:
        return None
    return 100.0 * sum(r.plan_s for r in obs.rounds) / wall
