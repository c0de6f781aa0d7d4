"""Front door: share of the traced rounds' services whose client-side
delay (round wall time plus modelled transmission) met the deadline."""

import numpy as np


def read(obs):
    if not obs.rounds:
        return None
    delay = np.concatenate([r.delays for r in obs.rounds])
    deadline = np.concatenate([r.deadlines for r in obs.rounds])
    return 100.0 * float(np.mean(delay <= deadline))
