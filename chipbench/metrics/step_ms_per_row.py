"""U-Net step: the chip's busy time in the traced window over the rows
that served a service.  Nearly all of it is the bucketed step programs;
it counts every operation, so it reads the same whichever kernels or
programs implement the step."""


def read(obs):
    rows = sum(sum(r.batch_sizes) for r in obs.rounds)
    if rows <= 0 or obs.trace.busy_s <= 0:
        return None
    return 1e3 * obs.trace.busy_s / rows
