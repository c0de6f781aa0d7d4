"""U-Net step, the whole step: the U-Net operations the traced window's
services needed (rows that served a service times the operations of one
forward, counted from the configuration's shapes) over the window's wall
time, as a share of the chip's peak."""

from chipbench import yardstick


def read(obs):
    rows = sum(sum(r.batch_sizes) for r in obs.rounds)
    if rows <= 0 or obs.trace.window_s <= 0:
        return None
    flops = rows * yardstick.unet_flops_per_row(obs.sizes)
    return 100.0 * flops / obs.trace.window_s / obs.peaks["flops_per_s"]
