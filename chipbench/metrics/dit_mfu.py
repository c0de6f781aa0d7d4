"""DiT step, the whole step: the DiT operations the traced window's
services needed (rows that served a service times the operations of one
forward, counted from the configuration's shapes) over the window's wall
time, as a share of the chip's peak.  The count is the benchmark's own,
kept here beside its one reader."""


def flops_per_row(sz: dict) -> float:
    """Operations (2 per multiply-add) of one DiT forward for one
    latent: the patch embedding, the timestep MLP, in each block the
    adaLN linear, the qkv projection, attention's two products (scores
    and values, over every head), the output projection and the MLP,
    and the final adaLN and linear.  Normalisation, softmax and
    elementwise work are not counted."""
    D, p, C = sz["hidden_size"], sz["patch_size"], sz["in_channels"]
    n = (sz["image_size"] // p) ** 2
    f = int(D * sz["mlp_ratio"])
    co = 2 * C  # the noise and the variance (learn_sigma, as published)
    macs = n * p * p * C * D                              # patch embed
    macs += sz["frequency_embedding_size"] * D + D * D    # timestep MLP
    block = (D * 6 * D                                    # adaLN
             + n * D * 3 * D                              # qkv
             + 2 * n * n * D                              # q k^T, p v
             + n * D * D                                  # proj
             + 2 * n * D * f)                             # MLP
    macs += sz["depth"] * block
    macs += D * 2 * D + n * D * p * p * co                # final layer
    return 2.0 * macs


def read(obs):
    rows = sum(sum(r.batch_sizes) for r in obs.rounds)
    if rows <= 0 or obs.trace.window_s <= 0:
        return None
    flops = rows * flops_per_row(obs.sizes)
    return 100.0 * flops / obs.trace.window_s / obs.peaks["flops_per_s"]
