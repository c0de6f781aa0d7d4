"""The denoisers' shared configuration class, ``UNetConfig``, and its
presets.

``UNetConfig`` describes every denoiser the batch-denoising executor
runs; its ``arch`` field picks the network (``repro.diffusion.executor
.denoiser``):

* ``"unet"``: the paper's own GenAI substrate, the DDPM/DDIM CIFAR-10
  U-Net (~35M params) that its batch-denoising measurements (Fig. 1a/1b)
  are taken from.  ``CONFIG`` is the published size; ``SMOKE`` is what
  CPU tests and benches instantiate.
* ``"dit"``: DiT (Peebles & Xie 2023, arXiv:2212.09748), an adaLN-Zero
  transformer over patches of a latent.  ``DIT_XL_2`` is DiT-XL/2 at
  256x256 (a 32x32x4 latent, ~675M params).

Not part of the assigned-architecture pool of LLM configs.
"""

from dataclasses import dataclass
from typing import Tuple


@dataclass(frozen=True)
class UNetConfig:
    """Sizes of one denoiser.  Fields every denoiser reads:

    * ``arch``: ``"unet"`` (the default) or ``"dit"``;
    * ``image_size``, ``in_channels``: the sample the denoiser steps,
      ``(image_size, image_size, in_channels)``; for a DiT the latent
      (32 for a 256x256 image through the 8x VAE);
    * ``num_train_timesteps``: the DDPM schedule's length.

    U-Net only: ``base_channels``, ``channel_mults``,
    ``num_res_blocks``, ``attn_resolutions``, ``num_groups`` and
    ``dropout`` (sampling only; never applied).

    DiT only: ``patch_size`` (p, tokens are p x p patches),
    ``hidden_size``, ``depth`` (blocks), ``num_heads``, ``mlp_ratio``,
    ``num_classes`` (the label table has one more row, the null label
    of classifier-free guidance) and ``frequency_embedding_size`` (the
    timestep's sinusoid).  They default to 0: a DiT's sizes are set in
    full, as ``DIT_XL_2`` sets them.  Every sample is conditioned on the
    null label, i.e. unconditional.  A DiT always predicts the variance too
    (``learn_sigma``, as every published DiT): 2 x ``in_channels``
    outputs, of which the executor uses the first ``in_channels``, the
    noise.

    ``dtype`` is declared but not read: the denoisers compute in the
    dtype of their parameters, float32 as built.
    """
    name: str = "ddim-cifar10"
    arch: str = "unet"
    image_size: int = 32
    in_channels: int = 3
    base_channels: int = 128
    channel_mults: Tuple[int, ...] = (1, 2, 2, 2)
    num_res_blocks: int = 2
    attn_resolutions: Tuple[int, ...] = (16,)
    num_groups: int = 32
    dropout: float = 0.0
    num_train_timesteps: int = 1000
    dtype: str = "float32"
    patch_size: int = 0
    hidden_size: int = 0
    depth: int = 0
    num_heads: int = 0
    mlp_ratio: float = 0.0
    num_classes: int = 0
    frequency_embedding_size: int = 0


CONFIG = UNetConfig()

SMOKE = UNetConfig(
    name="ddim-cifar10-smoke",
    image_size=16,
    base_channels=32,
    channel_mults=(1, 2),
    num_res_blocks=1,
    attn_resolutions=(8,),
    num_groups=8,
)

# DiT-XL/2 at 256x256: Peebles & Xie 2023, Table 1; facebookresearch/DiT
# models.py DiT_XL_2 (input_size 32 = 256 / 8, in_channels 4)
DIT_XL_2 = UNetConfig(
    name="dit-xl-2-256",
    arch="dit",
    in_channels=4,
    patch_size=2,
    hidden_size=1152,
    depth=28,
    num_heads=16,
    mlp_ratio=4.0,
    num_classes=1000,
    frequency_embedding_size=256,
)
