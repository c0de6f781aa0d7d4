"""The arithmetic the benchmark's metrics rest on, kept with the benchmark.

These are copies, not imports, of the system's models, so that no change
to the system can move the yardstick:

* the quality model, the paper's Fig. 1b power law FID(T) = alpha T^-beta
  + gamma with FID(0) = 550 for a service that got no step (DDIM eta=0 on
  CIFAR-10: FID 13.36 / 6.84 / 4.67 / 4.16 at T = 10 / 20 / 50 / 100);
* the transmission delay of a generated image, S / (B_k eta_k) (the
  paper's Eqs. 8 and 11);
* the U-Net's operations and the GroupNorm+SiLU kernel's bytes, counted
  from the configuration's shapes;
* the chip's published peaks, keyed by JAX's ``device_kind``.
"""

from __future__ import annotations

FID_ALPHA = 491.0
FID_BETA = 1.72
FID_GAMMA = 4.0
FID_AT_ZERO = 550.0


def fid(steps: int) -> float:
    """Modelled FID of an image denoised in ``steps`` DDIM steps."""
    if steps <= 0:
        return FID_AT_ZERO
    return FID_ALPHA * steps ** (-FID_BETA) + FID_GAMMA


def tx_delay(content_bits: float, bandwidth_hz: float,
             spectral_eff: float) -> float:
    """Seconds to send ``content_bits`` over ``bandwidth_hz`` at
    ``spectral_eff`` bit/s/Hz."""
    return content_bits / max(bandwidth_hz * spectral_eff, 1e-12)


# Published peaks of one chip, by JAX's device_kind.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def _unet_layers(sz: dict):
    """Yield the U-Net's layers for one image, in order, as
    ``("conv", H_out, W_out, k, cin, cout[, stride])``, ``("dense", m, n)``,
    ``("attn", H*W, C)`` and ``("gn_silu", H, W, C)``."""
    ch = sz["base_channels"]
    temb = 4 * ch
    yield ("dense", ch, temb)
    yield ("dense", temb, temb)
    res = sz["image_size"]
    yield ("conv", res, res, 3, sz["in_channels"], ch)

    def block(r, cin, cout):
        yield ("gn_silu", r, r, cin)
        yield ("conv", r, r, 3, cin, cout)
        yield ("dense", temb, cout)
        yield ("gn_silu", r, r, cout)
        yield ("conv", r, r, 3, cout, cout)
        if cin != cout:
            yield ("conv", r, r, 1, cin, cout)

    cin = ch
    chans = [(cin, res)]
    mults = sz["channel_mults"]
    for li, m in enumerate(mults):
        cout = ch * m
        for _ in range(sz["num_res_blocks"]):
            yield from block(res, cin, cout)
            if res in sz["attn_resolutions"]:
                yield ("attn", res * res, cout)
            cin = cout
            chans.append((cin, res))
        if li != len(mults) - 1:
            res //= 2
            yield ("conv", res, res, 3, cin, cin, 2)
            chans.append((cin, res))
    yield from block(res, cin, cin)
    yield ("attn", res * res, cin)
    yield from block(res, cin, cin)
    for li in reversed(range(len(mults))):
        cout = ch * mults[li]
        for _ in range(sz["num_res_blocks"] + 1):
            skip_c, skip_res = chans.pop()
            yield from block(res, cin + skip_c, cout)
            if skip_res in sz["attn_resolutions"]:
                yield ("attn", res * res, cout)
            cin = cout
        if li != 0:
            res *= 2
            yield ("conv", res, res, 3, cin, cin)
    yield ("gn_silu", res, res, ch)
    yield ("conv", res, res, 3, ch, sz["in_channels"])


def _taps(n_out: int, k: int, stride: int) -> int:
    """Kernel taps that land inside the input, summed over the outputs
    of one axis of a ``SAME`` convolution (padding taps multiply zeros
    and are not counted, as XLA's cost analysis does not)."""
    n_in = n_out * stride
    pad_lo = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for j in range(k)
               if 0 <= o * stride - pad_lo + j < n_in)


def unet_flops_per_row(sz: dict) -> float:
    """Operations (2 per multiply-add) of one U-Net forward for one
    image: convolutions (taps inside the image only), dense layers and
    attention's four projections and two score products.  Normalisation
    and elementwise work are not counted."""
    total = 0
    for layer in _unet_layers(sz):
        kind = layer[0]
        if kind == "conv":
            _, h, w, k, cin, cout, *stride = layer
            s = stride[0] if stride else 1
            total += 2 * _taps(h, k, s) * _taps(w, k, s) * cin * cout
        elif kind == "dense":
            total += 2 * layer[1] * layer[2]
        elif kind == "attn":
            _, n, c = layer
            total += 4 * 2 * n * c * c + 2 * 2 * n * n * c
    return float(total)


def gn_silu_shapes(sz: dict):
    """(H, W, C) of each GroupNorm+SiLU kernel call in one forward."""
    return [layer[1:] for layer in _unet_layers(sz) if layer[0] == "gn_silu"]


def gn_silu_call_bytes(rows: int, hw: int, c: int,
                       itemsize: int = 4) -> float:
    """HBM bytes one GroupNorm+SiLU call over ``rows`` images of ``hw``
    pixels and ``c`` channels must move: read the activation and write
    the output once, read the per-channel scale and shift (float32)."""
    return float(2 * rows * hw * c * itemsize + 2 * c * 4)
