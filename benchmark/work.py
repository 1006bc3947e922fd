"""The least work one render asks of the chip, counted from the request
and not from whatever programs serve it, so the roofline share reads
the same whichever way a later PR implements the path.

bytes       every sample of the region read once (C*H*W*itemsize) plus
            the JPEG bytes the client actually received, written once.
operations  per pixel: window + codomain + colour + composite for each
            channel, RGB -> YCbCr, 4:2:0 subsample, the separable 8x8
            DCT of one luma and two quarter-size chroma planes, and the
            quantisation.
"""

from __future__ import annotations

# window: subtract, scale, two clips, round, /255 = 6; colour: 3
# multiplies; composite: 3 adds.
OPS_PER_CHANNEL_PIXEL = 12
# 3x3 colour matrix with offsets: 9 multiplies + 9 adds.
OPS_YCBCR_PER_PIXEL = 18
# 2x2 mean of two chroma planes: (3 adds + 1 multiply) * 2 / 4 pixels.
OPS_SUBSAMPLE_PER_PIXEL = 2
# Separable DCT-II of an 8x8 block: 2 passes * 8 rows * 8 outputs * (8
# multiplies + 7 adds) = 1920 per 64 samples = 30 a sample; luma is one
# sample a pixel, the two chroma planes half a sample a pixel together.
OPS_DCT_PER_SAMPLE = 30
# divide (multiply by reciprocal) + round, per sample.
OPS_QUANT_PER_SAMPLE = 2
SAMPLES_PER_PIXEL = 1.5


def render_bytes(channels: int, height: int, width: int, itemsize: int,
                 jpeg_bytes: float) -> float:
    return channels * height * width * itemsize + jpeg_bytes


def render_ops(channels: int, height: int, width: int) -> float:
    per_pixel = (channels * OPS_PER_CHANNEL_PIXEL + OPS_YCBCR_PER_PIXEL
                 + OPS_SUBSAMPLE_PER_PIXEL
                 + SAMPLES_PER_PIXEL * (OPS_DCT_PER_SAMPLE
                                        + OPS_QUANT_PER_SAMPLE))
    return per_pixel * height * width


def least_seconds(peak: dict, n_bytes: float, n_ops: float) -> tuple:
    """(seconds, which bound applies) for one render on this chip."""
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
