"""Operations and bytes of the GP kernels, from their shapes, and the least
time the chip could take for them (`bench/peaks.json`).

`gp_predict_experts` (one expert: `gp.predict_batch`) for n training rows,
s query rows, d inputs and m outputs needs at least:

* products: the cross term x_star x_train^T (2 s n d), the lower-triangular
  L^-1 k (s n (n + 1)) and the mean k alpha (2 s n m), all float32 at
  `Precision.HIGHEST`, which the MXU runs as `f32_highest_passes` bfloat16
  passes;
* bytes: the inputs, alpha, the lower triangle of L^-1 and the outputs,
  each read or written once, in float32.

Elementwise work (norms, the exponential, the squares) is not counted, so
the least time is a lower bound and the share cannot pass 100% unless the
kernel's time leaves out part of its work."""
from __future__ import annotations

GP_PREDICT_KERNEL = "gp_predict_experts"     # its op name on the device
D_INPUTS = 7
M_OUTPUTS = 1


def gp_predict_cost(n: int, s: int, d: int = D_INPUTS,
                    m: int = M_OUTPUTS) -> "tuple[float, float]":
    """(matmul flops, bytes) of one launch."""
    flops = 2.0 * s * n * d + float(s) * n * (n + 1) + 2.0 * s * n * m
    words = n * d + s * d + n * m + n * (n + 1) / 2 + s * (m + 1)
    return flops, 4.0 * words


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    f32_rate = peak["bf16_flops_per_s"] / peak["f32_highest_passes"]
    return max(flops / f32_rate, nbytes / peak["hbm_bytes_per_s"])


def least_seconds_gp_predict(launches: dict, peaks: dict,
                             device_kind: str) -> float:
    """Sum of the least times of `launches` {(n_train, bucket): count}.
    A device missing from the table is an error, not a default."""
    peak = peaks[device_kind]
    total = 0.0
    for (n, s), count in launches.items():
        total += count * least_seconds(*gp_predict_cost(n, s), peak)
    return total
