"""Seeded inputs: the paper's Table II ranges and its Latin-hypercube
sampler (section IV-B), kept with the benchmark so that the traffic does
not move when the program's own sampler changes."""
from __future__ import annotations

import numpy as np

# Table II: the seven GS2 inputs and their ranges.
GS2_PARAM_RANGES = (
    ("safety_factor", 2.0, 9.0),
    ("magnetic_shear", 0.0, 5.0),
    ("electron_density_gradient", 0.0, 10.0),
    ("electron_temperature_gradient", 0.5, 6.0),
    ("beta", 0.0, 0.3),
    ("collision_frequency", 0.0, 0.1),
    ("binormal_wavelength", 0.0, 1.0),
)


def latin_hypercube(n: int, seed) -> np.ndarray:
    """[n, 7] Latin-hypercube sample over the Table II ranges."""
    rng = np.random.default_rng(seed)
    d = len(GS2_PARAM_RANGES)
    u = (rng.permuted(np.tile(np.arange(n), (d, 1)), axis=1).T
         + rng.random((n, d))) / n
    lo = np.array([r[1] for r in GS2_PARAM_RANGES])
    hi = np.array([r[2] for r in GS2_PARAM_RANGES])
    return lo + u * (hi - lo)


def stream(seed, purpose: int) -> np.random.Generator:
    """An independent generator for one use of the run's seed."""
    return np.random.default_rng([int(seed), purpose])


def input_key(theta) -> bytes:
    """A task's input as the program holds it (float32), as a dict key."""
    return np.asarray(theta, np.float32).reshape(-1).tobytes()
