"""A stand-in verifier plug-in for the harness's tests.

Its margin is a fixed function of the row, ``cos(frequency * (row @ w))``
with ``w`` drawn from the seed, computed in float32; ``verify`` says yes
where the margin is positive. The reference computes the same function in
float64. It exercises the plug-in contract of ``bench/run.py`` without a
model.
"""
import numpy as np

KEYS = {"frequency"}
# float32 against float64 of the cosine of a five-term sum of order 100:
# about 1e-5 of the margins' RMS (about 0.7); 1e-3 leaves a hundredfold
# room, and a margin off by twice it is still far below any real fault
TOLERANCE = 1e-3


def _weights(seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 0x5EED]).standard_normal(5)


class RowMarginVerifier:
    def __init__(self, w: np.ndarray, frequency: float):
        self.w = w.astype(np.float32)
        self.frequency = np.float32(frequency)
        self.calls = 0

    def margins(self, rows: np.ndarray) -> np.ndarray:
        x = np.asarray(rows, np.float32) @ self.w
        return np.cos(self.frequency * x).astype(np.float32)

    def verify(self, rows: np.ndarray) -> np.ndarray:
        self.calls += len(rows)
        return self.margins(rows) > 0


def build(cfg, *, world, stores, seed, rehearse):
    return RowMarginVerifier(_weights(seed), cfg["verifier"]["frequency"])


def reference_margins(cfg, rows, *, world, seed, rehearse):
    x = np.asarray(rows, np.float64) @ _weights(seed)
    return np.cos(cfg["verifier"]["frequency"] * x)
