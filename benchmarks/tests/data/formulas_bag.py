"""Operations of the tests' second trained architecture: the function a
metric file's bare ``train_flops`` resolves to for its cells."""

from __future__ import annotations


def train_flops(cfg: dict, traffic: dict, census: dict) -> float:
    """Forward and backward (twice the forward) of the two matrix
    products, per sample; the look-up and the mean are left out."""
    per_sample = 3 * 2 * (cfg["width"] * cfg["inner"]
                          + cfg["inner"] * cfg["classes"])
    return float(per_sample * census["samples"])
