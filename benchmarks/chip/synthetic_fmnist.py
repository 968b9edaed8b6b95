"""Seeded synthetic Fashion-MNIST and its non-IID client split.

The benchmark's own copy of the generator the paper's experiment runs
on here (no network, so no real FMNIST): per-class smooth prototype
images plus noise, 28x28x1, 10 classes, split over clients by a
Dirichlet label skew (Hsu et al. 2019) and equalised to the same size
per client, as in the paper's 30 clients x 1500 samples.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def image_classes(rng: np.random.Generator, num_samples: int,
                  num_classes: int = 10, shape=(28, 28, 1),
                  noise: float = 0.35) -> Tuple[np.ndarray, np.ndarray]:
    protos = rng.normal(0.0, 1.0, (num_classes,) + tuple(shape))
    for _ in range(2):  # low-pass: image-like local structure
        protos = 0.5 * protos + 0.25 * (np.roll(protos, 1, 1)
                                        + np.roll(protos, -1, 1))
        protos = 0.5 * protos + 0.25 * (np.roll(protos, 1, 2)
                                        + np.roll(protos, -1, 2))
    y = rng.integers(0, num_classes, num_samples).astype(np.int32)
    x = protos[y].astype(np.float32) + np.float32(noise) * rng.standard_normal(
        (num_samples,) + tuple(shape), np.float32)
    return x, y


def dirichlet_split(rng: np.random.Generator, labels: np.ndarray,
                    num_clients: int, alpha: float,
                    min_per_client: int = 8) -> List[np.ndarray]:
    """Index arrays, one per client; client i's labels ~ Dir(alpha)."""
    num_classes = int(labels.max()) + 1
    by_class = [rng.permutation(np.flatnonzero(labels == c))
                for c in range(num_classes)]
    while True:
        parts = [[] for _ in range(num_clients)]
        for idx in by_class:
            props = rng.dirichlet(np.full(num_clients, alpha))
            counts = np.floor(props * len(idx)).astype(int)
            for j in np.argsort(-props)[:len(idx) - counts.sum()]:
                counts[j] += 1
            for i, chunk in enumerate(np.split(idx, np.cumsum(counts)[:-1])):
                parts[i].extend(chunk)
        if min(len(p) for p in parts) >= min_per_client:
            return [rng.permutation(np.asarray(p, np.int64)) for p in parts]


def federated(seed: int, num_clients: int, samples_per_client: int,
              alpha: float, noise: float = 0.35):
    """[(x, y)] per client, each exactly ``samples_per_client`` long."""
    rng = np.random.default_rng(seed)
    x, y = image_classes(rng, num_clients * samples_per_client, noise=noise)
    out = []
    for idx in dirichlet_split(rng, y, num_clients, alpha):
        if len(idx) >= samples_per_client:
            idx = idx[:samples_per_client]
        else:
            idx = np.concatenate([idx, rng.choice(
                idx, samples_per_client - len(idx))])
        out.append((x[idx], y[idx]))
    return out


def pool(seed: int, num_samples: int, noise: float = 0.35):
    """One shared (x, y) pool, for populations that overlap client slices."""
    return image_classes(np.random.default_rng(seed), num_samples,
                         noise=noise)
