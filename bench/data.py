"""Seeded synthetic CIFAR-shaped data and the fleet's data partition.

The images follow the recipe of the simulator's own synthetic task (a class
prototype of low-frequency noise plus per-image Gaussian noise), written here
so that the benchmark, not the program, makes its inputs from ``--seed``.
The partition is the paper's Non-IID split (§IV-A): ``(1 - s%)`` of the data
dealt IID, the rest sorted by label and dealt in contiguous chunks.
"""
from __future__ import annotations

import types
from typing import List

import numpy as np

NOISE = 0.6


def make_task(num_classes: int, image_size: int, train_size: int,
              test_size: int, seed: int) -> types.SimpleNamespace:
    """Train and test images ``[n, s, s, 3]`` float32 with int32 labels."""
    rng = np.random.default_rng(seed)
    s = image_size
    low = rng.normal(0.0, 1.0, (num_classes, 8, 8, 3))
    protos = np.repeat(np.repeat(low, s // 8, axis=1), s // 8, axis=2)
    protos = (protos / np.abs(protos).max()).astype(np.float32)

    def make(n: int, sub: int):
        r = np.random.default_rng([seed, sub])
        y = r.integers(0, num_classes, n).astype(np.int32)
        x = r.standard_normal((n, s, s, 3), dtype=np.float32)
        x *= np.float32(NOISE)
        x += protos[y]
        return x, y

    x_train, y_train = make(train_size, 1)
    x_test, y_test = make(test_size, 2)
    return types.SimpleNamespace(
        num_classes=num_classes, image_size=image_size,
        x_train=x_train, y_train=y_train, x_test=x_test, y_test=y_test,
    )


def partition_noniid(y: np.ndarray, num_workers: int, s_percent: float,
                     seed: int) -> List[np.ndarray]:
    """Per-worker index arrays of the paper's Non-IID split."""
    n = len(y)
    perm = np.random.default_rng(seed).permutation(n)
    n_sorted = int(n * s_percent / 100.0)
    iid, skew = perm[: n - n_sorted], perm[n - n_sorted:]
    skew = skew[np.argsort(y[skew], kind="stable")]
    chunk = len(skew) // num_workers
    shards = []
    for w in range(num_workers):
        hi = (w + 1) * chunk if w < num_workers - 1 else len(skew)
        shards.append(np.concatenate([iid[w::num_workers], skew[w * chunk:hi]]))
    return [sh.astype(np.int64) for sh in shards]
