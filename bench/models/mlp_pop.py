"""The paper's MLP classifier over a population of millions of rows: the
family ``mlp`` with its data drawn in parallel.

The data is the same class-conditional mixture as ``benchlib/data.py``
(the same prototypes, labels uniform over the classes, Gaussian noise
of 0.25, clipped to [0, 1]), drawn in float32 in fixed blocks of rows,
each from its own child seed of the run's seed, on as many threads as
the host has cores.  The blocks, not the threads, fix the numbers, so
the same seed gives the same data on any host.  The weights, the plain
forward pass and the split labels are those of ``mlp``.
"""

import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from benchlib import data, spec

_MLP = spec.model("mlp", Path(__file__).resolve().parents[1])
engine_kwargs = _MLP.engine_kwargs
init_params = _MLP.init_params
outputs = _MLP.outputs
split_labels = _MLP.split_labels

BLOCK_ROWS = 1 << 14
NOISE = 0.25


def _prototypes(n_features: int, n_classes: int, per_class: int = 2,
                proto_seed: int = 1234) -> np.ndarray:
    """(C, P, F) prototypes, as ``data.make_classification`` draws them."""
    rng = np.random.default_rng(proto_seed)
    side = int(round(n_features ** 0.5))
    if side * side != n_features:
        raise ValueError(f"n_features must be a square, not {n_features}")
    return np.stack([np.stack([data._smooth_prototype(rng, side).ravel()
                               for _ in range(per_class)])
                     for _ in range(n_classes)])


def make_classification(n: int, n_features: int, n_classes: int,
                        seed: int) -> data.Dataset:
    protos = _prototypes(n_features, n_classes)
    x = np.empty((n, n_features), np.float32)
    y = np.empty((n,), np.int64)
    seeds = np.random.SeedSequence(seed).spawn(-(-n // BLOCK_ROWS))

    def block(i):
        rng = np.random.default_rng(seeds[i])
        s, e = i * BLOCK_ROWS, min(n, (i + 1) * BLOCK_ROWS)
        y[s:e] = rng.integers(0, n_classes, size=e - s)
        which = rng.integers(0, protos.shape[1], size=e - s)
        out = x[s:e]
        rng.standard_normal(out=out, dtype=np.float32)
        out *= np.float32(NOISE)
        out += protos[y[s:e], which]
        np.clip(out, 0.0, 1.0, out=out)

    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        list(pool.map(block, range(len(seeds))))
    return data.Dataset(x=x, y=y)


def make_data(cfg: dict, seed: int):
    kw = dict(n_features=cfg["n_features"], n_classes=cfg["n_classes"])
    return (make_classification(cfg["n_train"], seed=seed, **kw),
            make_classification(cfg["n_test"], seed=seed + 1, **kw))
