"""The MLP classifier of the paper's setting: what the benchmark needs
of one model family, found by the name a configuration gives under
``"model"``.

- ``make_data(cfg, seed)``: train and test sets from the seed;
- ``engine_kwargs(cfg)``: the program's configuration keys for the model,
  and its number of classes;
- ``init_params(cfg, seed)``: seeded weights in the program's layout,
  made on the device in one jitted call;
- ``outputs(params, x, y, cfg)``: the plain forward pass, mean cross
  entropy and accuracy;
- ``split_labels(cfg, train)``: the per-example label the partition
  splits on and the clients' histograms count, and its number of bins.
"""

import math

import jax
import jax.numpy as jnp

from benchlib import data


def make_data(cfg: dict, seed: int):
    kw = dict(n_features=cfg["n_features"], n_classes=cfg["n_classes"])
    return (data.make_classification(cfg["n_train"], seed=seed, **kw),
            data.make_classification(cfg["n_test"], seed=seed + 1, **kw))


def engine_kwargs(cfg: dict):
    return {"hidden": tuple(cfg["hidden"])}, cfg["n_classes"]


def init_params(cfg: dict, seed: int):
    sizes = (cfg["n_features"], *cfg["hidden"], cfg["n_classes"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(sizes) - 1)
        return [{"w": jax.random.normal(k, (a, b), jnp.float32) * math.sqrt(2.0 / a),
                 "b": jnp.zeros((b,), jnp.float32)}
                for k, a, b in zip(keys, sizes[:-1], sizes[1:])]

    return make(jax.random.PRNGKey(seed))


def outputs(params, x, y, cfg: dict):
    h = x
    for layer in params[:-1]:
        h = jax.nn.relu(h @ layer["w"] + layer["b"])
    logits = (h @ params[-1]["w"] + params[-1]["b"]).astype(jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               y[:, None].astype(jnp.int32), -1)[:, 0]
    acc = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
    return nll.mean(), acc.mean()


def split_labels(cfg: dict, train):
    return train.y, cfg["n_classes"]
