"""Model FLOPs of one federated round of the paper's MLP, from shapes.

A forward pass costs 2 FLOPs per parameter and sample, a training step
6 (forward, and backward through activations and weights).  Per round:
the cohort's local steps, the loss poll of every client (FedLECC only),
and the evaluation on the test set, spread over its cadence.
"""

import math


def round_flops(cfg: dict, strategy: str) -> dict:
    sizes = (cfg["n_features"], *cfg["hidden"], cfg["n_classes"])
    params = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    per_client = cfg["n_train"] // cfg["n_clients"]
    steps = min(cfg["max_steps_cap"],
                math.ceil(per_client * cfg["local_epochs"] / cfg["batch_size"]))
    out = {
        "train": 6 * params * cfg["m"] * steps * cfg["batch_size"],
        "poll": (2 * params * cfg["n_clients"] * cfg["eval_samples"]
                 if strategy == "fedlecc" else 0),
        "eval": 2 * params * cfg["n_test"] / cfg["eval_every"],
    }
    out["total"] = sum(out.values())
    return out
