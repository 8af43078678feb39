"""Quickstart: FedLECC on synthetic label-skewed data in ~2 minutes (CPU).

Builds the paper's setting end-to-end: 40 clients, severe label skew
calibrated to HD≈0.85, MLP, SGD — then streams 30 federated rounds of
FedLECC selection through the engine API (``engine.rounds()`` yields one
frozen ``RoundResult`` per round) and prints the learning curve +
communication ledger.

Swap ``backend="host"`` for ``"compiled"`` to run the same config with
selection/training/aggregation as jitted computations (the scale-out
semantics) — same API, same results.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.data import make_classification
from repro.engine import FLConfig, make_engine


def main():
    train = make_classification(10_000, seed=0)
    test = make_classification(2_000, seed=1)

    cfg = FLConfig(
        n_clients=40,
        m=6,                      # participants per round
        rounds=30,
        strategy="fedlecc",
        strategy_kwargs={"J": 4},  # clusters per round
        target_hd=0.85,           # severe label skew
        eval_every=5,
        seed=0,
        backend="host",           # or "compiled": in-jit mask-gated round
    )
    engine = make_engine(cfg, train, test, n_classes=10)
    kind = "shards/client" if cfg.partition == "shards" else "Dirichlet alpha"
    print(f"partition: {kind}={engine.alpha:g}  "
          f"OPTICS found J_max={engine.strategy.n_clusters} clusters  "
          f"backend={engine.backend}")

    evaluated = []
    for r in engine.rounds():
        if r.evaluated:
            evaluated.append(r)
            print(f"[{cfg.strategy}] round {r.round:4d} "
                  f"acc={r.test_acc:.4f} loss={r.test_loss:.4f} "
                  f"comm={r.comm_mb:.1f}MB selected={list(r.selected)}")

    print("\nround  test_acc  comm_MB")
    for r in evaluated:
        print(f"{r.round:5d}  {r.test_acc:8.4f}  {r.comm_mb:7.1f}")
    print(f"\nfinal accuracy: {evaluated[-1].test_acc:.4f}")
    print(f"total communication: {evaluated[-1].comm_mb:.1f} MB "
          f"(vs {engine.comm.total_mb(30, 40, False, False):.1f} MB full participation)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
