"""Federated language-model training: FedLECC selecting over LM clients.

The scale-out story of DESIGN.md §3 run literally: K clients each hold
token streams with *topic skew* (distinct Markov transition tables play
the role of label skew); per round FedLECC clusters clients by their
token-histogram Hellinger distances and selects the highest-loss
clusters; selected clients run local SGD on a reduced xlstm-125m.

Since the ``Task`` registry axis, this is a thin ``make_engine``
consumer — no hand-rolled round loop.  ``FLConfig(task="lm")`` selects
the transformer LM task, and the very same config drives every backend:

- ``backend="host"``     — numpy selection + vmapped selected cohort
- ``backend="compiled"`` — jit mask selection, every client trains,
                           mask-gated aggregation
- ``backend="scaleout"`` — clients blocked over the ``pod`` mesh axis,
                           aggregation as the selection-weighted psum

The ground-truth topic ids are passed as the ``partition_labels`` data
override, so the non-IID shard partition groups clients by topic and
the planted cluster structure is what FedLECC's OPTICS sees.

Long runs survive process death with ``--ckpt DIR`` (DESIGN.md §12):
every round the full engine carry is saved atomically to
``DIR/round_*.ckpt`` and each ``RoundResult`` is appended to
``DIR/metrics.jsonl``; re-running with ``--resume`` restores the latest
checkpoint and finishes the remaining rounds bit-identically to an
uninterrupted run.

    PYTHONPATH=src python examples/federated_lm.py [--rounds 4]
    PYTHONPATH=src python examples/federated_lm.py --backends host scaleout
    PYTHONPATH=src python examples/federated_lm.py --backends host \
        --ckpt /tmp/fl_lm --resume
"""

import argparse
import os

import numpy as np

from repro.data.synthetic import Dataset, make_token_stream
from repro.engine import FLConfig, make_engine

VOCAB = 128
SEQ_LEN = 64
N_TOPICS = 3
SEQS_PER_CLIENT = 16


def build_corpus(K: int, seed: int = 0):
    """One corpus with planted topic structure: each *client* draws a
    topic, and all of its ``SEQS_PER_CLIENT`` sequences come from that
    topic's Markov transition table (the LM analogue of label skew).
    Per-topic counts are therefore multiples of the shard size, so the
    shard partition over the returned per-sequence topic ids yields
    topic-pure clients.  Returns (train, test, seq_topic_ids)."""
    rng = np.random.default_rng(seed)
    client_topics = rng.integers(0, N_TOPICS, K)
    topics = np.repeat(client_topics, SEQS_PER_CLIENT)
    x = np.empty((len(topics), SEQ_LEN), np.int32)
    y = np.empty((len(topics), SEQ_LEN), np.int32)
    for t in range(N_TOPICS):
        s = make_token_stream(int((topics == t).sum()), SEQ_LEN, VOCAB,
                              seed=100 + t)
        # bijective per-topic token relabeling: every Markov table's
        # unigram mass concentrates near token 0, so shift each topic's
        # vocabulary to give topics distinct token histograms (the skew
        # FedLECC clusters on) without changing learnability
        shift = t * (VOCAB // N_TOPICS)
        x[topics == t] = (s.x + shift) % VOCAB
        y[topics == t] = (s.y + shift) % VOCAB
    test = make_token_stream(32, SEQ_LEN, VOCAB, seed=999)
    return Dataset(x=x, y=y), test, topics


def main(rounds: int = 4, K: int = 12, m: int = 4,
         backends: tuple[str, ...] = ("host", "compiled", "scaleout"),
         ckpt: str | None = None, resume: bool = False):
    train, test, topics = build_corpus(K)

    for backend in backends:
        cfg = FLConfig(
            task="lm",
            # keep the reduced xlstm-125m small enough for a CPU smoke run
            task_kwargs={"model": "xlstm-125m",
                         "overrides": {"d_model": 64, "vocab": VOCAB}},
            backend=backend,
            strategy="fedlecc", strategy_kwargs={"J": N_TOPICS},
            n_clients=K, m=m, rounds=rounds,
            batch_size=8, eval_samples=8, eval_every=1,
            partition="shards", target_hd=0.8, max_steps_cap=4, seed=0,
        )
        # topic ids drive the non-IID split (task data override), so each
        # client's stream is topic-pure and token histograms cluster by topic
        extra = {}
        if ckpt is not None:
            from repro.checkpoint import JsonlTracker, latest_checkpoint

            cdir = os.path.join(ckpt, backend)
            extra["checkpointer"] = cdir
            extra["tracker"] = JsonlTracker(os.path.join(cdir, "metrics.jsonl"))
            if resume and latest_checkpoint(cdir) is not None:
                extra["resume"] = cdir
        engine = make_engine(cfg, train, test, n_classes=VOCAB,
                             partition_labels=topics, **extra)
        if "resume" in extra:
            print(f"[{backend}] resumed at round {engine._round}")
        print(f"[{backend}] clusters found: {engine.strategy.n_clusters} "
              f"({N_TOPICS} topics planted)")
        for r in engine.rounds():
            print(f"[{backend}] round {r.round}: selected {list(r.selected)} "
                  f"mean_local_loss={r.mean_selected_loss:.3f} "
                  f"test_loss={r.test_loss:.3f} "
                  f"next_token_acc={r.test_acc:.3f} "
                  f"comm={r.comm_mb:.1f}MB")
        engine.close_trackers()
    print("done — test_loss should trend down; all backends select "
          "identical clients for one seed (the conformance guarantee)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--backends", nargs="+",
                    default=["host", "compiled", "scaleout"],
                    choices=["host", "compiled", "scaleout"])
    ap.add_argument("--ckpt", default=None, metavar="DIR",
                    help="checkpoint every round into DIR/<backend>/ and "
                         "append RoundResults to metrics.jsonl there")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint under --ckpt "
                         "before running (no-op when none exists yet)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(rounds=args.rounds, backends=tuple(args.backends),
         ckpt=args.ckpt, resume=args.resume)
