"""Plain reference of the federated round, written from the semantics
and importing nothing of the program.

One round, as the configuration states it:

    poll -> select -> local SGD per selected client -> FedAvg -> evaluate

- Partition: McMahan shards of the label-sorted data, the number of
  shards per client chosen so that the mean pairwise Hellinger distance
  of the clients' label histograms is nearest the target.
- Clustering (FedLECC): pairwise Hellinger distances, OPTICS with an
  unbounded radius (each step visits the unprocessed point of least
  reachability, first index on ties), the cut at the middle of the
  largest gap in the upper half of the sorted finite reachabilities,
  DBSCAN-style labels, and every noise point a cluster of its own.
- Selection: Algorithm 1 of the paper (z = ceil(m / J) highest-loss
  clients from each of the J clusters of highest mean loss, then the
  following clusters fill what is left), or uniform random scores from
  ``numpy.random.default_rng(seed)`` with the m highest taken.
- Sampling: every random draw is the same ``jax.random`` call on the
  same key that the semantics name: the round key splits three ways off
  ``PRNGKey(seed + 17)``; client i trains on ``fold_in(train_key, i)``
  split into one key per step; the poll splits its key over the K
  clients.  Batches are drawn with replacement from a client's rows.
- Models: the forward pass of the configuration's model family
  (``models/<name>.py``), in plain ``jax.numpy``.  Matrix products run
  at ``highest`` precision.

``dtype`` is the precision the whole reference computes in: float32 is
the reference, bfloat16 its control.  The family's module makes the
weights from the seed and the harness hands them to the program, so
neither side takes weights from the other.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "partition", "label_hists", "cluster_labels", "fedlecc_select",
    "random_select", "Reference",
]


# ---------------------------------------------------------------- partition

def _shards(labels, n_clients, per_client, seed):
    rng = np.random.default_rng(seed)
    order = np.argsort(labels, kind="stable")
    blocks = []
    for c in np.unique(labels):
        block = order[labels[order] == c]
        rng.shuffle(block)
        blocks.append(block)
    order = np.concatenate(blocks)
    shards = np.array_split(order, n_clients * per_client)
    perm = rng.permutation(n_clients * per_client)
    return [np.concatenate([shards[perm[i * per_client + j]]
                            for j in range(per_client)])
            for i in range(n_clients)]


def hellinger(h: np.ndarray) -> np.ndarray:
    """(K, K) Hellinger distances of the rows of ``h`` (float64)."""
    p = np.asarray(h, np.float64)
    r = np.sqrt(p / np.maximum(p.sum(1, keepdims=True), 1e-12))
    d = np.sqrt(np.clip(1.0 - r @ r.T, 0.0, 1.0))
    np.fill_diagonal(d, 0.0)
    return d


def label_hists(labels, parts, n_classes) -> np.ndarray:
    """(K, C) normalized histograms of each client's labels."""
    h = np.stack([np.bincount(labels[ix].ravel(), minlength=n_classes)
                  for ix in parts]).astype(np.float64)
    return h / np.maximum(h.sum(1, keepdims=True), 1e-12)


def partition(labels, n_clients, target_hd, n_classes, seed):
    """Client index lists: the shard count per client whose mean
    off-diagonal Hellinger distance lies nearest ``target_hd``."""
    labels = np.asarray(labels)
    best, best_err = None, float("inf")
    for s in (1, 2, 3, 4, 6, 8):
        parts = _shards(labels, n_clients, s, seed)
        d = hellinger(label_hists(labels, parts, n_classes))
        err = abs(d.sum() / (n_clients * (n_clients - 1)) - target_hd)
        if err < best_err:
            best, best_err = parts, err
    return best


# --------------------------------------------------------------- clustering

def _optics(d: np.ndarray, min_samples: int):
    k = d.shape[0]
    core = np.sort(d, axis=1)[:, min(min_samples, k) - 1]
    reach = np.full(k, np.inf)
    done = np.zeros(k, bool)
    order = []
    for _ in range(k):
        i = int(np.argmin(np.where(done, np.inf, reach)))
        order.append(i)
        done[i] = True
        upd = ~done
        reach[upd] = np.minimum(reach[upd], np.maximum(core[i], d[i, upd]))
    return np.asarray(order), reach, core


def _auto_eps(reach: np.ndarray) -> float:
    r = np.sort(reach[np.isfinite(reach)])
    if r.size < 2:
        return float("inf")
    gaps = np.diff(r)
    lo = r.size // 2
    upper = gaps[lo:]
    if upper.size == 0 or upper.max() <= 1e-9:
        return float(r[-1]) + 1e-6
    g = lo + int(np.argmax(upper))
    return float(0.5 * (r[g] + r[g + 1]))


def cluster_labels(hists: np.ndarray, min_samples: int = 3) -> np.ndarray:
    """FedLECC's clusters of the clients' histograms."""
    order, reach, core = _optics(hellinger(hists), min_samples)
    eps = _auto_eps(reach)
    far, near = reach > eps, core <= eps
    labels = np.zeros(len(reach), np.int64)
    labels[order] = np.cumsum((far & near)[order]) - 1
    labels[far & ~near] = -1
    nxt = labels.max() + 1 if labels.max() >= 0 else 0
    for i in np.where(labels < 0)[0]:
        labels[i] = nxt
        nxt += 1
    return np.unique(labels, return_inverse=True)[1].astype(np.int64)


# ---------------------------------------------------------------- selection

def fedlecc_select(labels, losses, m: int, J: int) -> np.ndarray:
    """Algorithm 1: sorted indices of the m selected clients."""
    losses = np.asarray(losses, np.float64)
    clusters = np.unique(labels)
    J = max(1, min(J, clusters.size))
    z = math.ceil(m / J)
    means = np.array([losses[labels == c].mean() for c in clusters])
    ranked = clusters[np.argsort(-means, kind="stable")]
    chosen: list[int] = []

    def best(c):
        mem = np.where(labels == c)[0]
        return mem[np.argsort(-losses[mem], kind="stable")]

    for c in ranked[:J]:
        chosen += [int(i) for i in best(c)[:z]][: m - len(chosen)]
    for c in list(ranked[J:]) + list(ranked[:J]):
        for i in best(c):
            if len(chosen) >= m:
                break
            if i not in chosen:
                chosen.append(int(i))
    return np.sort(np.asarray(chosen[:m]))


def random_select(rng: np.random.Generator, k: int, m: int) -> np.ndarray:
    scores = rng.random(k).astype(np.float32)
    return np.sort(np.argsort(-scores, kind="stable")[:m])


SELECT_LADDER = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1)


def fedlecc_reach(labels, losses, cohort, m: int, J: int,
                  rng: np.random.Generator, draws: int = 256) -> float:
    """The least relative change ``d`` of ``losses`` (from
    ``SELECT_LADDER``) under which Algorithm 1 picks ``cohort``: each
    draw scales every loss by ``1 + d * u``, ``u`` uniform in [-1, 1]
    per client or, in every other draw, per cluster, so that a client's
    rank and a cluster's can both move.  0 where the losses as they are
    give ``cohort``; ``inf`` where no draw at the ladder's top does."""
    cohort = np.sort(np.asarray(cohort))
    losses = np.asarray(losses, np.float64)
    if np.array_equal(fedlecc_select(labels, losses, m, J), cohort):
        return 0.0
    for d in SELECT_LADDER:
        for i in range(draws):
            u = (rng.uniform(-1.0, 1.0, labels.max() + 1)[labels] if i % 2
                 else rng.uniform(-1.0, 1.0, losses.size))
            if np.array_equal(fedlecc_select(labels, losses * (1.0 + d * u), m, J),
                              cohort):
                return d
    return float("inf")


def swap_first(cohort, k: int) -> np.ndarray:
    """The planted fault of an altered selection: the first selected
    client swapped for the first unselected one."""
    out = set(int(i) for i in cohort)
    out.remove(min(out))
    out.add(min(set(range(k)) - set(int(i) for i in cohort)))
    return np.sort(np.fromiter(out, int))


# ---------------------------------------------------------------- the round

class Reference:
    """The federated rounds of one cell, from the seed and the data.

    ``run(n_rounds)`` returns, for each round, the polled losses, the
    selected clients, the cohort's mean local loss, the evaluation where
    due, and the parameters after the round (on the host)."""

    def __init__(self, cfg: dict, strategy: str, model, train, test, seed: int,
                 dtype=jnp.float32, fault: str | None = None):
        self.cfg, self.strategy, self.seed = cfg, strategy, seed
        self.dtype = jnp.dtype(dtype)
        self.fault = fault
        labels, n_bins = model.split_labels(cfg, train)
        labels = np.asarray(labels)
        self.parts = partition(labels, cfg["n_clients"], cfg["target_hd"],
                               n_bins, seed)
        self.sizes = np.array([len(ix) for ix in self.parts])
        if strategy == "fedlecc":
            self.labels = cluster_labels(label_hists(labels, self.parts, n_bins))
        self.rng = np.random.default_rng(seed)
        self.train, self.test = train, test
        self.taus = np.maximum(np.ceil(
            self.sizes * cfg["local_epochs"] / cfg["batch_size"]).astype(int), 1)
        self.max_steps = int(min(cfg["max_steps_cap"], self.taus.max()))
        n_max = int(self.sizes.max())
        self.mask = np.zeros((cfg["n_clients"], n_max), np.float32)
        for i, ix in enumerate(self.parts):
            self.mask[i, :len(ix)] = 1.0
        self._outputs = partial(model.outputs, cfg=cfg)
        self._build()

    def _cast(self, tree):
        return jax.tree.map(
            lambda a: a.astype(self.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def _rows(self, client: int, idx: np.ndarray):
        """Rows ``idx`` of a client's data, in its padded layout (a
        padding row repeats the client's first row; it is never drawn)."""
        ix = self.parts[client]
        ix = np.concatenate([ix, np.full(self.mask.shape[1] - len(ix), ix[0])])
        rows = ix[np.asarray(idx)]
        x = np.asarray(self.train.x)[rows]
        return self._cast(jnp.asarray(x)), jnp.asarray(np.asarray(self.train.y)[rows])

    def _build(self):
        outputs, cfg = self._outputs, self.cfg

        def draw(key, mask, n):
            p = mask / jnp.maximum(mask.sum(), 1e-9)
            return jax.random.choice(key, mask.shape[0], shape=(n,), p=p)

        self._poll_idx = jax.jit(lambda keys, masks: jax.vmap(
            lambda k, m: draw(k, m, cfg["eval_samples"]))(keys, masks))
        self._batch_idx = jax.jit(lambda keys, mask: jax.vmap(
            lambda k: draw(k, mask, cfg["batch_size"]))(keys))
        self._loss = jax.jit(lambda p, x, y: outputs(p, x, y)[0])
        self._eval = jax.jit(outputs)

        def sgd(p, x, y, live):
            loss, g = jax.value_and_grad(lambda q: outputs(q, x, y)[0])(p)
            return jax.tree.map(
                lambda a, b: (a - cfg["lr"] * live * b).astype(a.dtype), p, g), loss

        self._sgd = jax.jit(sgd)

    def _poll(self, params, key):
        k = self.cfg["n_clients"]
        idx = np.asarray(self._poll_idx(jax.random.split(key, k),
                                        jnp.asarray(self.mask)))
        return np.array([float(self._loss(params, *self._rows(c, idx[c])))
                         for c in range(k)], np.float32)

    def _train(self, params, client, key):
        steps = self.max_steps
        idx = np.asarray(self._batch_idx(jax.random.split(key, steps),
                                         jnp.asarray(self.mask[client])))
        if self.fault == "half_batch":
            idx = idx[:, : idx.shape[1] // 2]
        total = 0.0
        for t in range(steps):
            live = jnp.asarray(float(t < self.taus[client]), self.dtype)
            params, loss = self._sgd(params, *self._rows(client, idx[t]), live)
            total += float(t < self.taus[client]) * float(loss)
        return params, total / max(min(self.taus[client], steps), 1)

    def _evaluate(self, params):
        tx = self._cast(jnp.asarray(self.test.x))
        ty = jnp.asarray(self.test.y)
        loss, acc = self._eval(params, tx, ty)
        return float(loss), float(acc)

    def allows(self, cohort, losses, own) -> bool:
        """Whether the selection rule picks ``cohort``: Algorithm 1 on
        ``losses``, or, for random selection, the reference's own draw."""
        return self.reach(cohort, losses, own, None) == 0.0

    def reach(self, cohort, losses, own, rng) -> float:
        """``fedlecc_reach`` of ``cohort`` from ``losses`` (``rng`` None:
        exactly or not at all); for random selection 0 or ``inf`` by
        the reference's own draw."""
        cohort = np.sort(np.asarray(cohort))
        if self.strategy != "fedlecc":
            return 0.0 if np.array_equal(cohort, own) else float("inf")
        return fedlecc_reach(self.labels, losses, cohort, self.cfg["m"],
                             self.cfg["J"], rng, draws=0 if rng is None else 256)

    def run(self, params, n_rounds: int, cohorts=None, polled=None) -> list[dict]:
        """The first ``n_rounds`` rounds.  With ``cohorts`` (the clients a
        run under test selected, round by round) the reference trains
        those, and records whether its selection rule picks them from the
        run's own ``polled`` losses where the run shows them, else from
        its own, and how far its own losses have to move for the rule to
        pick them.  Without ``cohorts`` it trains its own selection (with
        the fault ``altered_selection``, altered)."""
        cfg = self.cfg
        k, m = cfg["n_clients"], cfg["m"]
        params = self._cast(params)
        key = jax.random.PRNGKey(self.seed + 17)
        out = []
        with jax.default_matmul_precision("highest"):
            for rnd in range(n_rounds):
                key, k_poll, k_train = jax.random.split(key, 3)
                if self.strategy == "fedlecc":
                    losses = self._poll(params, k_poll)
                    own = fedlecc_select(self.labels, losses, m, cfg["J"])
                else:
                    losses = None
                    own = random_select(self.rng, k, m)
                sel = own if cohorts is None else np.sort(np.asarray(cohorts[rnd]))
                if self.fault == "altered_selection":
                    sel = swap_first(sel, k)
                trained, local = [], []
                for c in sel:
                    p_c, l_c = self._train(params, int(c),
                                           jax.random.fold_in(k_train, int(c)))
                    trained.append(p_c)
                    local.append(l_c)
                w = self.sizes[sel] / self.sizes[sel].sum()
                params = jax.tree.map(
                    lambda *leaves: sum(
                        float(wi) * leaf.astype(jnp.float32)
                        for wi, leaf in zip(w, leaves)).astype(self.dtype),
                    *trained)
                seen = losses if polled is None else polled[rnd]
                rec = {"round": rnd, "losses": losses, "selected": sel,
                       "allowed": self.allows(sel, seen, own),
                       "select_gap": self.reach(
                           sel, losses, own, np.random.default_rng([self.seed, rnd])),
                       "train_loss": float(np.mean(local)),
                       "params": jax.device_get(params)}
                if rnd % cfg["eval_every"] == 0:
                    rec["test_loss"], rec["test_acc"] = self._evaluate(params)
                out.append(rec)
        return out
