"""ScaleoutEngine — the pod-scale mesh round behind the engine protocol.

The production ``make_scaleout_round`` path (clients ↔ pods, shard_map
+ weighted psum, ``repro.federated.scaleout``) does not bypass the
canonical ``poll_losses → select → local_train → aggregate → evaluate``
round: ``ScaleoutEngine`` drives exactly that protocol and streams the
same frozen ``RoundResult``s as the host and compiled backends.

Mapping (DESIGN.md §3b):

- the client stacks live on the mesh, ``P("pod")``: the K clients are
  blocked over the pods (K/P clients per pod), placed straight from the
  host, so the population may exceed what one device holds;
- the loss poll runs over the sharded stacks, each pod polling its own
  block in place;
- selection runs through the shared ``MaskSelectionMixin`` path, as on
  the compiled backend;
- the round then trains **only the cohort**, in one program
  (``_pod_cohort_round``): the selected clients' rows are gathered from
  the pods that hold them and spread evenly, ``cohort_capacity / P``
  clients a pod (sorted cohort order, so slot j trains on pod
  ``j // (capacity / P)``); each pod trains its share from one
  replicated copy of the global parameters, and **aggregation is each
  pod's weighted partial sum followed by the psum over ``pod``**.
  Per-round training work and memory are cohort-proportional; only the
  poll touches every client.

The cohort capacity is ``m_eff`` rounded up to a multiple of P, so the
program's shapes are static whatever the cohort's split over pods and no
round retraces; the padding slots train a copy of a cohort member and
aggregate with weight zero, as do clients a systems deadline dropped.
Per-client PRNG keys derive by global client index (``fold_in``), so a
``ScaleoutEngine`` round matches the ``host`` and ``compiled`` rounds
for the same config — the cross-backend conformance suite asserts this
for every mask-capable strategy.

Between rounds the global parameters are kept twice: the replicated
device copy the next poll and round read, and ``self.params``, a host
copy read back once a round (the round's one sync on its result).  The
host copy is what the engine protocol hands out, so callers' own jitted
code, on whatever device, can combine it with theirs; parameters
installed from outside (``engine.params = ...``, a restored
checkpoint) are re-placed on the mesh on first use.

``make_scaleout_round`` — the engine-API entry for the production
*transformer* mesh round used by ``repro.launch.dryrun --federated`` —
lives here too (moved from ``repro.engine.compiled``, which keeps a
delegating re-export).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.selection import selection_weights
from repro.engine.base import Engine, MaskSelectionMixin
from repro.engine.compiled import cohort_trainer
from repro.engine.trace import scope, to_host

__all__ = ["ScaleoutEngine", "make_scaleout_round", "pod_cohort_round"]


def pod_cohort_round(apply_fn, loss_fn, mesh, *, lr: float, max_steps: int,
                     batch_size: int, mu: float = 0.0):
    """The cohort round over a mesh with a ``pod`` axis, as one jitted
    program named ``_pod_cohort_round``:

        (params, xs, ys, mask, taus, sizes, idx, n, arrived, key)
            -> (new_params, losses)

    ``xs``/``ys``/``mask`` are the (K, N_max, ...) client stacks sharded
    ``P("pod")``; ``taus``/``sizes`` the (K,) step budgets and sizes;
    ``idx`` the (C,) cohort slots (client ids, C a multiple of the pod
    count), of which the first ``n`` hold the cohort and the rest are
    padding; ``arrived`` the (K,) bool mask of the clients whose updates
    aggregate, weighted as ``selection_weights`` weighs them.  Padding
    slots weigh zero.  ``losses`` (C,) are the slots' mean local losses,
    in slot order.  Slot j trains on pod ``j // (C / P)``.
    """

    train = cohort_trainer(apply_fn, loss_fn, lr=lr, max_steps=max_steps,
                           batch_size=batch_size, mu=mu)
    n_pods = mesh.shape["pod"]

    def body(params, xs, ys, mask, taus, sizes, idx, n, arrived, key):
        pod = jax.lax.axis_index("pod")
        block = xs.shape[0]
        per_pod = idx.shape[0] // n_pods
        mine = jax.lax.dynamic_slice_in_dim(idx, pod * per_pod, per_pod)
        with scope("train"):
            # each pod copies out the cohort rows it holds (zeros for the
            # others), one client at a time: a dynamic slice along the
            # client axis reads just that client, where the TPU's gather
            # would copy the whole stack; the reduce-scatter then hands
            # every pod the rows of its own slots
            local = idx - pod * block
            held = (local >= 0) & (local < block)
            at = jnp.clip(local, 0, block - 1)
            stacks = (xs, ys, mask)

            def copy_out(j, bufs):
                return tuple(
                    jax.lax.dynamic_update_index_in_dim(
                        buf, jnp.where(held[j], jax.lax.dynamic_index_in_dim(
                            stack, at[j], keepdims=False), 0).astype(buf.dtype),
                        j, axis=0)
                    for buf, stack in zip(bufs, stacks))

            rows = jax.lax.fori_loop(
                0, idx.shape[0], copy_out,
                tuple(jnp.zeros(idx.shape + s.shape[1:], s.dtype) for s in stacks))
            cx, cy, cm = (jax.lax.psum_scatter(r, "pod", scatter_dimension=0,
                                               tiled=True) for r in rows)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(mine)
            ends, losses = train(params, cx, cy, cm, jnp.take(taus, mine), keys)
        with scope("aggregate"):
            w = jnp.where(jnp.arange(idx.shape[0]) < n,
                          jnp.take(selection_weights(arrived, sizes), idx), 0.0)
            w = jax.lax.dynamic_slice_in_dim(w, pod * per_pod, per_pod)
            new = jax.tree.map(
                lambda s: jax.lax.psum(
                    jnp.tensordot(w, s.astype(jnp.float32), axes=1), "pod"
                ).astype(s.dtype),
                ends,
            )
        return new, losses

    rep, pod = P(), P("pod")
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, pod, pod, pod, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, pod), axis_names={"pod"}, check_vma=False,
    )

    def _pod_cohort_round(params, xs, ys, mask, taus, sizes, idx, n, arrived,
                          key):
        return sharded(params, xs, ys, mask, taus, sizes, idx, n, arrived, key)

    return jax.jit(_pod_cohort_round, donate_argnums=())


class ScaleoutEngine(MaskSelectionMixin, Engine):
    backend = "scaleout"
    requires_fedavg_aggregator = True  # aggregation IS the psum

    def __init__(self, cfg, train, test, n_classes: int, mesh=None,
                 partition_labels=None):
        # the mesh comes first: the client stacks are placed on it
        self.mesh = mesh if mesh is not None else self._default_mesh(cfg.n_clients)
        if "pod" not in self.mesh.shape:
            raise ValueError(
                f"scaleout mesh must carry a 'pod' (client) axis; got axes "
                f"{tuple(self.mesh.shape)} — build it with "
                f"make_host_mesh(pod=...) or make_production_mesh(multi_pod=True)"
            )
        self.n_pods = int(self.mesh.shape["pod"])
        if cfg.n_clients % self.n_pods:
            raise ValueError(
                f"n_clients={cfg.n_clients} must be divisible by the pod axis "
                f"({self.n_pods}) so clients block evenly over pods"
            )
        self._replicated = NamedSharding(self.mesh, P())
        super().__init__(cfg, train, test, n_classes,
                         partition_labels=partition_labels)
        self._check_mask_backend()
        self.test_x, self.test_y = jax.device_put(
            (self.test_x, self.test_y), self._replicated
        )
        self._taus_j, self._sizes_j = jax.device_put(
            (self.taus, self.sizes.astype(np.float32)), self._replicated
        )
        # static cohort shape: m_eff slots rounded up to whole pods
        self.cohort_capacity = -(-self.m_eff // self.n_pods) * self.n_pods
        self._row_bytes = sum(a.nbytes // a.shape[0]
                              for a in (self.xs, self.ys, self.mask))
        self.params = jax.device_get(self.params)
        self._placed_for = self._placed = None
        self._round_fn = pod_cohort_round(
            self._apply_fn, self._loss_fn, self.mesh, lr=cfg.lr,
            max_steps=self.max_steps, batch_size=cfg.batch_size, mu=cfg.mu,
        )

    @staticmethod
    def _default_mesh(n_clients: int):
        """Largest pod axis that divides n_clients and fits the local
        devices (1 on a single-device host — the conformance regime)."""
        from repro.launch.mesh import make_host_mesh

        n_dev = jax.device_count()
        pods = max(p for p in range(1, n_dev + 1) if n_clients % p == 0)
        return make_host_mesh(pod=pods)

    def _place_clients(self, xs, ys, mask) -> tuple:
        """K/P clients a pod, each shard sent from the host to its device."""
        return jax.device_put((xs, ys, mask), NamedSharding(self.mesh, P("pod")))

    def _device_params(self):
        """The global parameters replicated over the mesh; a value of
        ``self.params`` installed from outside is placed on first use."""
        if self._placed_for is not self.params:
            self._placed = jax.device_put(self.params, self._replicated)
            self._placed_for = self.params
        return self._placed

    def _cohort_slots(self, sel: np.ndarray) -> np.ndarray:
        """(capacity,) client ids: the sorted cohort, padded by repeating
        its first client."""
        sel = np.asarray(sel, np.int32)
        pad = sel[0] if len(sel) else 0
        return np.concatenate(
            [sel, np.full(self.cohort_capacity - len(sel), pad, np.int32)])

    def _train_counters(self, sel: np.ndarray) -> dict:
        """The cohort's clients held by each pod, and the bytes of
        client rows the round moves to a pod other than their own."""
        sel = np.asarray(sel, np.int64)
        block = self.cfg.n_clients // self.n_pods
        held = np.bincount(sel // block, minlength=self.n_pods)
        per_pod = self.cohort_capacity // self.n_pods
        moved = int(np.sum(sel // block != np.arange(len(sel)) // per_pod))
        return {"cohort_per_pod": "/".join(str(int(n)) for n in held),
                "gathered_bytes": moved * self._row_bytes}

    # -- hooks (select comes from MaskSelectionMixin) --------------------
    def local_train(self, rnd: int, sel: np.ndarray, key: jax.Array,
                    survivors: np.ndarray | None = None):
        """One mesh round: the cohort trains and the weighted psum
        aggregates in the same compiled call.  Returns the aggregated
        params as the payload.  Under a systems deadline the weights
        carry only the *survivors* — dropped cohort members contribute
        exact zeros, like the padding slots."""
        arrived = np.zeros((self.cfg.n_clients,), np.bool_)
        arrived[sel if survivors is None else survivors] = True
        new_params, losses = self._round_fn(
            self._device_params(), self.xs, self.ys, self.mask,
            self._taus_j, self._sizes_j, self._cohort_slots(sel),
            np.int32(len(sel)), arrived, key,
        )
        return new_params, to_host(losses)[:len(sel)]

    def aggregate(self, rnd: int, sel: np.ndarray, payload,
                  survivors: np.ndarray | None = None) -> None:
        # Aggregation already happened inside the mesh round (the psum).
        # The replicated result serves the next round on the mesh; its
        # host copy is what the engine hands out.
        if survivors is not None and len(survivors) == 0:
            return  # all-zero psum (nobody uploaded): keep the old model
        self.params = to_host(payload, jax.device_get)
        self._placed, self._placed_for = payload, self.params


def make_scaleout_round(model_cfg, mesh, lr: float, local_steps: int = 4,
                        compress_bits: int = 0):
    """Engine-API entry for the production transformer mesh round
    (clients ↔ pods).

    Thin wrapper over ``repro.federated.scaleout.make_federated_round`` —
    the mesh round is the mask-gated-backend semantics at pod scale:
    every pod trains, and the strategy-produced ``selection_weights``
    vector gates the all-reduce.  Imported lazily so ``repro.engine``
    stays light.
    """
    from repro.federated.scaleout import make_federated_round

    return make_federated_round(
        model_cfg, mesh, lr=lr, local_steps=local_steps,
        compress_bits=compress_bits,
    )
