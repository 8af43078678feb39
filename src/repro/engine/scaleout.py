"""ScaleoutEngine — the pod-scale mesh round behind the engine protocol.

This closes the loop ROADMAP follow-up (c) describes: the production
``make_scaleout_round`` path (clients ↔ pods, shard_map + mask-gated
psum, ``repro.federated.scaleout``) no longer bypasses the canonical
``poll_losses → select → local_train → aggregate → evaluate`` round —
``ScaleoutEngine`` drives exactly that protocol and streams the same
frozen ``RoundResult``s as the host and compiled backends.

Mapping (DESIGN.md §3b):

- the ``pod`` mesh axis is *manual* (``jax.shard_map``); the K
  clients are blocked over the pods (K/P clients per pod, vmapped
  locally), so one pod process == one block of independently evolving
  client replicas;
- the round enters with per-client parameter stacks
  (``stack_for_clients``) sharded ``P("pod")`` — the same contract as
  the production transformer round;
- selection runs through the shared ``MaskSelectionMixin`` path: the
  strategy's jit-compatible ``select_mask_jax`` produces the
  participation mask, ``selection_weights`` turns it into the weight
  vector, and **aggregation is the weighted psum over the pod axis** —
  "only m of K clients upload" ≡ "the all-reduce carries zero weight
  for unselected clients".

Because every client trains every round with ``fold_in``-derived keys
and zero-weight clients contribute exact zeros to the psum, a
``ScaleoutEngine`` round is numerically equivalent to the ``host`` and
``compiled`` rounds for the same config — the cross-backend conformance
suite asserts this for every mask-capable strategy.

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
from repro.engine.trace import to_host

__all__ = ["ScaleoutEngine", "make_scaleout_round"]


class ScaleoutEngine(MaskSelectionMixin, Engine):
    backend = "scaleout"
    requires_fedavg_aggregator = True  # aggregation IS the psum

    def __init__(self, cfg, train, test, n_classes: int, mesh=None,
                 partition_labels=None):
        super().__init__(cfg, train, test, n_classes,
                         partition_labels=partition_labels)
        self._check_mask_backend()
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
        # the client data stacks live on the mesh, K/P clients per pod,
        # so no round copies them out of the first device
        on_pods = NamedSharding(self.mesh, P("pod"))
        self.xs, self.ys, self.mask = jax.device_put(
            (self.xs, self.ys, self.mask), on_pods
        )
        self._sizes_j = jnp.asarray(self.sizes, jnp.float32)
        # aggregate() installs host (device_get) params every round; start
        # from host params too, or the round-0 poll/evaluate compile for a
        # committed single-device Array and round 1 retraces for numpy
        self.params = jax.device_get(self.params)
        self._build_scaleout_round()

    @staticmethod
    def _default_mesh(n_clients: int):
        """Largest pod axis that divides n_clients and fits the local
        devices (1 on a single-device host — the conformance regime)."""
        from repro.launch.mesh import make_host_mesh

        n_dev = jax.device_count()
        pods = max(p for p in range(1, n_dev + 1) if n_clients % p == 0)
        return make_host_mesh(pod=pods)

    # ------------------------------------------------------------------
    def _build_scaleout_round(self) -> None:
        from repro.federated.client import local_train
        from repro.federated.scaleout import stack_for_clients

        self._stack_for_clients = stack_for_clients

        cfg = self.cfg
        apply_fn, loss_fn = self._apply_fn, self._loss_fn

        def _one_client(start, x, y, mask, tau, key):
            return local_train(
                apply_fn, loss_fn, start, x, y, mask, tau, key,
                lr=cfg.lr, max_steps=self.max_steps, batch_size=cfg.batch_size,
                mode="plain", mu=cfg.mu, h_state=None,
            )

        # per-pod block of K/P clients, each starting from its stack row
        vmapped = jax.vmap(_one_client, in_axes=(0, 0, 0, 0, 0, 0))

        def body(stacked, xs, ys, mask, taus, keys, w):
            ends, losses = vmapped(stacked, xs, ys, mask, taus, keys)
            # mask-gated weighted partial sum over the local client block,
            # then the all-reduce over pods: θ ← psum_pod Σ_block w_i θ_i.
            # Unselected clients (w=0) contribute exact zeros but still
            # receive the aggregated model (psum is replicated over pod).
            agg = jax.tree.map(
                lambda s: jax.lax.psum(
                    jnp.tensordot(w, s.astype(jnp.float32), axes=1), "pod"
                ).astype(s.dtype),
                ends,
            )
            return agg, losses

        pod = P("pod")
        pspec = jax.tree.map(lambda _: pod, self.params)
        rspec = jax.tree.map(lambda _: P(), self.params)
        self._round_fn = jax.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(pspec, pod, pod, pod, pod, pod, pod),
                out_specs=(rspec, pod),
                axis_names={"pod"},
                check_vma=False,
            ),
            donate_argnums=(),
        )

    # -- hooks (select comes from MaskSelectionMixin) --------------------
    def local_train(self, rnd: int, sel: np.ndarray, key: jax.Array,
                    survivors: np.ndarray | None = None):
        """One fused mesh round: every client trains from its stack row;
        the selection-weighted psum aggregates in the same compiled call.
        Returns the aggregated params as the payload.  Under a systems
        deadline the psum weights carry only the *survivors* — dropped
        cohort members contribute exact zeros, like unselected clients."""
        K = self.cfg.n_clients
        keys = self._client_keys(key, jnp.arange(K))
        weight_idx = sel if survivors is None else survivors
        mask = jnp.zeros((K,), jnp.bool_).at[jnp.asarray(weight_idx)].set(True)
        w = selection_weights(mask, self._sizes_j)
        new_params, losses = self._round_fn(
            self._stack_for_clients(self.params, K),
            self.xs, self.ys, self.mask, jnp.asarray(self.taus), keys, w,
        )
        return new_params, to_host(losses)[sel]

    def aggregate(self, rnd: int, sel: np.ndarray, payload,
                  survivors: np.ndarray | None = None) -> None:
        # Aggregation already happened inside the mesh round (the psum);
        # install the replicated result.  Pull to host so downstream jits
        # (poll/evaluate) never mix mesh-committed and uncommitted args.
        if survivors is not None and len(survivors) == 0:
            return  # all-zero psum (nobody uploaded): keep the old model
        self.params = to_host(payload, jax.device_get)


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
