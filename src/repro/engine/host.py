"""HostEngine — the paper-faithful simulation backend.

Selection is host-side numpy (K scalars per round, DESIGN.md §8.5);
local training vmaps over just the selected cohort inside one jit.  This
is the direct descendant of the old ``FederatedSimulation`` round loop,
with strategy / aggregator / client-mode / task dispatch replaced by the
engine registries and all rule-specific state (FedDyn ``h``) owned by
the registered components.  The workload (model, loss, eval metric)
comes entirely from the task's ``(apply_fn, loss_fn)`` pair — this
backend runs the MLP classification task and the transformer LM task
through the identical hooks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.base import Engine
from repro.engine.trace import span, to_host
from repro.federated.client import local_train

__all__ = ["HostEngine"]


class HostEngine(Engine):
    backend = "host"

    def __init__(self, cfg, train, test, n_classes: int, partition_labels=None):
        super().__init__(cfg, train, test, n_classes,
                         partition_labels=partition_labels)
        self._build_host_jits()

    # ------------------------------------------------------------------
    def _build_host_jits(self) -> None:
        cfg = self.cfg
        apply_fn, loss_fn = self._apply_fn, self._loss_fn

        def _one_client(global_params, x, y, mask, tau, key, h):
            return local_train(
                apply_fn, loss_fn, global_params, x, y, mask, tau, key,
                lr=cfg.lr, max_steps=self.max_steps, batch_size=cfg.batch_size,
                mode=cfg.client_mode, mu=cfg.mu, h_state=h,
            )

        h_ax = 0 if self.client_mode.needs_h else None
        self._round_train = jax.jit(
            jax.vmap(_one_client, in_axes=(None, 0, 0, 0, 0, 0, h_ax)),
            donate_argnums=(),
        )

    # -- hooks ----------------------------------------------------------
    def select(self, rnd: int, losses: np.ndarray) -> np.ndarray:
        return self.strategy.select(rnd, losses, self.rng)

    def local_train(self, rnd: int, sel: np.ndarray, key: jax.Array,
                    survivors: np.ndarray | None = None):
        del survivors  # everyone selected trains; drops happen at aggregation
        sel_j = jnp.asarray(sel)
        keys = self._client_keys(key, sel)
        h_sel = (
            jax.tree.map(lambda a: a[sel_j], self.h_clients)
            if self.client_mode.needs_h
            else None
        )
        if self._population is not None:
            # population mode (DESIGN.md §15): cohort rows come from the
            # host-side ClientStore — same values the device gather
            # would produce, so the round is bit-identical
            with span("gather"):
                xs, ys, mask = self._store.gather(sel)
        else:
            xs, ys, mask = self.xs[sel_j], self.ys[sel_j], self.mask[sel_j]
        stacked, local_losses = self._round_train(
            self.params, xs, ys, mask,
            jnp.asarray(self.taus[sel]), keys, h_sel,
        )
        return (stacked, h_sel), to_host(local_losses)

    # -- fault seam (DESIGN.md §14): payload rows are the cohort stack --
    def _payload_stack(self, payload):
        return payload[0]

    def _payload_replace(self, payload, stacked):
        return (stacked, payload[1])

    def aggregate(self, rnd: int, sel: np.ndarray, payload,
                  survivors: np.ndarray | None = None) -> None:
        stacked, h_sel = payload
        if survivors is not None and len(survivors) != len(sel):
            # systems deadline/availability drop: only the surviving
            # uploads reach the server — reweight over them (the
            # dropped clients trained locally, but nothing arrived).
            if len(survivors) == 0:
                return  # nobody uploaded: the global model stands still
            keep = np.flatnonzero(np.isin(sel, survivors))
            rows = jnp.asarray(keep)
            stacked = jax.tree.map(lambda a: a[rows], stacked)
            if h_sel is not None:
                h_sel = jax.tree.map(lambda a: a[rows], h_sel)
            sel = np.asarray(sel)[keep]
        w = self.sizes[sel] / self.sizes[sel].sum()
        w_j = jnp.asarray(w, jnp.float32)
        taus_j = jnp.asarray(self.taus[sel], jnp.float32)

        new_params = self.aggregator.aggregate(
            stacked, self.params, w_j, taus_j, self.agg_state,
            n_selected=len(sel),
        )
        self.agg_state = self.aggregator.update_state(
            self.agg_state, stacked, self.params, w_j, n_selected=len(sel)
        )
        self.params = new_params

        if self.client_mode.needs_h:
            h_new = self.client_mode.update_client_state(
                h_sel, stacked, self.params, self.cfg.mu
            )
            sel_j = jnp.asarray(sel)
            self.h_clients = jax.tree.map(
                lambda all_, new: all_.at[sel_j].set(new),
                self.h_clients, h_new,
            )
