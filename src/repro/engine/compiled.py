"""CompiledEngine — selection inside the compiled computation.

Mirrors the scale-out mesh round (``repro.federated.scaleout``):
*selection enters as a weight vector* — the strategy's jit-compatible
mask (``select_mask_jax``) is turned into aggregation weights
(``selection_weights``) that zero out unselected clients, exactly the
mask-gated psum of DESIGN.md §3b realized on one device.

Per-round compute is proportional to the **cohort**, not the
population: since ``cfg.m`` is static, the round gathers the m selected
client stacks with ``jnp.take`` (static shapes — the traced values are
just the indices, so nothing retraces), trains only those m clients,
and aggregates the cohort stack with the cohort slice of the mask-gated
weight vector.  Unselected clients contribute exactly what they did in
the ungathered all-K path — zero-weighted terms — so the result is
numerically identical (the conformance suite locks it against the host
and scaleout backends); what changes is that their ~(K−m)/K share of
the training FLOPs is no longer spent.  ``cohort_gather=False``
(``make_engine`` passthrough) keeps the legacy every-client-trains
path, retained as the scale-out-semantics reference and as the
benchmark baseline (``benchmarks/bench_rounds.py --wallclock``).

Because per-client PRNG keys are derived by client index (``fold_in``,
see ``Engine._client_keys``), a client's local-training stream is
identical whichever cohort it runs in, and a ``CompiledEngine`` round is
numerically identical to the ``HostEngine`` round for the same config —
the cross-backend equivalence test asserts this.

``FLConfig.compress_bits > 0`` swaps the fedavg aggregation for
``compressed_fedavg`` (``repro.federated.compression``): each selected
client's delta is stochastically quantized to ``compress_bits`` before
the weighted reduce, modeling the quantized upload counted by the
``CommModel`` ledger.  The quantization PRNG stream derives from the
round's train key (``fold_in(key, K)`` — client fold_ins use 0..K−1,
so the tag never collides), which keeps it reproducible and shared with
the fused backend.

Requirements: the strategy must provide a jit-compatible selection
(``supports_compiled_selection``), and ``client_mode`` must be
``"plain"`` (per-client FedDyn state for unselected clients has no
scale-out analog yet) — both rejected up front by ``FLConfig``
validation and re-checked here.  Selection is the shared
``MaskSelectionMixin`` path, identical to ``ScaleoutEngine``'s.

``make_scaleout_round`` (the production transformer mesh round) moved to
``repro.engine.scaleout``; the re-export here is kept for backward
compatibility.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.selection import selection_weights
from repro.engine.base import Engine, MaskSelectionMixin
from repro.engine.trace import scope, span, to_host
from repro.federated.client import local_train

__all__ = ["CompiledEngine", "make_scaleout_round"]


class CompiledEngine(MaskSelectionMixin, Engine):
    backend = "compiled"

    def __init__(self, cfg, train, test, n_classes: int, partition_labels=None,
                 cohort_gather: bool = True):
        super().__init__(cfg, train, test, n_classes,
                         partition_labels=partition_labels)
        self._check_mask_backend()
        self.cohort_gather = bool(cohort_gather)
        if cfg.population is not None and not self.cohort_gather:
            raise ValueError(
                "FLConfig.population keeps the client stacks host-side, so "
                "the legacy every-client-trains path (cohort_gather=False) "
                "has nothing device-resident to train on — use "
                "cohort_gather=True or set population=None"
            )
        self._taus_j = jnp.asarray(self.taus)
        self._sizes_j = jnp.asarray(self.sizes, jnp.float32)
        self._build_compiled_jits()

    # ------------------------------------------------------------------
    def _build_compiled_jits(self) -> None:
        cfg = self.cfg
        apply_fn, loss_fn = self._apply_fn, self._loss_fn
        K = cfg.n_clients

        def _one_client(global_params, x, y, mask, tau, key):
            return local_train(
                apply_fn, loss_fn, global_params, x, y, mask, tau, key,
                lr=cfg.lr, max_steps=self.max_steps, batch_size=cfg.batch_size,
                mode="plain", mu=cfg.mu, h_state=None,
            )

        vmapped = jax.vmap(_one_client, in_axes=(None, 0, 0, 0, 0, 0))

        def _train_all(params, xs, ys, mask, taus, key):
            with scope("train"):
                keys = self._client_keys(key, jnp.arange(K))
                return vmapped(params, xs, ys, mask, taus, keys)

        self._train_all = jax.jit(_train_all, donate_argnums=())

        def _cohort_train(params, idx, key):
            """Train just the m-client cohort: ``idx`` is traced but its
            shape is static (m = cfg.m), so the gathers and the vmap keep
            one compiled graph across rounds — the no-retrace guard test
            pins this."""
            with scope("train"):
                keys = self._client_keys(key, idx)
                return vmapped(
                    params,
                    jnp.take(self.xs, idx, axis=0),
                    jnp.take(self.ys, idx, axis=0),
                    jnp.take(self.mask, idx, axis=0),
                    jnp.take(self._taus_j, idx),
                    keys,
                )

        # raw body reused inside the fused round chunk (repro.engine.fused)
        self._cohort_train_raw = _cohort_train
        self._train_cohort = jax.jit(_cohort_train, donate_argnums=())

        def _train_gathered(params, xs, ys, mask, taus, idx, key):
            """Population mode (DESIGN.md §15): the cohort stacks arrive
            from the host-side ClientStore instead of the device-resident
            all-K stacks ``_cohort_train`` closes over.  Keys still
            derive *inside* the jit by global client index, exactly like
            ``_cohort_train``, so the same cohort trains bit-identically
            either way."""
            with scope("train"):
                keys = self._client_keys(key, idx)
                return vmapped(params, xs, ys, mask, taus, keys)

        self._train_gathered = jax.jit(_train_gathered, donate_argnums=())

        def _masked_weights(mask):
            return selection_weights(mask, self._sizes_j)

        self._masked_weights = jax.jit(_masked_weights, donate_argnums=())

        if cfg.compress_bits:
            from repro.federated.compression import compressed_fedavg

            self._compressed_agg = jax.jit(
                partial(compressed_fedavg, bits=cfg.compress_bits),
                donate_argnums=(),
            )
        self.last_quant_error: float | None = None

    @staticmethod
    def _quant_key(train_key: jax.Array, n_clients: int) -> jax.Array:
        """The stochastic-rounding stream for compressed aggregation —
        derived from the round's train key with tag K (client fold_ins
        use 0..K−1, so this never collides with a client stream)."""
        return jax.random.fold_in(train_key, n_clients)

    # -- hooks (select comes from MaskSelectionMixin) --------------------
    def local_train(self, rnd: int, sel: np.ndarray, key: jax.Array,
                    survivors: np.ndarray | None = None):
        del survivors  # static-shape cohort always trains; drops are zeroed
        if self.cfg.compress_bits:
            self._qkey = self._quant_key(key, self.cfg.n_clients)
        if self._population is not None:
            with span("gather"):
                xs, ys, mask = self._store.gather(sel)
            stacked, losses = self._train_gathered(
                self.params, xs, ys, mask,
                jnp.asarray(self.taus[sel]),
                jnp.asarray(sel, jnp.int32), key,
            )
            return stacked, to_host(losses)
        if self.cohort_gather:
            stacked, losses = self._train_cohort(
                self.params, jnp.asarray(sel, jnp.int32), key
            )
            return stacked, to_host(losses)
        stacked, losses = self._train_all(
            self.params, self.xs, self.ys, self.mask, self._taus_j, key
        )
        return stacked, to_host(losses)[sel]

    # -- fault seam (DESIGN.md §14): the payload *is* the stack ---------
    def _payload_stack(self, payload):
        return payload

    def _payload_replace(self, payload, stacked):
        return stacked

    def _payload_clients(self, sel: np.ndarray) -> np.ndarray:
        if self.cohort_gather:
            return np.asarray(sel, np.int64)
        # legacy all-K path: row i of the payload is client i
        return np.arange(self.cfg.n_clients, dtype=np.int64)

    def aggregate(self, rnd: int, sel: np.ndarray, payload,
                  survivors: np.ndarray | None = None) -> None:
        stacked = payload
        sel_j = jnp.asarray(sel)
        # The weight mask carries only the *survivors* (systems deadline
        # drops, DESIGN.md §10): dropped cohort members keep their static
        # payload slot but aggregate with exact weight zero — the same
        # mask-gating mechanism that makes unselected clients free.
        weight_idx = sel if survivors is None else survivors
        if survivors is not None and len(survivors) == 0:
            return  # nobody uploaded: the global model stands still
        mask = jnp.zeros((self.cfg.n_clients,), jnp.bool_).at[
            jnp.asarray(weight_idx)
        ].set(True)
        w_full = self._masked_weights(mask)

        if self.cfg.compress_bits:
            # Quantization models the *cohort's* upload, so the reduce
            # always runs over the m selected stacks (extracted from the
            # all-K payload when cohort_gather is off).
            if self.cohort_gather:
                cohort = stacked
            else:
                cohort = jax.tree.map(
                    lambda s: jnp.take(s, sel_j, axis=0), stacked
                )
            new_params, qerr = self._compressed_agg(
                cohort, self.params, jnp.take(w_full, sel_j), self._qkey
            )
            self.last_quant_error = float(to_host(qerr))
            self.params = new_params
            return

        if self.cohort_gather:
            w = jnp.take(w_full, sel_j)
            taus = jnp.asarray(self.taus[sel], jnp.float32)
        else:
            w = w_full
            taus = jnp.asarray(self.taus, jnp.float32)
        n_agg = len(weight_idx)
        new_params = self.aggregator.aggregate(
            stacked, self.params, w, taus, self.agg_state, n_selected=n_agg,
        )
        self.agg_state = self.aggregator.update_state(
            self.agg_state, stacked, self.params, w, n_selected=n_agg
        )
        self.params = new_params


def make_scaleout_round(model_cfg, mesh, lr: float, local_steps: int = 4,
                        compress_bits: int = 0):
    """Deprecated location — moved to ``repro.engine.scaleout`` alongside
    ``ScaleoutEngine``.  Thin delegation kept for backward compatibility."""
    from repro.engine.scaleout import make_scaleout_round as _impl

    return _impl(model_cfg, mesh, lr=lr, local_steps=local_steps,
                 compress_bits=compress_bits)
