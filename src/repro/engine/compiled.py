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

Aggregation is one compiled program a round (``_aggregate_round``,
under the device scope ``aggregate``): the mask-gated weights, the
cohort slice, and the bound aggregator's ``aggregate`` and
``update_state`` in one dispatch, since each dispatch costs host time
in which the device, with a few microseconds of work here, stands idle.
Its variant (compressed or not, cohort-gathered or not, which
aggregator) is fixed when the engine is built.  It donates nothing: the
faults path's optimistic aggregation keeps the pre-round params and
aggregator state and may call it again with the gate's survivors.

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

__all__ = ["CompiledEngine", "cohort_trainer", "make_scaleout_round"]


def cohort_trainer(apply_fn, loss_fn, *, lr: float, max_steps: int,
                   batch_size: int, mu: float = 0.0):
    """Plain local SGD of a stack of cohort clients, each from the same
    global parameters: ``(params, xs, ys, mask, taus, keys) -> (ends,
    losses)``, vmapped over the clients.  The cohort training of the
    mask-gated backends (compiled, fused and scaleout) is this one
    body."""

    def one_client(global_params, x, y, mask, tau, key):
        return local_train(
            apply_fn, loss_fn, global_params, x, y, mask, tau, key,
            lr=lr, max_steps=max_steps, batch_size=batch_size,
            mode="plain", mu=mu, h_state=None,
        )

    return jax.vmap(one_client, in_axes=(None, 0, 0, 0, 0, 0))


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
        K = cfg.n_clients
        vmapped = cohort_trainer(
            self._apply_fn, self._loss_fn, lr=cfg.lr, max_steps=self.max_steps,
            batch_size=cfg.batch_size, mu=cfg.mu,
        )

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

        aggregator, gather = self.aggregator, self.cohort_gather
        sizes, taus_all = self._sizes_j, self._taus_j
        if cfg.compress_bits:
            from repro.federated.compression import compressed_fedavg

            compressed = partial(compressed_fedavg, bits=cfg.compress_bits)

        def _aggregate_round(stacked, params, sel, mask, n_selected,
                             agg_state, qkey):
            """One round's aggregation as one program: the survivor mask
            (K,) becomes mask-gated weights, sliced to the payload's rows,
            and the bound aggregator reduces the payload.  Returns the new
            params, the new aggregator state and, compressed, the mean
            quantization error (else None)."""
            with scope("aggregate"):
                w_full = selection_weights(mask, sizes)
                w_sel = jnp.take(w_full, sel)
                if cfg.compress_bits:
                    # Quantization models the *cohort's* upload, so the
                    # reduce always runs over the m selected stacks
                    # (taken from the all-K payload when cohort_gather
                    # is off).
                    cohort = stacked if gather else jax.tree.map(
                        lambda s: jnp.take(s, sel, axis=0), stacked
                    )
                    new_params, qerr = compressed(cohort, params, w_sel, qkey)
                    return new_params, agg_state, qerr
                w = w_sel if gather else w_full
                taus = (jnp.take(taus_all, sel) if gather
                        else taus_all).astype(jnp.float32)
                new_params = aggregator.aggregate(
                    stacked, params, w, taus, agg_state, n_selected=n_selected,
                )
                new_state = aggregator.update_state(
                    agg_state, stacked, params, w, n_selected=n_selected
                )
                return new_params, new_state, None

        self._aggregate_round = jax.jit(_aggregate_round, donate_argnums=())
        self._qkey = None
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
        """Aggregate the round's payload into ``params`` (and the
        aggregator state) with one dispatch of ``_aggregate_round``.

        The weight mask carries only the *survivors* (systems deadline
        drops, DESIGN.md §10): dropped cohort members keep their static
        payload slot but aggregate with exact weight zero — the same
        mask-gating mechanism that makes unselected clients free.  The
        mask is built here with numpy, a (K,) bool whatever the survivor
        count, so no round retraces the program.  Nothing is donated:
        the faults path may restore the pre-round params and state and
        call again with the gate's survivors."""
        weight_idx = sel if survivors is None else survivors
        if survivors is not None and len(survivors) == 0:
            return  # nobody uploaded: the global model stands still
        mask = np.zeros((self.cfg.n_clients,), np.bool_)
        mask[weight_idx] = True
        self.params, self.agg_state, qerr = self._aggregate_round(
            payload, self.params, np.asarray(sel, np.int32), mask,
            np.int32(len(weight_idx)), self.agg_state, self._qkey,
        )
        if qerr is not None:
            self.last_quant_error = float(to_host(qerr))


def make_scaleout_round(model_cfg, mesh, lr: float, local_steps: int = 4,
                        compress_bits: int = 0):
    """Deprecated location — moved to ``repro.engine.scaleout`` alongside
    ``ScaleoutEngine``.  Thin delegation kept for backward compatibility."""
    from repro.engine.scaleout import make_scaleout_round as _impl

    return _impl(model_cfg, mesh, lr=lr, local_steps=local_steps,
                 compress_bits=compress_bits)
