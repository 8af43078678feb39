"""Backend-agnostic federated engine: the typed round protocol.

``Engine`` owns everything every backend shares — the non-IID partition,
the packed client tensors, the selection strategy, the comm ledger —
and drives one canonical round loop:

    poll_losses → select → local_train → aggregate → evaluate

Everything *workload*-specific (model init, per-example loss, eval
metric, the client feature used for clustering) is owned by the
registered ``Task`` selected via ``FLConfig.task``
(``repro.engine.tasks``): ``classification`` is the paper's MLP over
label-skewed images, ``lm`` is a transformer language model over
token streams with topic skew.  The engine itself never names a model.

Backends implement the hooks:

- ``HostEngine``     (``repro.engine.host``)     — numpy selection +
  vmapped cohort training (the paper-faithful simulation).
- ``CompiledEngine`` (``repro.engine.compiled``) — selection, training,
  and mask-gated aggregation as jitted computations (the scale-out
  semantics where every client computes and the participation mask
  gates aggregation).
- ``ScaleoutEngine`` (``repro.engine.scaleout``) — the same mask-gated
  semantics at mesh scale: client stacks sharded over the ``pod`` axis,
  the cohort trained over the pods via shard_map, aggregation as the
  selection-weighted psum.
- ``FusedEngine``    (``repro.engine.fused``)    — the compiled
  semantics with whole round *chunks* device-resident: one scanned jit
  per chunk, selection fully traced (``FLConfig.fuse_rounds``,
  DESIGN.md §8.6).

``CompiledEngine`` and ``ScaleoutEngine`` share one selection path,
``MaskSelectionMixin`` — strategy-produced jit-compatible masks
(``select_mask_jax``) instead of host-side index lists.

``rounds()`` is a streaming iterator yielding one frozen ``RoundResult``
per round (plus an optional callback), so consumers — examples,
benchmarks, schedulers — observe training without owning the loop.
``run()`` is the legacy consumer, producing the same history dict that
``FederatedSimulation.run()`` always returned.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm_model import CommModel, count_params
from repro.engine.aggregators import get_aggregator
from repro.engine.client_modes import get_client_mode
from repro.engine.config import (
    FLConfig,
    mask_backend_aggregator_error,
    mask_backend_client_mode_error,
    mask_backend_strategy_error,
)
from repro.engine.registry import STRATEGY_REGISTRY, mask_selection_strategies
from repro.engine.trace import scope, span, step, to_host

__all__ = [
    "Engine",
    "MaskSelectionMixin",
    "RoundResult",
    "mask_selection_strategies",
    "rounds_to_accuracy",
]


def _mean_loss(sel_losses) -> float:
    """Mean local-training loss over the cohort; ``nan`` (without numpy's
    ``RuntimeWarning``) when a strategy selected nobody this round."""
    ls = np.asarray(sel_losses)
    return float(ls.mean()) if ls.size else float("nan")


@dataclass(frozen=True)
class RoundResult:
    """One completed federated round (frozen; the streaming record type
    of ``engine.rounds()`` on every backend and every task).

    Fields:

    - ``round``              — 0-based absolute round index (stable
      across chunked ``rounds()`` calls).
    - ``selected``           — sorted tuple of the participating client
      indices this round.
    - ``mean_selected_loss`` — mean *local training* loss over the
      selected cohort (averaged over each client's executed steps).
    - ``comm_mb``            — cumulative communication ledger in MB up
      to and including this round (model up/down for the cohort, loss
      polls, one-time histograms — ``repro.core.comm_model``).
    - ``test_loss``/``test_acc`` — global-model evaluation on the held-
      out set; the metric is task-defined (classification accuracy, or
      next-token accuracy for the LM task).  ``None`` on rounds where
      evaluation was skipped (``eval_every`` cadence).
    - ``sim_time``/``sim_clock`` — simulated wall-clock seconds of this
      round / cumulative since round 0, from the systems layer
      (``FLConfig.systems``, DESIGN.md §10).  0.0 when no systems
      config is active (the frictionless engine has no clock).
    - ``n_dropped``          — dispatched-but-not-aggregated clients
      this round (offline at dispatch, or stragglers past the systems
      deadline).  ``selected`` always lists the *survivors* — the
      clients whose updates were actually aggregated.
    - ``metrics``            — optional task-defined extra evaluation
      metrics (e.g. the LM task's held-out perplexity, total and per
      topic cluster); ``None`` on unevaluated rounds and for tasks
      without extras.  Energy-tracking runs (``SystemsConfig.
      track_energy``, ROADMAP (q)) additionally carry the round's
      cohort battery spend (``energy_mah`` / ``energy_total_mah`` /
      ``n_depleted``) here on *every* round.
    - ``staleness``          — mean staleness (in params versions) of
      the updates aggregated this round.  Always 0.0 on the lock-step
      engines (every update trains against the current params); > 0
      only under the async runtime (DESIGN.md §13).
    - ``params_version``     — server params version after this round's
      aggregation.  The lock-step engines bump once per round
      (``round + 1``); the async runtime's version lags the step index
      whenever a step's buffer was empty or fully stale.
    - ``n_faulty``/``n_quarantined`` — fault axis (``FLConfig.faults``,
      DESIGN.md §14): updates that arrived carrying an injected fault
      this round, and clients serving a quarantine after it.  Inert
      zeros when no fault config is active.
    """

    round: int
    selected: tuple[int, ...]
    mean_selected_loss: float
    comm_mb: float
    test_loss: float | None = None
    test_acc: float | None = None
    sim_time: float = 0.0
    sim_clock: float = 0.0
    n_dropped: int = 0
    metrics: dict | None = None
    staleness: float = 0.0
    params_version: int = 0
    n_faulty: int = 0
    n_quarantined: int = 0

    @property
    def evaluated(self) -> bool:
        return self.test_acc is not None


class Engine:
    """Shared state + the canonical round loop; backends fill in hooks.

    ``partition_labels`` is the task-data override threaded through
    ``make_engine(**kwargs)``: a (N,) integer array replacing the task's
    derived per-example partition labels (e.g. real topic ids for the
    LM task), so callers with ground-truth skew structure control the
    non-IID split without subclassing the task.
    """

    backend = "base"

    def __init__(self, cfg: FLConfig, train, test, n_classes: int,
                 partition_labels=None):
        from repro.data.partition import calibrate_alpha, dirichlet_partition, pack_clients
        from repro.engine.tasks import build_task

        self.cfg = cfg
        self.n_classes = n_classes
        self.rng = np.random.default_rng(cfg.seed)
        self.task = build_task(cfg)

        # --- non-IID partition (calibrated to the paper's HD regime),
        # split on the task's per-example label axis ---
        if partition_labels is None:
            labels = np.asarray(self.task.partition_labels(train))
        else:
            labels = np.asarray(partition_labels)
            if labels.shape != (len(train.x),):
                raise ValueError(
                    f"partition_labels must be ({len(train.x)},); got "
                    f"shape {labels.shape}"
                )
        part_classes = self.task.partition_classes(n_classes)
        if partition_labels is not None and (
            labels.min() < 0 or labels.max() >= part_classes
        ):
            raise ValueError(
                f"partition_labels values must lie in [0, {part_classes}) "
                f"(the task's partition-label space); got range "
                f"[{labels.min()}, {labels.max()}]"
            )
        if cfg.partition == "shards":
            from repro.data.partition import calibrate_shards, shard_partition

            s = calibrate_shards(labels, cfg.n_clients, cfg.target_hd,
                                 part_classes, seed=cfg.seed)
            self.alpha = float(s)  # records shards/client in the alpha slot
            self.client_idx = shard_partition(
                labels, cfg.n_clients, s, seed=cfg.seed
            )
        else:
            alpha = cfg.alpha_dirichlet
            if alpha is None:
                alpha = calibrate_alpha(
                    labels, cfg.n_clients, cfg.target_hd, part_classes,
                    seed=cfg.seed,
                )
            self.alpha = float(alpha)
            self.client_idx = dirichlet_partition(
                labels, cfg.n_clients, self.alpha, seed=cfg.seed
            )
        self.hists = self.task.client_features(train, self.client_idx, n_classes)
        xs, ys, mask = pack_clients(train.x, train.y, self.client_idx)
        self.sizes = np.array([len(ix) for ix in self.client_idx])
        # --- population axis (DESIGN.md §15): with a PopulationConfig the
        # packed stacks stay *host-side* behind a ClientStore — only the
        # rows a round actually touches (the resident shards' poll subset
        # and the dispatched cohort) are ever device-put, so per-round
        # device memory is cohort-proportional.  None = today's
        # device-resident stacks, bit-identical.
        self._store: Any = None       # ClientStore in population mode
        self._population: Any = None  # HierarchicalSelector (built below,
        #                               after the strategy fixes needs_losses)
        if cfg.population is not None:
            from repro.population.store import InMemoryStore

            self._store = InMemoryStore(
                xs, ys, mask, self.sizes, np.asarray(self.hists),
                n_shards=cfg.population.n_shards,
            )
            self.xs = self.ys = self.mask = None
        else:
            self.xs, self.ys, self.mask = self._place_clients(xs, ys, mask)
        self.test_x, self.test_y = jnp.asarray(test.x), jnp.asarray(test.y)
        self._train_data = train  # handed to the task when building fns
        self._test_data = test    # handed to the task for extra eval metrics

        # --- model (task-owned) / optimizer-free local SGD ---
        self.params = self.task.init_params(
            jax.random.PRNGKey(cfg.seed), train, n_classes
        )
        self.n_params = count_params(self.params)

        # --- local step budgets (heterogeneous → FedNova is meaningful) ---
        taus = np.ceil(
            self.sizes * cfg.local_epochs / cfg.batch_size
        ).astype(np.int32)
        self.taus = np.maximum(taus, 1)
        self.max_steps = int(min(cfg.max_steps_cap, self.taus.max()))

        # --- systems layer (device profiles / wall clock / deadline,
        # DESIGN.md §10).  None = the frictionless engine; with a config,
        # the strategy dispatches the over-selected cohort (m_eff) and
        # the deadline policy drops stragglers down to the survivors. ---
        self._systems: Any = None  # SystemsRuntime when cfg.systems is set
        if cfg.systems is not None:
            from repro.systems.runtime import SystemsRuntime

            self._systems = SystemsRuntime(
                cfg.systems,
                n_clients=cfg.n_clients,
                steps=np.minimum(self.taus, self.max_steps),
                n_params=self.n_params,
                upload_bytes_per_param=(
                    cfg.compress_bits / 8.0 if cfg.compress_bits else 4.0
                ),
                seed=cfg.seed,
            )
            self.m_eff = cfg.systems.m_effective(cfg.m, cfg.n_clients)
        else:
            self.m_eff = cfg.m
        self.sim_clock = 0.0

        # --- pluggable components, all via the registries ---
        self.strategy = STRATEGY_REGISTRY.build(
            cfg.strategy, m=self.m_eff, **cfg.strategy_kwargs
        )
        if self._systems is None:
            # legacy setup signature kept working for external strategies
            self.strategy.setup(self.hists, self.sizes, seed=cfg.seed)
        else:
            self.strategy.setup(self.hists, self.sizes, seed=cfg.seed,
                                latency=self._systems.latency_hint())
        # --- hierarchical shard selection (population mode): built after
        # the strategy so ``needs_losses`` decides whether shards rank by
        # running loss estimates or by the dedicated loss-blind stream ---
        if cfg.population is not None:
            from repro.population.hierarchy import HierarchicalSelector

            self._population = HierarchicalSelector(
                cfg.population, self._store, seed=cfg.seed,
                needs_losses=self.strategy.needs_losses,
            )
            shard_sizes = np.sort([
                len(self._store.shard_members(s))
                for s in range(cfg.population.n_shards)
            ])
            worst = int(shard_sizes[:cfg.population.shards_per_round].sum())
            if worst < self.m_eff:
                raise ValueError(
                    f"population.shards_per_round="
                    f"{cfg.population.shards_per_round} resident shards can "
                    f"hold as few as {worst} clients but the round needs "
                    f"m_eff={self.m_eff} — raise shards_per_round or lower "
                    f"n_shards/m"
                )
        self._pop_members: np.ndarray | None = None  # set per round
        self.aggregator = get_aggregator(cfg.aggregator, cfg)
        self.agg_state = self.aggregator.init_state(self.params)
        self.client_mode = get_client_mode(cfg.client_mode)
        self.h_clients = self.client_mode.init_client_state(
            self.params, cfg.n_clients
        )

        # --- communication ledger (histogram traffic is the task's
        # clustering-feature dimension: n_classes for classification,
        # hist_bins for the LM task; quantized uploads shrink the
        # per-round upload bytes) ---
        self.comm = CommModel(
            self.n_params, cfg.n_clients, self.hists.shape[1],
            upload_bytes_per_param=(
                cfg.compress_bits / 8.0 if cfg.compress_bits else None
            ),
        )
        self.comm_mb = self.comm.one_time_mb(self.strategy.needs_histograms)

        # --- fault axis (DESIGN.md §14): injection on a dedicated child
        # rng stream, the server-side validation gate, and the
        # ClientHealth quarantine ledger.  None = bit-identical engine.
        self._faults: Any = None
        if cfg.faults is not None:
            from repro.faults.runtime import FaultRuntime

            self._faults = FaultRuntime(
                cfg.faults,
                n_clients=cfg.n_clients,
                seed=cfg.seed,
                params_template=self.params,
            )

        self._build_shared_jits()
        self._round = 0
        # the rounds() PRNG carry, persisted across calls
        self._key: jax.Array | None = None
        self.history: dict[str, list] = {
            "round": [], "test_acc": [], "test_loss": [], "comm_mb": [],
            "mean_selected_loss": [], "selected": [],
        }
        # observability + durability seams (DESIGN.md §12): trackers get
        # every committed RoundResult; a Checkpointer attached here is
        # consulted after each round via its save policy.
        self.trackers: list[Any] = []
        self.checkpointer: Any = None

    # ------------------------------------------------------------------
    def _build_shared_jits(self) -> None:
        cfg = self.cfg
        # The task's (apply, loss, metric) triple; backends thread
        # apply/loss into local_train unchanged.
        apply_fn, loss_fn, metric_fn = self.task.build_fns(
            self._train_data, self.n_classes
        )
        self._apply_fn, self._loss_fn = apply_fn, loss_fn

        def _poll_losses(params, xs, ys, mask, key):
            """Subsampled local empirical loss of the *global* model on
            every client (Algorithm 1 lines 2–4)."""

            def one(x, y, m, k):
                n = x.shape[0]
                p = m / jnp.maximum(m.sum(), 1e-9)
                idx = jax.random.choice(k, n, shape=(cfg.eval_samples,), p=p)
                out = apply_fn(params, jnp.take(x, idx, axis=0))
                return loss_fn(out, jnp.take(y, idx, axis=0), None)

            with scope("poll"):
                keys = jax.random.split(key, xs.shape[0])
                return jax.vmap(one)(xs, ys, mask, keys)

        self._poll_losses = jax.jit(_poll_losses, donate_argnums=())

        if cfg.population is not None:
            K = cfg.n_clients

            def _poll_subset(params, xs, ys, mask, members, key):
                """The flat poll restricted to the resident members.
                Per-client subsample keys come from the *same* K-way
                split ``_poll_losses`` performs, indexed by global client
                id, so with one shard (members = arange(K)) this
                reproduces the flat poll bit for bit."""

                def one(x, y, m, k):
                    n = x.shape[0]
                    p = m / jnp.maximum(m.sum(), 1e-9)
                    idx = jax.random.choice(
                        k, n, shape=(cfg.eval_samples,), p=p
                    )
                    out = apply_fn(params, jnp.take(x, idx, axis=0))
                    return loss_fn(out, jnp.take(y, idx, axis=0), None)

                with scope("poll"):
                    keys = jnp.take(jax.random.split(key, K), members, axis=0)
                    return jax.vmap(one)(xs, ys, mask, keys)

            self._poll_subset = jax.jit(_poll_subset, donate_argnums=())

        def _evaluate(params, x, y):
            out = apply_fn(params, x)
            return loss_fn(out, y, None), metric_fn(out, y)

        self._evaluate = jax.jit(_evaluate, donate_argnums=())

        # Task-defined extra evaluation metrics (None for tasks without
        # any): e.g. the LM task's held-out perplexity, total and per
        # topic cluster (ROADMAP (h)).
        self._eval_extra = self.task.build_eval_extra(
            self._test_data, self.n_classes
        )

    def _place_clients(self, xs, ys, mask) -> tuple:
        """Put the packed (K, N_max, ...) client stacks where the rounds
        read them: the default device.  Backends whose stacks live on a
        mesh place them there straight from the host."""
        return jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask)

    def _device_params(self):
        """The global parameters as the poll and the evaluation take
        them: ``self.params`` itself on one device."""
        return self.params

    def _train_counters(self, sel: np.ndarray) -> dict:
        """Counters the round's ``fl.train`` span carries as annotation
        arguments (none by default)."""
        return {}

    @staticmethod
    def _client_keys(key: jax.Array, indices) -> jax.Array:
        """Per-client PRNG keys derived by client index (``fold_in``), so
        a client's local-training stream is identical whichever backend —
        and whichever cohort — it runs in."""
        return jax.vmap(
            lambda i: jax.random.fold_in(key, i)
        )(jnp.asarray(indices, jnp.int32))

    # -- hooks (backend contract) --------------------------------------
    def poll_losses(self, rnd: int, key: jax.Array) -> np.ndarray:
        """(K,) polled losses — zeros when the strategy never polls.
        Population mode polls only the round's resident members (the
        others stay 0 here and are ``-inf``-gated before selection)."""
        if self._population is not None:
            out = np.zeros(self.cfg.n_clients, np.float32)
            if self.strategy.needs_losses:
                members = self._pop_members
                assert members is not None, "poll before begin_round"
                with span("gather"):
                    xs, ys, mask = self._store.gather(members)
                out[members] = to_host(
                    self._poll_subset(
                        self._device_params(), xs, ys, mask,
                        jnp.asarray(members), key,
                    )
                )
            return out
        if self.strategy.needs_losses:
            return to_host(self._poll_losses(
                self._device_params(), self.xs, self.ys, self.mask, key
            ))
        return np.zeros(self.cfg.n_clients, np.float32)

    def _selection_gate(self, rnd: int) -> np.ndarray | None:
        """(K,) bool admission gate for round ``rnd`` — systems
        availability ∧ fault-ledger health; ``None`` when ungated."""
        gate: np.ndarray | None = None
        if self._systems is not None:
            gate = np.asarray(self._systems.available(rnd), bool)
        if self._faults is not None:
            admit = self._faults.health.admitted(rnd)
            gate = admit if gate is None else gate & admit
        return gate

    def _gated_losses(self, rnd: int, losses: np.ndarray,
                      extra_gate: np.ndarray | None = None) -> np.ndarray:
        """Apply the admission gate to the polled losses as ``-inf`` —
        the single place every selection path (lock-step, async
        dispatch, fused chunk driver) excludes offline or quarantined
        clients before the strategy sees the loss vector (DESIGN.md
        §10/§14).  ``extra_gate`` is a caller-side AND (the async
        engine's not-already-in-flight mask)."""
        gate = self._selection_gate(rnd)
        if extra_gate is not None:
            gate = extra_gate if gate is None else gate & extra_gate
        if gate is None:
            return losses
        return np.where(gate, losses, -np.inf).astype(np.float32)

    def select(self, rnd: int, losses: np.ndarray) -> np.ndarray:
        """Sorted indices of this round's participants."""
        raise NotImplementedError

    def local_train(self, rnd: int, sel: np.ndarray, key: jax.Array,
                    survivors: np.ndarray | None = None):
        """Run local training.  Returns ``(payload, sel_losses)`` where
        ``payload`` is backend-opaque (threaded into ``aggregate``) and
        ``sel_losses`` is a (len(sel),) array of local training losses.
        ``survivors`` (systems runs only) is the subset of ``sel`` whose
        update will actually arrive — backends that aggregate inside the
        round (scaleout's psum) weight by it; the others may ignore it
        (dropped clients still *train*, they just miss the upload)."""
        raise NotImplementedError

    def aggregate(self, rnd: int, sel: np.ndarray, payload,
                  survivors: np.ndarray | None = None) -> None:
        """Fold the payload into ``self.params`` (and any server state).
        ``survivors`` (systems runs only, a subset of ``sel``) restricts
        the aggregation to the updates that beat the deadline — weights
        renormalize over the surviving mass; ``None`` means everyone
        arrived (the frictionless call shape, unchanged from before the
        systems axis)."""
        raise NotImplementedError

    # -- fault seam (backend contract; called only when ``cfg.faults``
    # is active, so backends without faults support never implement it) -
    def _payload_stack(self, payload):
        """The stacked trained-params pytree inside a ``local_train``
        payload (leading axis = rows), handed to fault injection and the
        validation gate."""
        raise NotImplementedError

    def _payload_replace(self, payload, stacked):
        """The same payload with its stacked params swapped for the
        (injected / clipped) replacement."""
        raise NotImplementedError

    def _payload_clients(self, sel: np.ndarray) -> np.ndarray:
        """Client id per row of the payload stack.  Row i of the default
        eager payload was trained by ``sel[i]``; the compiled all-K path
        overrides this with the identity."""
        return np.asarray(sel, np.int64)

    def _aggregate_state(self) -> tuple:
        """References to everything ``aggregate`` rebinds, for the
        optimistic-aggregation undo.  Every backend's ``aggregate``
        updates state *functionally* (new pytrees / new floats bound to
        ``self``), so holding the old references is a complete, free
        snapshot — covering ``params``, ``agg_state``, the host tier's
        per-client state, and the compiled compress path's
        ``last_quant_error``."""
        return (
            self.params,
            self.agg_state,
            getattr(self, "h_clients", None),
            getattr(self, "last_quant_error", None),
        )

    def _restore_aggregate_state(self, saved: tuple) -> None:
        params, agg_state, h_clients, qerr = saved
        self.params = params
        self.agg_state = agg_state
        if h_clients is not None:
            self.h_clients = h_clients
        if qerr is not None:
            self.last_quant_error = qerr

    def evaluate(self) -> tuple[float, float]:
        tl, ta = to_host(
            self._evaluate(self._device_params(), self.test_x, self.test_y),
            jax.device_get,
        )
        return float(tl), float(ta)

    def eval_metrics(self) -> dict | None:
        """Task-defined extra metrics on the held-out set (None when the
        task has none) — computed on the ``eval_every`` cadence only."""
        if self._eval_extra is None:
            return None
        return self._eval_extra(self._device_params(), self.test_x, self.test_y)

    def _carry_key(self) -> jax.Array:
        """The persisted ``rounds()`` PRNG carry.  The stream from round
        0 is unchanged from the pre-persistence implementation (one
        3-way split per round off ``PRNGKey(seed + 17)``); persisting the
        carried key just removes the O(rounds) re-split replay a resumed
        ``rounds()`` call used to pay, and lets the fused backend thread
        the same carry through its scanned chunks."""
        if self._key is None:
            self._key = jax.random.PRNGKey(self.cfg.seed + 17)
            # legacy resume (a deserialized engine with _round planted
            # but no stored key): replay the per-round splits once
            for _ in range(self._round):
                self._key, _, _ = jax.random.split(self._key, 3)
        return self._key

    # -- checkpoint / restore (DESIGN.md §12) ---------------------------
    _STATE_VERSION = 1

    def _state_pytree(self) -> dict:
        """The array-valued half of the round carry, serialized as the
        checkpoint pytree (structure doubles as the restore ``like``):
        params, aggregator server state (FedDyn ``h``), per-client state
        (FedDyn ``h_i``), the jax PRNG carry, and any strategy state."""
        state = {
            "params": self.params,
            "agg_state": self.agg_state,
            "h_clients": self.h_clients,
            "prng_key": self._carry_key(),
            "strategy": self.strategy.state_dict(),
        }
        if self._faults is not None and self._faults.has_stale:
            # stale_replay's per-client replay cache is array-valued
            # round carry — it rides the pytree, not the meta
            state["fault_stale"] = self._faults.stale_state()
        return state

    def _config_fingerprint(self) -> dict:
        from repro.checkpoint.tracker import _to_builtin

        return _to_builtin(self.cfg.to_dict())

    def save(self, path: str) -> None:
        """Serialize the full round carry to ``path`` (atomic + fsync'd
        via ``repro.checkpoint.serializer``): the state pytree plus the
        scalar carry (``_round``, ``comm_mb``, ``sim_clock``), the numpy
        selection-rng bit-generator state, the history dict, and the
        ``FLConfig`` fingerprint that ``restore`` verifies."""
        from repro.checkpoint.serializer import save_checkpoint
        from repro.checkpoint.tracker import _to_builtin

        meta: dict[str, Any] = {
            "state_version": self._STATE_VERSION,
            "backend": self.backend,
            "round": int(self._round),
            "comm_mb": float(self.comm_mb),
            "sim_clock": float(self.sim_clock),
            # PCG64 state holds 128-bit ints msgpack can't carry; json can
            "rng_state": json.dumps(self.rng.bit_generator.state),
            "history": _to_builtin(self.history),
            "config": self._config_fingerprint(),
        }
        if self._systems is not None:
            meta["systems"] = self._systems.state_dict()
        meta.update(self._extra_meta())
        save_checkpoint(path, self._state_pytree(), meta=meta)

    def _extra_meta(self) -> dict:
        """Execution-mode hook: extra scalar-valued meta merged into the
        checkpoint (the async runtime records its ledger structure here
        so ``restore`` can rebuild the ``like`` skeleton before the
        arrays load).  The base contribution is the fault-axis
        ``ClientHealth`` ledger, so kill-and-resume mid-quarantine is
        bit-identical (DESIGN.md §14.3) — plus the population axis's
        shard loss estimates (DESIGN.md §15), the hierarchy's only
        cross-round state."""
        meta: dict[str, Any] = {}
        if self._faults is not None:
            meta["faults"] = self._faults.meta_state()
        if self._population is not None:
            meta["population"] = self._population.state_dict()
        return meta

    def restore(self, path: str) -> dict:
        """Install a checkpoint written by ``save`` into this engine.

        The engine must be freshly constructed from the *same*
        ``FLConfig`` (the stored fingerprint is compared and a mismatch
        is rejected — resuming into a different config would silently
        change the experiment).  Returns the checkpoint meta dict."""
        from repro.checkpoint.serializer import load_checkpoint

        state, meta = load_checkpoint(path, like=self._state_pytree())
        if meta.get("state_version") != self._STATE_VERSION:
            raise ValueError(
                f"engine checkpoint state_version "
                f"{meta.get('state_version')!r} unsupported (expected "
                f"{self._STATE_VERSION}) — was {path!r} written by "
                f"Engine.save?"
            )
        want = self._config_fingerprint()
        got = meta.get("config")
        if got != want:
            keys = sorted(set(want) | set(got or {}))
            diff = [k for k in keys if (got or {}).get(k) != want.get(k)]
            raise ValueError(
                f"checkpoint config does not match this engine's FLConfig "
                f"(differing fields: {diff}) — resuming would change the "
                f"experiment; rebuild the engine with the original config"
            )
        self._install_state(state, meta)
        return meta

    def _install_state(self, state: dict, meta: dict) -> None:
        """Install a verified checkpoint's arrays + scalar carry into
        this engine (split from ``restore`` so execution modes can
        extend the install — the async runtime adds its in-flight
        ledger on top)."""
        self.params = jax.tree.map(jnp.asarray, state["params"])
        self.agg_state = (
            None if state["agg_state"] is None
            else jax.tree.map(jnp.asarray, state["agg_state"])
        )
        self.h_clients = (
            None if state["h_clients"] is None
            else jax.tree.map(jnp.asarray, state["h_clients"])
        )
        self._key = jnp.asarray(state["prng_key"])
        self.strategy.load_state_dict(state["strategy"])
        self._round = int(meta["round"])
        self.comm_mb = float(meta["comm_mb"])
        self.sim_clock = float(meta["sim_clock"])
        self.rng.bit_generator.state = json.loads(meta["rng_state"])
        self.history = {k: list(v) for k, v in meta["history"].items()}
        if self._systems is not None:
            self._systems.load_state_dict(meta.get("systems", {}))
        if self._faults is not None:
            self._faults.load_meta_state(meta["faults"])
            if self._faults.has_stale:
                self._faults.load_stale_state(state["fault_stale"])
        if self._population is not None:
            self._population.load_state_dict(meta["population"])

    # -- per-round emission (history / trackers / checkpoints) ----------
    def _record_history(self, r: RoundResult) -> None:
        """Evaluated rounds land in the in-memory history dict (the
        legacy ``FederatedSimulation.run()`` shape, checkpointed so a
        resumed run's history is contiguous)."""
        if not r.evaluated:
            return
        self.history["round"].append(r.round)
        self.history["test_acc"].append(r.test_acc)
        self.history["test_loss"].append(r.test_loss)
        self.history["comm_mb"].append(r.comm_mb)
        self.history["mean_selected_loss"].append(r.mean_selected_loss)
        self.history["selected"].append(list(r.selected))
        # systems runs gain the simulated clock (time-to-accuracy)
        # and the cumulative drop count; tasks with extra eval
        # metrics (LM perplexity) surface them under their own keys.
        # Keys appear only when active, so the legacy history shape
        # is unchanged for plain runs.
        if self._systems is not None:
            self.history.setdefault("sim_clock", []).append(r.sim_clock)
            self.history.setdefault("n_dropped", []).append(r.n_dropped)
        if self._faults is not None:
            self.history.setdefault("n_faulty", []).append(r.n_faulty)
            self.history.setdefault("n_quarantined", []).append(r.n_quarantined)
        for k, v in (r.metrics or {}).items():
            self.history.setdefault(k, []).append(v)

    def _emit(self, result: RoundResult,
              callback: Callable[[RoundResult], None] | None,
              allow_save: bool = True) -> None:
        """Post-commit fan-out for one round, in durability order:
        history row → callback → trackers → checkpoint policy.  The
        engine state (``_round`` et al.) is already committed when this
        runs, so a checkpoint taken here resumes *after* this round;
        trackers fire before the save (at-least-once delivery — a resume
        may re-log rounds past the last checkpoint).  ``allow_save`` is
        the fused backend's chunk-boundary gate: its state commits per
        chunk, so only chunk-final rounds may trigger a save."""
        self._record_history(result)
        if callback is not None:
            callback(result)
        for t in self.trackers:
            t.log_round(result)
        if allow_save and self.checkpointer is not None:
            with span("save"):
                self.checkpointer.maybe_save(self, result.round)

    def close_trackers(self) -> None:
        for t in self.trackers:
            t.close()

    # -- the canonical round loop --------------------------------------
    def rounds(
        self,
        n_rounds: int | None = None,
        callback: Callable[[RoundResult], None] | None = None,
    ) -> Iterator[RoundResult]:
        """Stream ``RoundResult`` records, one per federated round.

        ``n_rounds=None`` runs the rounds *remaining* to reach
        ``cfg.rounds`` (so a freshly restored engine finishes the
        configured run); pass an explicit count to run chunks."""
        cfg = self.cfg
        if n_rounds is None:
            n_rounds = max(cfg.rounds - self._round, 0)
        key = self._carry_key()

        start = self._round
        for rnd in range(start, start + n_rounds):
            # the span closes before the yield: the consumer's time
            # between rounds lies outside every fl.* span
            with step("round", rnd):
                result, key = self._run_round(rnd, key)
                self._emit(result, callback)
            yield result

    def _run_round(self, rnd: int, key: jax.Array) -> tuple[RoundResult, jax.Array]:
        """One round of ``rounds()`` off the PRNG carry ``key``: poll,
        select, train, aggregate and, on the cadence, evaluate; commits
        the engine state and returns the round's record and the new
        carry."""
        cfg = self.cfg
        key, k_poll, k_train = jax.random.split(key, 3)

        # population mode (DESIGN.md §15): pick the round's resident
        # shards first — they bound what gets polled and gathered
        pop_gate = None
        if self._population is not None:
            _, self._pop_members = self._population.begin_round(rnd)
            pop_gate = self._population.resident_mask()

        with span("poll"):
            losses = self.poll_losses(rnd, k_poll)
        if self._population is not None:
            # fold raw polled member losses into the shard estimates
            # *before* any gating zeroes them out
            self._population.observe(losses)
        # admission gate (DESIGN.md §10/§14/§15): offline,
        # quarantined, or non-resident clients enter every selection
        # path as -inf before select
        losses = self._gated_losses(rnd, losses, extra_gate=pop_gate)
        with span("select"):
            sel = np.asarray(self.select(rnd, losses))

        # deadline / availability outcome of the dispatched cohort:
        # survivors keep their aggregation weight, dropped clients
        # (offline, or stragglers past the deadline) are zeroed
        if self._systems is not None:
            outcome = self._systems.outcome(rnd, sel)
            surv = outcome.survivors
            n_reached = outcome.n_reached
            sim_time, n_dropped = outcome.sim_time, outcome.n_dropped
            with span("train", **self._train_counters(sel)):
                payload, sel_losses = self.local_train(
                    rnd, sel, k_train, survivors=surv
                )
        else:
            surv = sel
            n_reached = len(sel)
            sim_time, n_dropped = 0.0, 0
            with span("train", **self._train_counters(sel)):
                payload, sel_losses = self.local_train(rnd, sel, k_train)

        n_faulty = n_quarantined = 0
        uploaded: float = float(len(surv))
        # fault injection and the validation gate are part of the
        # aggregation stage
        with span("aggregate"):
            if self._faults is not None:
                # quarantined clients picked anyway (loss-blind
                # strategies) are dropped like stragglers, before their
                # update can reach the aggregation
                admit = self._faults.health.admitted(rnd)
                surv = np.asarray(surv, np.int64)
                surv = surv[admit[surv]]
                clients = self._payload_clients(sel)
                arrived = np.isin(clients, surv)
                stacked = self._payload_stack(payload)
                injected, pending = self._faults.process_begin(
                    rnd, clients, arrived, stacked, self.params
                )
                if injected is not stacked:
                    payload = self._payload_replace(payload, injected)
                # Optimistic aggregation (DESIGN.md §14.2): dispatch the
                # aggregation assuming the gate flags nobody — true on
                # every honest round — so it overlaps the gate's flagged
                # read-back instead of serializing behind it.  On the
                # rare flagged round, drop the optimistic result (all
                # aggregate paths rebind state functionally, so the
                # saved refs are the untouched pre-round state) and redo
                # with the true survivors — the exact same call either
                # way, so both orders are bit-identical.
                optimistic = clients[arrived]
                saved = self._aggregate_state()
                self.aggregate(rnd, sel, payload, survivors=optimistic)
                finfo = self._faults.process_finish(pending)
                surv = finfo.survivors
                if len(surv) != len(optimistic):
                    self._restore_aggregate_state(saved)
                    self.aggregate(rnd, sel, payload, survivors=surv)
                n_faulty, n_quarantined = finfo.n_faulty, finfo.n_quarantined
                uploaded = finfo.uploaded
            elif self._systems is not None:
                self.aggregate(rnd, sel, payload, survivors=surv)
            else:
                self.aggregate(rnd, sel, payload)

        # population mode polls only the resident members; everyone
        # else is free on the ledger too
        n_polled = (
            None if self._pop_members is None else len(self._pop_members)
        )
        if self._systems is not None or self._faults is not None:
            # the server observes survivor losses only
            keep = np.isin(sel, surv)
            mean_loss = _mean_loss(np.asarray(sel_losses)[keep])
            self.comm_mb += self.comm.round_mb(
                n_reached, self.strategy.needs_losses,
                m_uploaded=uploaded, n_polled=n_polled,
            )
        else:
            mean_loss = _mean_loss(sel_losses)
            self.comm_mb += self.comm.round_mb(
                len(sel), self.strategy.needs_losses, n_polled=n_polled,
            )
        if self._systems is not None:
            self.sim_clock += sim_time

        # energy ledger (ROADMAP (q)): the dispatched-and-online
        # cohort spends its local-training charge; reported every
        # round (not just evaluated ones) via RoundResult.metrics
        energy = None
        if self._systems is not None and self._systems.tracks_energy:
            energy = self._systems.spend_energy(rnd, sel)

        test_loss = test_acc = metrics = None
        # absolute cadence keyed to the *configured* terminal round,
        # so chunked / resumed rounds() calls evaluate on exactly the
        # schedule one contiguous call would (a per-call final-round
        # force-eval would make resumed histories diverge)
        if rnd % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
            with span("evaluate"):
                test_loss, test_acc = self.evaluate()
                metrics = self.eval_metrics()
        if energy is not None:
            metrics = {**(metrics or {}), **energy}

        self._round = rnd + 1
        self._key = key
        result = RoundResult(
            round=rnd,
            selected=tuple(int(i) for i in surv),
            mean_selected_loss=mean_loss,
            comm_mb=float(self.comm_mb),
            test_loss=test_loss,
            test_acc=test_acc,
            sim_time=float(sim_time),
            sim_clock=float(self.sim_clock),
            n_dropped=int(n_dropped),
            metrics=metrics,
            params_version=rnd + 1,
            n_faulty=int(n_faulty),
            n_quarantined=int(n_quarantined),
        )
        return result, key

    def run(self, rounds: int | None = None, log_every: int = 0) -> dict[str, list]:
        """Legacy consumer: drain ``rounds()`` and return the history
        dict (evaluated rounds only, matching
        ``FederatedSimulation.run()``; the rows themselves are appended
        inside ``rounds()`` so checkpoints capture them too)."""
        for r in self.rounds(rounds):
            if r.evaluated and log_every and (r.round % log_every == 0):
                print(
                    f"[{self.cfg.strategy}] round {r.round:4d} "
                    f"acc={r.test_acc:.4f} loss={r.test_loss:.4f} "
                    f"comm={r.comm_mb:.1f}MB"
                )
        return self.history


class MaskSelectionMixin:
    """Selection hook shared by the mask-gated backends.

    ``select`` asks the strategy for a jit-compatible participation mask
    (``select_mask_jax``); any per-round randomness is drawn host-side
    from ``self.rng`` — the same numpy stream ``HostEngine`` would
    consume — so a host run and a mask-gated run of the same config stay
    in lockstep round by round.  ``_check_mask_backend`` is the
    engine-level guard behind the up-front ``FLConfig`` validation
    (defense in depth for mutated / hand-built configs).
    """

    # backends that aggregate inside the compiled round (the psum) can
    # only realize fedavg semantics; ScaleoutEngine flips this on
    requires_fedavg_aggregator = False

    def _check_mask_backend(self) -> None:
        if not getattr(self.strategy, "supports_compiled_selection", False):
            raise ValueError(
                mask_backend_strategy_error(self.cfg.strategy, self.backend)
            )
        if self.cfg.client_mode != "plain":
            raise ValueError(
                mask_backend_client_mode_error(self.cfg.client_mode, self.backend)
            )
        if self.requires_fedavg_aggregator and self.cfg.aggregator != "fedavg":
            raise ValueError(mask_backend_aggregator_error(self.cfg.aggregator))

    def select(self, rnd: int, losses: np.ndarray) -> np.ndarray:
        mask = to_host(self.strategy.select_mask_jax(losses, self.rng))
        return np.where(mask)[0]


def rounds_to_accuracy(history: dict[str, list], target: float) -> int | None:
    """First evaluated round reaching ``target`` test accuracy (Fig 3 / the
    paper's −22%-rounds claim); None if never reached."""
    for rnd, acc in zip(history["round"], history["test_acc"]):
        if acc >= target:
            return rnd
    return None
