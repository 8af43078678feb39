"""repro.engine — the pluggable federated engine API.

One API, four orthogonal axes, three backends:

- ``registry``     — ``@register_strategy`` / ``@register_aggregator`` /
                     ``@register_client_mode`` / ``@register_task``
                     decorators + lookups
- ``config``       — ``FLConfig`` with validation, ``backend`` and
                     ``task`` switches, and ``to_dict``/``from_dict``
                     round-tripping
- ``base``         — ``Engine`` round protocol (poll_losses → select →
                     local_train → aggregate → evaluate), streaming
                     ``rounds()`` iterator of frozen ``RoundResult``s,
                     plus ``MaskSelectionMixin`` (the shared
                     ``select_mask_jax`` selection path)
- ``host``         — ``HostEngine``: numpy selection + vmapped cohort
- ``compiled``     — ``CompiledEngine``: jitted selection/round with the
                     participation mask gating aggregation (scale-out
                     semantics on one device); trains only the gathered
                     m-client cohort (static shapes via ``jnp.take``)
- ``fused``        — ``FusedEngine``: the compiled semantics with whole
                     round chunks as one donated ``lax.scan``
                     (``FLConfig.fuse_rounds > 0``; selection fully
                     traced via ``select_mask_traced``)
- ``scaleout``     — ``ScaleoutEngine``: the mesh round (client stacks
                     sharded over the ``pod`` axis, the cohort gathered
                     and trained over the pods, selection-weighted
                     psum), plus ``make_scaleout_round`` for the
                     production transformer mesh
- ``aggregators``  — FedAvg / FedNova / FedDyn as stateful objects
- ``client_modes`` — plain / FedProx / FedDyn gradient modifiers
- ``tasks``        — the federated workload: ``classification`` (paper
                     MLP, label histograms — the default) and ``lm``
                     (transformer LM, token histograms); a ``Task``
                     owns model init, loss, eval metric, and the
                     clustering feature
- ``presets``      — named method cells (Table II/III) via
                     ``get_preset(name).make_config(...)``

Strategy × backend support matrix, identical for both tasks (mask-gated
backends need a jit-compatible ``select_mask_jax``; ``fuse_rounds > 0``
additionally needs a fully-traced ``select_mask_traced``; FLConfig
validation enforces both up front):

    strategy          host   compiled   scaleout   fuse_rounds
    ----------------  ----   --------   --------   -----------
    fedlecc            ✓        ✓          ✓            ✓
    fedlecc_adaptive   ✓        ✓          ✓            —
    poc                ✓        ✓          ✓            ✓ (jax rng)
    lossonly           ✓        ✓          ✓            ✓
    clusterrandom      ✓        ✓          ✓            ✓ (jax rng)
    haccs              ✓        ✓          ✓            ✓
    random             ✓        ✓          ✓            ✓ (jax rng)
    fedcls             ✓        —          —            —
    fedcor             ✓        —          —            —

(``compiled``/``scaleout`` additionally require ``client_mode="plain"``;
``scaleout`` aggregates inside the mesh round and ``fuse_rounds``/
``compress_bits`` aggregate inside the compiled round, so those three
require ``aggregator="fedavg"``.)

The async runtime (``FLConfig.async_mode``, DESIGN.md §13) layers a
FedBuff-style event loop over the host/compiled hooks: the server
aggregates the first-``buffer_k`` arrivals per step with
staleness-discounted weights while further cohorts stay in flight
(``AsyncConfig(dispatch="sync")`` is the degenerate lock-step form,
bit-identical to the plain engines).

The fault axis (``FLConfig.faults``, ``repro.faults``, DESIGN.md §14)
injects per-client faults (NaN updates, exploding/sign-flipped/label-
flipped deltas, stale replays, truncated uploads) on a dedicated child
rng stream and defends with a server-side validation gate, robust
aggregators (``trimmed_mean`` / ``coordinate_median``), and the
``ClientHealth`` quarantine ledger — on the host/compiled paths
(eager, fused, and async); ``faults=None`` is bit-identical to an
engine without the subsystem.

The population axis (``FLConfig.population``, ``repro.population``,
DESIGN.md §15) scales the host/compiled engines to cross-device client
counts: the packed client stacks stay host-side behind a
``ClientStore``, a shard-level Algorithm 1 (``HierarchicalSelector``)
picks the round's resident shards, and only resident rows are ever
polled, gathered to device, or charged to the comm ledger — per-round
cost becomes cohort-proportional.  ``PopulationConfig(n_shards=1)`` (and
``population=None``) are bit-identical to the flat engines.

The systems axis (``FLConfig.systems``, ``repro.systems``, DESIGN.md
§10) is orthogonal to all of the above: a ``SystemsConfig`` adds device
profiles, an availability trace, simulated wall-clock per round
(``RoundResult.sim_time``/``sim_clock``), and deadline/over-selection
semantics (stragglers dropped, survivors reweighted) on every backend::

    from repro.engine import FLConfig, SystemsConfig, make_engine

    cfg = FLConfig(strategy="fedlecc", backend="compiled",
                   systems=SystemsConfig(profile="mobile_mix",
                                         availability="markov",
                                         deadline_s=30.0, over_select=1.3))

Typical use::

    from repro.engine import FLConfig, make_engine

    cfg = FLConfig(strategy="fedlecc", backend="scaleout", rounds=30)
    engine = make_engine(cfg, train, test, n_classes=10)
    for result in engine.rounds():
        ...  # result: RoundResult(round, selected, losses, metrics, MB)

    # federated LM: same strategies, same backends, token streams
    cfg = FLConfig(task="lm", strategy="fedlecc", backend="scaleout")

The engines are imported lazily (module ``__getattr__``) so that
registering a component never drags in the training stack.
"""

from repro.engine.config import BACKENDS, FLConfig
from repro.engine.registry import (
    AGGREGATOR_REGISTRY,
    CLIENT_MODE_REGISTRY,
    PRESET_REGISTRY,
    STRATEGY_REGISTRY,
    TASK_REGISTRY,
    Registry,
    list_aggregators,
    list_client_modes,
    list_strategies,
    list_tasks,
    mask_selection_strategies,
    register_aggregator,
    register_client_mode,
    register_strategy,
    register_task,
)

__all__ = [
    "BACKENDS",
    "FLConfig",
    "Registry",
    "STRATEGY_REGISTRY",
    "AGGREGATOR_REGISTRY",
    "CLIENT_MODE_REGISTRY",
    "TASK_REGISTRY",
    "PRESET_REGISTRY",
    "register_strategy",
    "register_aggregator",
    "register_client_mode",
    "register_task",
    "list_strategies",
    "list_aggregators",
    "list_client_modes",
    "list_tasks",
    "Task",
    "build_task",
    "Engine",
    "MaskSelectionMixin",
    "RoundResult",
    "mask_selection_strategies",
    "rounds_to_accuracy",
    "HostEngine",
    "CompiledEngine",
    "FusedEngine",
    "ScaleoutEngine",
    "make_scaleout_round",
    "ExperimentPreset",
    "get_preset",
    "list_presets",
    "register_preset",
    "make_engine",
    "SystemsConfig",
    "PopulationConfig",
    "FaultConfig",
    "AsyncConfig",
    "AsyncHostEngine",
    "AsyncCompiledEngine",
    "CheckpointPolicy",
    "Checkpointer",
    "JsonlTracker",
    "MetricsTracker",
]

_LAZY = {
    "Task": ("repro.engine.tasks", "Task"),
    "build_task": ("repro.engine.tasks", "build_task"),
    "Engine": ("repro.engine.base", "Engine"),
    "MaskSelectionMixin": ("repro.engine.base", "MaskSelectionMixin"),
    "RoundResult": ("repro.engine.base", "RoundResult"),
    "rounds_to_accuracy": ("repro.engine.base", "rounds_to_accuracy"),
    "HostEngine": ("repro.engine.host", "HostEngine"),
    "CompiledEngine": ("repro.engine.compiled", "CompiledEngine"),
    "FusedEngine": ("repro.engine.fused", "FusedEngine"),
    "ScaleoutEngine": ("repro.engine.scaleout", "ScaleoutEngine"),
    "make_scaleout_round": ("repro.engine.scaleout", "make_scaleout_round"),
    "SystemsConfig": ("repro.systems.config", "SystemsConfig"),
    "PopulationConfig": ("repro.population.config", "PopulationConfig"),
    "FaultConfig": ("repro.faults.config", "FaultConfig"),
    "AsyncConfig": ("repro.engine.async_config", "AsyncConfig"),
    "AsyncHostEngine": ("repro.engine.async_engine", "AsyncHostEngine"),
    "AsyncCompiledEngine": ("repro.engine.async_engine", "AsyncCompiledEngine"),
    "ExperimentPreset": ("repro.engine.presets", "ExperimentPreset"),
    "get_preset": ("repro.engine.presets", "get_preset"),
    "list_presets": ("repro.engine.presets", "list_presets"),
    "register_preset": ("repro.engine.presets", "register_preset"),
    "CheckpointPolicy": ("repro.checkpoint.policy", "CheckpointPolicy"),
    "Checkpointer": ("repro.checkpoint.policy", "Checkpointer"),
    "JsonlTracker": ("repro.checkpoint.tracker", "JsonlTracker"),
    "MetricsTracker": ("repro.checkpoint.tracker", "MetricsTracker"),
}


def __getattr__(name):
    try:
        mod, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module 'repro.engine' has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(mod), attr)
    globals()[name] = value
    return value


def make_engine(cfg: FLConfig, train, test, n_classes: int, *,
                resume=None, checkpointer=None, tracker=None, **kwargs):
    """Build the engine selected by ``cfg.backend``
    ("host" | "compiled" | "scaleout").

    ``train``/``test`` are the task's datasets (``repro.data.Dataset``:
    image features + class labels for ``task="classification"``, token /
    next-token sequences for ``task="lm"``); ``n_classes`` is the label
    cardinality (the vocab size for LM).

    Checkpointing / observability (DESIGN.md §12):

    - ``resume=``       — path to a checkpoint written by
      ``Engine.save`` (or a directory of them: the latest is picked);
      the built engine restores it before returning, so the next
      ``rounds()`` call continues the run.  The stored config
      fingerprint must match ``cfg``.
    - ``checkpointer=`` — a ``repro.checkpoint.Checkpointer`` (or a
      directory path, which builds one with the default every-round
      policy); attached as ``engine.checkpointer`` so its policy is
      consulted after every committed round.
    - ``tracker=``      — a ``repro.checkpoint.MetricsTracker`` (or list
      of them) appended to ``engine.trackers``; every streamed
      ``RoundResult`` is logged durably.

    Extra kwargs pass through to the backend constructor:

    - ``mesh=``             — (scaleout only) a mesh with a ``pod`` axis
      replacing the auto-sized default
      (``make_host_mesh(pod=...)`` / ``make_production_mesh``).
    - ``partition_labels=`` — (all backends) task-data override: a (N,)
      integer array the non-IID partitioner splits on instead of the
      task's derived labels (e.g. ground-truth topic ids for LM
      corpora — see ``examples/federated_lm.py``).
    - ``cohort_gather=``    — (compiled only) ``False`` restores the
      legacy every-client-trains path (the scale-out-semantics
      reference); the default gathers and trains just the m-client
      cohort.  Ignored when ``cfg.fuse_rounds > 0`` (fused chunks
      always gather).

    ``cfg.fuse_rounds > 0`` selects the scan-fused execution mode of the
    compiled backend (``FusedEngine``, DESIGN.md §8.6).

    ``cfg.async_mode`` selects the asynchronous runtime (DESIGN.md §13):
    the host/compiled hooks driven by an event loop that buffers the
    first-``k`` arrivals per aggregation step with staleness-discounted
    weights (``AsyncHostEngine`` / ``AsyncCompiledEngine``).
    """
    engine = _build_engine(cfg, train, test, n_classes, **kwargs)
    if checkpointer is not None:
        if isinstance(checkpointer, str):
            from repro.checkpoint import Checkpointer

            checkpointer = Checkpointer(checkpointer)
        engine.checkpointer = checkpointer
    if tracker is not None:
        engine.trackers.extend(
            tracker if isinstance(tracker, (list, tuple)) else [tracker]
        )
    if resume is not None:
        import os

        path = resume
        if os.path.isdir(path):
            # Walk the directory newest-first: a truncated / corrupt
            # latest file (detected loudly as CheckpointError by the
            # serializer) falls back to the previous valid checkpoint
            # with a warning instead of aborting the resume.  Config /
            # structure mismatches stay fatal — falling back would
            # silently change the experiment.
            import warnings

            from repro.checkpoint import CheckpointError, checkpoint_paths

            candidates = checkpoint_paths(path)
            if not candidates:
                raise FileNotFoundError(
                    f"resume directory {path!r} holds no round_*.ckpt files"
                )
            for i, cand in enumerate(candidates):
                try:
                    engine.restore(cand)
                    break
                except CheckpointError as e:
                    if i == len(candidates) - 1:
                        raise CheckpointError(
                            f"no valid checkpoint in {path!r} — every "
                            f"round_*.ckpt file is corrupt (last error: {e})"
                        ) from e
                    warnings.warn(
                        f"skipping corrupt checkpoint {cand!r} "
                        f"({e}); falling back to "
                        f"{candidates[i + 1]!r}",
                        stacklevel=2,
                    )
        else:
            engine.restore(path)
    return engine


def _build_engine(cfg: FLConfig, train, test, n_classes: int, **kwargs):
    if cfg.async_mode is not None:
        # the async runtime wraps the host/compiled hooks with an
        # event-driven loop (DESIGN.md §13); FLConfig validation already
        # rejected incompatible backends/modes
        if cfg.backend == "compiled":
            from repro.engine.async_engine import AsyncCompiledEngine

            return AsyncCompiledEngine(cfg, train, test, n_classes, **kwargs)
        from repro.engine.async_engine import AsyncHostEngine

        return AsyncHostEngine(cfg, train, test, n_classes, **kwargs)
    if cfg.backend == "compiled":
        if cfg.fuse_rounds > 0:
            from repro.engine.fused import FusedEngine

            kwargs.pop("cohort_gather", None)  # fused always gathers
            return FusedEngine(cfg, train, test, n_classes, **kwargs)
        from repro.engine.compiled import CompiledEngine

        return CompiledEngine(cfg, train, test, n_classes, **kwargs)
    if cfg.backend == "scaleout":
        from repro.engine.scaleout import ScaleoutEngine

        return ScaleoutEngine(cfg, train, test, n_classes, **kwargs)
    from repro.engine.host import HostEngine

    return HostEngine(cfg, train, test, n_classes, **kwargs)
