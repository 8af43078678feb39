"""repro.engine: registries, FLConfig validation/round-trip, the typed
round protocol, and host ↔ compiled backend equivalence."""

import dataclasses

import numpy as np
import pytest

from conftest import fl_cfg as _cfg, lm_fl_cfg as _lm_cfg
from repro.engine import (
    FLConfig,
    Registry,
    RoundResult,
    make_engine,
    list_aggregators,
    list_client_modes,
    list_strategies,
    list_tasks,
)
from repro.engine.aggregators import get_aggregator
from repro.engine.presets import get_preset, list_presets


# ---------------------------------------------------------------- registry
def test_registries_populated():
    assert "fedlecc" in list_strategies() and "random" in list_strategies()
    assert list_aggregators() == [
        "coordinate_median", "fedavg", "feddyn", "fednova", "trimmed_mean",
    ]
    assert list_client_modes() == ["feddyn", "fedprox", "plain"]
    assert list_tasks() == ["classification", "lm"]


def test_custom_registration_does_not_hide_builtins():
    # registering a custom component must not short-circuit provider
    # population (regression: the populate gate was "items non-empty",
    # so a custom-first registration hid every built-in)
    from repro.engine.registry import STRATEGY_REGISTRY, register_strategy

    @register_strategy("_test_custom")
    class Custom:
        pass

    try:
        names = list_strategies()
        assert "_test_custom" in names and "fedlecc" in names
    finally:
        del STRATEGY_REGISTRY["_test_custom"]  # legacy dict-style del
    # the gate is an explicit flag, not an item-count check
    reg = Registry("widget-" + "x")
    reg.register("mine")(Custom)
    assert reg.names() == ["mine"] and reg._populated


def test_same_component_reregistration_allowed():
    reg = Registry("widget")

    def make():
        @reg.register("a")
        class A:
            pass

        return A

    first, second = make(), make()  # same qualname/module, new class objects
    assert reg["a"] is second  # reload-style overwrite, no ValueError


def test_registry_decorator_and_errors():
    reg = Registry("widget")

    @reg.register("a")
    class A:
        pass

    assert reg["a"] is A and "a" in reg and len(reg) == 1
    with pytest.raises(ValueError, match="duplicate"):
        reg.register("a")(int)
    with pytest.raises(KeyError, match="unknown widget 'b'"):
        reg["b"]
    assert reg.build("a").__class__ is A


# ------------------------------------------------------------------ config
def test_flconfig_validation():
    with pytest.raises(ValueError, match="backend"):
        _cfg(backend="gpu")
    with pytest.raises(ValueError, match="unknown strategy"):
        _cfg(strategy="nope")
    with pytest.raises(ValueError, match="unknown aggregator"):
        _cfg(aggregator="nope")
    with pytest.raises(ValueError, match="unknown client_mode"):
        _cfg(client_mode="nope")
    with pytest.raises(ValueError, match="unknown task"):
        _cfg(task="vision")
    with pytest.raises(ValueError, match="task_kwargs must be a dict"):
        _cfg(task_kwargs=[1, 2])
    with pytest.raises(ValueError, match="m must be"):
        _cfg(m=50)  # > n_clients
    with pytest.raises(ValueError, match="partition"):
        _cfg(partition="iid")


def test_flconfig_dict_round_trip():
    cfg = _cfg(backend="compiled", alpha_dirichlet=0.3, hidden=(32, 16))
    d = cfg.to_dict()
    assert d["hidden"] == [32, 16]  # JSON-safe
    import json

    restored = FLConfig.from_dict(json.loads(json.dumps(d)))
    assert restored == cfg
    assert restored.hidden == (32, 16)
    with pytest.raises(ValueError, match="unknown FLConfig keys"):
        FLConfig.from_dict({**d, "bogus": 1})


def test_flconfig_lm_task_round_trip():
    """task / task_kwargs (nested dicts) survive the JSON round-trip."""
    import json

    cfg = _lm_cfg(backend="scaleout")
    assert cfg.task == "lm"
    d = cfg.to_dict()
    restored = FLConfig.from_dict(json.loads(json.dumps(d)))
    assert restored == cfg
    assert restored.task_kwargs["overrides"]["d_model"] == 32


# ----------------------------------------------------------------- presets
def test_presets_build_configs():
    assert "fedlecc" in list_presets()
    assert set(list_presets(fast_only=True)) == {"fedavg", "poc", "fedlecc"}
    p = get_preset("feddyn")
    cfg = p.make_config(n_clients=12, m=4, rounds=2, hidden=(16,))
    assert cfg.aggregator == "feddyn" and cfg.client_mode == "feddyn"
    assert cfg.mu == pytest.approx(0.1)
    # overrides win
    assert get_preset("fedlecc").make_config(
        n_clients=12, m=4, strategy_kwargs={"J": 2}
    ).strategy_kwargs == {"J": 2}


# ---------------------------------------------------- typed round protocol
def test_rounds_stream_and_callback(data):
    train, test = data
    engine = make_engine(_cfg(eval_every=2), train, test, n_classes=10)
    seen = []
    results = list(engine.rounds(3, callback=seen.append))
    assert [r.round for r in results] == [0, 1, 2]
    assert results == seen
    for r in results:
        assert isinstance(r, RoundResult)
        assert len(r.selected) == 4 and len(set(r.selected)) == 4
        assert np.isfinite(r.mean_selected_loss)
        with pytest.raises(dataclasses.FrozenInstanceError):
            r.round = 99
    # eval_every=2 over 3 rounds: rounds 0, 2 evaluated (2 also last)
    assert [r.evaluated for r in results] == [True, False, True]
    assert results[1].test_acc is None
    # the ledger is monotone and matches the engine's running total
    assert results[-1].comm_mb == pytest.approx(engine.comm_mb)


def test_chunked_rounds_keep_absolute_eval_cadence(data):
    """rounds(5)+rounds(5) must evaluate on the *identical* absolute
    schedule as rounds(10): the cadence plus the configured terminal
    round, never a chunk's own last round (DESIGN.md §12 — resumed runs
    must reproduce contiguous histories exactly)."""
    train, test = data

    def evaluated_rounds(chunks):
        engine = make_engine(_cfg(rounds=10, eval_every=5), train, test,
                             n_classes=10)
        out = []
        for c in chunks:
            out += [r.round for r in engine.rounds(c) if r.evaluated]
        return out, engine

    contiguous, e1 = evaluated_rounds([10])
    chunked, e2 = evaluated_rounds([5, 5])
    assert contiguous == [0, 5, 9]
    assert chunked == contiguous  # no per-call final-round force-eval
    # and the training trajectory itself is identical
    import jax
    import jax.numpy as jnp

    err = max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree.leaves(e1.params), jax.tree.leaves(e2.params))
    )
    assert err == 0.0


def test_run_history_matches_legacy_shape(data):
    train, test = data
    engine = make_engine(_cfg(), train, test, n_classes=10)
    h = engine.run()
    assert sorted(h) == ["comm_mb", "mean_selected_loss", "round",
                         "selected", "test_acc", "test_loss"]
    assert h["round"] == [0, 1, 2]
    assert all(len(s) == 4 for s in h["selected"])


def test_feddyn_state_lives_in_aggregator(data):
    train, test = data
    cfg = _cfg(strategy="random", aggregator="feddyn", client_mode="feddyn",
               mu=0.1, rounds=2)
    engine = make_engine(cfg, train, test, n_classes=10)
    assert engine.aggregator.needs_state and engine.agg_state is not None
    assert engine.client_mode.needs_h and engine.h_clients is not None
    import jax

    before = jax.tree.leaves(engine.agg_state)[0].copy()
    list(engine.rounds(2))
    after = jax.tree.leaves(engine.agg_state)[0]
    assert float(np.abs(np.asarray(after - before)).max()) > 0  # h moved


def test_aggregator_objects_standalone(data):
    cfg = _cfg(strategy="random", aggregator="fedavg")
    agg = get_aggregator("fedavg", cfg)
    assert agg.init_state(None) is None and not agg.needs_state


# ----------------------------------------------------- task-axis engine
# Golden values for the canonical tiny config on the CPU: the default
# task="classification" path must reproduce them exactly — the Task
# refactor (commit 3dcf2ea) was a pure re-plumbing.  They pin the PRNG
# bit layout of the installed JAX, so they are re-pinned whenever JAX
# changes its default random bits.  Pinned under JAX 0.9.0 (default
# jax_threefry_partitionable=True).
_GOLDEN_SELECTED = [(5, 8, 9, 10), (5, 6, 8, 9), (5, 6, 8, 9)]
_GOLDEN_W0_ROW0 = [0.1767926663160324, -0.1613832265138626,
                   -0.13282738626003265, -0.20708005130290985]


def test_default_task_matches_pre_refactor_golden(data):
    """Same selections and final params (one seed) as before the Task
    registry axis existed — the default config is a zero-behavior-change
    refactor."""
    import jax

    train, test = data
    engine = make_engine(_cfg(), train, test, n_classes=10)
    results = list(engine.rounds(3))
    assert [r.selected for r in results] == _GOLDEN_SELECTED
    w0 = next(np.asarray(x) for x in jax.tree.leaves(engine.params)
              if np.asarray(x).ndim == 2)
    np.testing.assert_allclose(w0[0, :4], _GOLDEN_W0_ROW0, atol=1e-6)


def test_task_owns_clustering_features(data, lm_data):
    """classification clusters on (K, n_classes) label histograms; lm
    clusters on (K, hist_bins) token histograms — both row-normalized."""
    train, test = data
    eng = make_engine(_cfg(), train, test, n_classes=10)
    assert eng.hists.shape == (12, 10)
    lm_train, lm_test = lm_data
    lm_eng = make_engine(_lm_cfg(), lm_train, lm_test, n_classes=32)
    assert lm_eng.hists.shape == (8, 16)  # hist_bins=16 in the tiny cfg
    for h in (eng.hists, lm_eng.hists):
        np.testing.assert_allclose(h.sum(axis=1), 1.0, atol=1e-9)


def test_lm_task_rejects_non_token_models():
    """Modality stubs and the MTP head are not wired into the federated
    loss — the task must fail at construction, not mid-round."""
    with pytest.raises(ValueError, match="input_mode"):
        _lm_cfg(task_kwargs={"model": "stablelm-3b",
                             "overrides": {"input_mode": "frames"}})
    with pytest.raises(ValueError, match="MTP"):
        _lm_cfg(task_kwargs={"model": "stablelm-3b",
                             "overrides": {"mtp": True}})
    # unknown model names / bad kwargs surface as ValueError, keeping
    # the fail-with-ValueError-at-construction contract
    with pytest.raises(ValueError, match="invalid task_kwargs"):
        _lm_cfg(task_kwargs={"model": "nope"})
    with pytest.raises(ValueError, match="invalid task_kwargs"):
        _lm_cfg(task_kwargs={"bogus_kwarg": 1})


def test_partition_labels_override(data):
    """The make_engine task-data override: a caller-provided label axis
    drives the non-IID split instead of the task's derived labels."""
    train, test = data
    default = make_engine(_cfg(), train, test, n_classes=10)
    override = make_engine(_cfg(), train, test, n_classes=10,
                           partition_labels=np.asarray(train.y))
    for a, b in zip(default.client_idx, override.client_idx):
        np.testing.assert_array_equal(a, b)  # same labels → same split
    with pytest.raises(ValueError, match="partition_labels"):
        make_engine(_cfg(), train, test, n_classes=10,
                    partition_labels=np.zeros(3, np.int64))


# ------------------------------------------------- cross-backend parity
def test_backend_masks_identical_for_same_losses(data):
    """HostEngine and CompiledEngine must select the same participation
    set for fedlecc given the same labels/losses (engine-level extension
    of the fedlecc_select ↔ fedlecc_select_jax property)."""
    train, test = data
    host = make_engine(_cfg(backend="host"), train, test, n_classes=10)
    comp = make_engine(_cfg(backend="compiled"), train, test, n_classes=10)
    np.testing.assert_array_equal(host.strategy.labels, comp.strategy.labels)
    rng = np.random.default_rng(3)
    for rnd in range(4):
        losses = rng.uniform(0.1, 5.0, 12).astype(np.float32)
        np.testing.assert_array_equal(
            host.select(rnd, losses), comp.select(rnd, losses)
        )


def test_backends_run_fedlecc_end_to_end_equivalently(data):
    """Both backends run >=2 full fedlecc rounds; per-client fold_in keys
    + exact-zero gating make the compiled round numerically match the
    host round (selections identical, params equal to f32 tolerance)."""
    import jax
    import jax.numpy as jnp

    train, test = data
    host = make_engine(_cfg(backend="host"), train, test, n_classes=10)
    comp = make_engine(_cfg(backend="compiled"), train, test, n_classes=10)
    rh = list(host.rounds(3))
    rc = list(comp.rounds(3))
    assert len(rh) == len(rc) == 3
    for a, b in zip(rh, rc):
        assert a.selected == b.selected
        assert a.comm_mb == pytest.approx(b.comm_mb)
        assert a.mean_selected_loss == pytest.approx(b.mean_selected_loss,
                                                     rel=1e-4)
    err = max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree.leaves(host.params),
                        jax.tree.leaves(comp.params))
    )
    assert err < 1e-5


def test_mask_backends_reject_unsupported_combos_at_config_time():
    """A strategy without select_mask_jax on a mask-gated backend must
    fail at FLConfig construction (not mid-engine-build), and the error
    must name the strategies that do support it."""
    from repro.engine import mask_selection_strategies

    supported = mask_selection_strategies()
    assert "fedlecc" in supported and "poc" in supported
    for backend in ("compiled", "scaleout"):
        with pytest.raises(ValueError, match="jit-compatible selection") as ei:
            _cfg(backend=backend, strategy="fedcor")
        for name in supported:  # actionable: lists every working strategy
            assert name in str(ei.value)
        with pytest.raises(ValueError, match="client_mode"):
            _cfg(backend=backend, client_mode="fedprox", mu=0.1)
    # previously-rejected-at-engine-build combos now never construct;
    # strategies WITH a jit mask still build fine on both backends
    _cfg(backend="compiled", strategy="poc")
    _cfg(backend="scaleout", strategy="haccs")


def test_scaleout_backend_requires_fedavg_aggregator():
    # rejected up front at config construction, like the strategy check
    with pytest.raises(ValueError, match="fedavg"):
        _cfg(backend="scaleout", aggregator="fednova")


def test_scaleout_backend_rejects_mesh_without_pod_axis(data):
    train, test = data
    from repro.launch.mesh import make_host_mesh

    with pytest.raises(ValueError, match="pod"):
        make_engine(_cfg(backend="scaleout"), train, test, n_classes=10,
                    mesh=make_host_mesh(data=1, model=1))


# --------------------------------------------------------- legacy shim
def test_federated_simulation_shim_deprecated_but_working(data):
    train, test = data
    from repro.federated import FederatedSimulation
    from repro.federated.simulation import FLConfig as ShimConfig

    assert ShimConfig is FLConfig
    with pytest.warns(DeprecationWarning, match="FederatedSimulation"):
        sim = FederatedSimulation(_cfg(rounds=2), train, test, n_classes=10)
    h = sim.run()
    assert len(h["test_acc"]) >= 1 and np.isfinite(h["test_loss"][-1])
