"""The compiled backend's aggregation is one jitted program a round
(``CompiledEngine._aggregate_round``): the same numbers as the eager
reference (mask-gated weights, the cohort slice, the bound aggregator's
``aggregate`` and ``update_state``) for every aggregator the backend
takes, with and without compression, cohort gathering and survivor
drops; one compile whatever the survivor count; one dispatch a round."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import fl_cfg
from repro.core.selection import selection_weights
from repro.engine import make_engine
from repro.federated.compression import compressed_fedavg

AGGREGATORS = ("fedavg", "fednova", "feddyn", "trimmed_mean",
               "coordinate_median")
SEL = np.array([1, 4, 6, 9], np.int64)
SURVIVORS = {"all": None, "drop": np.array([4, 9], np.int64)}


def _engine(data, cohort_gather=True, **kw):
    train, test = data
    return make_engine(fl_cfg(backend="compiled", **kw), train, test,
                       n_classes=10, cohort_gather=cohort_gather)


def _eager_reference(eng, sel, payload, survivors):
    """The pre-jit aggregation, op by op: what ``aggregate`` must match."""
    weight_idx = sel if survivors is None else survivors
    mask = jnp.zeros((eng.cfg.n_clients,), jnp.bool_).at[
        jnp.asarray(weight_idx)].set(True)
    w_full = selection_weights(mask, eng._sizes_j)
    sel_j = jnp.asarray(sel)
    if eng.cfg.compress_bits:
        cohort = payload if eng.cohort_gather else jax.tree.map(
            lambda s: jnp.take(s, sel_j, axis=0), payload)
        params, qerr = compressed_fedavg(
            cohort, eng.params, jnp.take(w_full, sel_j), eng._qkey,
            bits=eng.cfg.compress_bits)
        return params, eng.agg_state, float(qerr)
    if eng.cohort_gather:
        w = jnp.take(w_full, sel_j)
        taus = jnp.asarray(eng.taus[sel], jnp.float32)
    else:
        w, taus = w_full, jnp.asarray(eng.taus, jnp.float32)
    n = len(weight_idx)
    params = eng.aggregator.aggregate(payload, eng.params, w, taus,
                                      eng.agg_state, n_selected=n)
    state = eng.aggregator.update_state(eng.agg_state, payload, eng.params,
                                        w, n_selected=n)
    return params, state, None


def _assert_trees_close(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6,
                                   rtol=0)


def _check_against_eager(eng, survivors):
    key = jax.random.PRNGKey(7)
    payload, _ = eng.local_train(0, SEL, key)
    if eng.aggregator.needs_state:
        # a non-zero server state, so the state's use is tested too
        eng.agg_state = jax.tree.map(
            lambda h: 0.01 * jnp.cos(jnp.arange(h.size, dtype=h.dtype)
                                     ).reshape(h.shape), eng.agg_state)
    want_params, want_state, want_qerr = _eager_reference(
        eng, SEL, payload, survivors)
    assert getattr(eng.aggregator, "_last_mean", None) is None
    eng.aggregate(0, SEL, payload, survivors=survivors)
    _assert_trees_close(eng.params, want_params)
    _assert_trees_close(eng.agg_state, want_state)
    # the FedDyn stash is consumed inside the trace: no tracer survives
    assert getattr(eng.aggregator, "_last_mean", None) is None
    if want_qerr is not None:
        assert abs(eng.last_quant_error - want_qerr) <= 1e-6


@pytest.mark.parametrize("survivors", sorted(SURVIVORS))
@pytest.mark.parametrize("gather", [True, False])
@pytest.mark.parametrize("aggregator", AGGREGATORS)
def test_jitted_aggregation_matches_eager(aggregator, gather, survivors,
                                          data):
    eng = _engine(data, gather, aggregator=aggregator, mu=0.1)
    _check_against_eager(eng, SURVIVORS[survivors])


@pytest.mark.parametrize("survivors", sorted(SURVIVORS))
@pytest.mark.parametrize("gather", [True, False])
def test_jitted_compressed_aggregation_matches_eager(gather, survivors, data):
    eng = _engine(data, gather, compress_bits=8)
    _check_against_eager(eng, SURVIVORS[survivors])


def test_nobody_uploaded_leaves_the_model_standing(data):
    eng = _engine(data)
    payload, _ = eng.local_train(0, SEL, jax.random.PRNGKey(0))
    before = eng.params
    eng.aggregate(0, SEL, payload, survivors=np.array([], np.int64))
    assert eng.params is before
    assert eng._aggregate_round._cache_size() == 0


def test_survivor_count_never_retraces(data):
    """Deadline drops change the survivor count from round to round; the
    survivor mask keeps its (K,) shape, so the program compiles once."""
    sys_kw = dict(profile="mobile_mix", availability="markov",
                  availability_kwargs={"p_drop": 0.2, "p_join": 0.6},
                  deadline_s=2.0, over_select=1.5, jitter_sigma=0.1)
    eng = _engine(data, systems=sys_kw, rounds=8, eval_every=100)
    rs = list(eng.rounds(8))
    assert len({len(r.selected) for r in rs}) > 1
    assert eng._aggregate_round._cache_size() == 1


@pytest.mark.parametrize("systems", [False, True])
def test_one_aggregation_program_per_round(systems, data):
    """``aggregate`` dispatches the one program once a round (none on a
    round nobody uploaded in)."""
    kw = {}
    if systems:
        kw["systems"] = dict(profile="zipf_compute", availability="bernoulli",
                             availability_kwargs={"p": 0.7}, deadline_s=2.0,
                             over_select=1.5, jitter_sigma=0.1)
    eng = _engine(data, rounds=4, eval_every=100, **kw)
    program, calls = eng._aggregate_round, []

    def counted(*args):
        calls.append(1)
        return program(*args)

    eng._aggregate_round = counted
    rs = list(eng.rounds(4))
    uploaded = sum(len(r.selected) > 0 for r in rs)
    assert len(calls) == uploaded and uploaded >= 3
    assert program._cache_size() == 1
