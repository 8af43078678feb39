"""Main-path programs compiled for a described TPU v5e — no chip needed.

The TPU compiler refuses here what interpret mode and the CPU backend
accept: unaligned kernel slices, too much fast memory, programs that do
not fit the device.  The topology is described inside a module fixture
(only the worker running this file loads the TPU library), and every
test of the chip's compiler lives in this one file.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""

import importlib.util
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9
CHIP_SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside the checkout
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,K,C", [(100, 100, 10), (4096, 10_000, 64)])
def test_hellinger_strip_kernel_compiles_for_v5e(one_chip, B, K, C):
    """The strip FedLECC's clustering builds on TPU is the Pallas kernel,
    compiled (not interpreted) by the chip's compiler."""
    from repro.kernels.hellinger.ops import hellinger_strip_pallas

    compiled = hellinger_strip_pallas.lower(
        _spec((B, C), jnp.float32, one_chip), _spec((K, C), jnp.float32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paper_cohort_train_compiles_for_v5e(one_chip):
    """The compiled backend's cohort-train step for the paper MLP
    (K=100 clients, m=10 per round) compiles for one v5e and fits it."""
    from repro.data import make_classification
    from repro.engine import get_preset, make_engine

    cfg = get_preset("fedlecc").make_config(backend="compiled", seed=0)
    assert (cfg.n_clients, cfg.m, cfg.hidden) == (100, 10, (200, 200))
    train = make_classification(20_000, seed=0)
    test = make_classification(200, seed=1)
    engine = make_engine(cfg, train, test, n_classes=10)
    params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                          engine.params)
    lowered = engine._train_cohort.lower(
        params, _spec((cfg.m,), jnp.int32, one_chip),
        _spec((2,), jnp.uint32, one_chip),
    )
    stacked, losses = lowered.out_info
    assert losses.shape == (cfg.m,)
    assert all(s.shape[0] == cfg.m for s in jax.tree.leaves(stacked))
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


@pytest.fixture(scope="module")
def full_width_lm():
    """``chip_smoke.py`` phase c's engine, xlstm-125m at its published
    width, and the two largest programs of its round."""
    spec = importlib.util.spec_from_file_location("chip_smoke", CHIP_SMOKE)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    return cs, cs.lm_programs(cs.lm_engine(**cs.LM_SIZES))


@pytest.mark.parametrize("program", ["loss poll", "cohort train"])
def test_full_width_lm_program_fits_v5e(one_chip, full_width_lm, program):
    """Phase c's loss poll (K=16 clients, 8 sequences each) and cohort
    train (m=4) compile for one v5e and fit it."""
    cs, programs = full_width_lm
    fn, args = programs[program]
    specs = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), args)
    used = cs.program_bytes(fn, specs)
    print(f"{program}: {used}")
    assert 0 < sum(used.values()) < V5E_HBM_BYTES
