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
import numpy as np
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9
CHIP_SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def topo():
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pod_mesh(topo):
    """The 2x2 host's four chips as a pod=4 mesh."""
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(topo.devices).reshape(4, 1, 1), ("pod", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)


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


@pytest.mark.parametrize("layout", ["default", "row_major"])
def test_population_scaleout_programs_fit_v5e_2x2(pod_mesh, layout):
    """The scale-out cell's two device programs at its size (K=10 000
    clients of 600 samples and 784 features, 18.8 GB of client stacks
    sharded ``P("pod")``, m=100) compile for a v5e 2x2 host and fit each
    chip, with the stacks in the TPU's default layout for the shape
    (clients minor-most, as ``ScaleoutEngine`` places them) and in
    row-major layout.  The loss poll reads each pod's clients in place:
    no collective.  The cohort round gathers the cohort, not the stacks:
    on row-major stacks neither program copies a pod's 4.7 GB block and
    the round's temporaries are a few hundred MB (in the default layout
    each program first copies the block into row-major layout)."""
    import re

    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import make_classification
    from repro.engine import get_preset, make_engine
    from repro.engine.scaleout import pod_cohort_round

    pod, rep = NamedSharding(pod_mesh, P("pod")), NamedSharding(pod_mesh, P())

    def spec(shape, dtype, sharding):
        if layout == "row_major":
            sharding = Format(Layout(tuple(range(len(shape)))), sharding)
        return _spec(shape, dtype, sharding)

    cfg = get_preset("fedlecc").make_config(backend="compiled", n_clients=8, m=4,
                                            seed=0, strategy_kwargs={"J": 2})
    engine = make_engine(cfg, make_classification(800, seed=0),
                         make_classification(100, seed=1), n_classes=10)
    K, N, F, C = 10_000, 600, 784, 100
    params = jax.tree.map(lambda x: spec(x.shape, x.dtype, rep), engine.params)
    stacks = (spec((K, N, F), jnp.float32, pod), spec((K, N), jnp.int32, pod),
              spec((K, N), jnp.float32, pod))
    key = spec((2,), jnp.uint32, rep)
    poll = engine._poll_losses.lower(params, *stacks, key).compile()
    cohort = pod_cohort_round(
        engine._apply_fn, engine._loss_fn, pod_mesh, lr=cfg.lr,
        max_steps=engine.max_steps, batch_size=cfg.batch_size,
    ).lower(params, *stacks, spec((K,), jnp.int32, rep),
            spec((K,), jnp.float32, rep), spec((C,), jnp.int32, rep),
            spec((), jnp.int32, rep), spec((K,), jnp.bool_, rep), key).compile()
    collective = re.compile(r"= \S+ (all-gather|all-reduce|all-to-all|"
                            r"reduce-scatter|collective-permute)")
    assert not collective.search(poll.as_text())
    assert collective.search(cohort.as_text())  # the gather and the psum
    for program in (poll, cohort):
        mem = program.memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        print(f"{used / 1e9:.2f} GB a chip, {mem.temp_size_in_bytes / 1e9:.2f} temp")
        assert 4.7e9 < used < V5E_HBM_BYTES
    if layout == "row_major":
        whole_block = re.compile(r"= \S*\[2500,600,784\]\S* copy\(")
        assert not any(whole_block.search(p.as_text()) for p in (poll, cohort))
        assert cohort.memory_analysis().temp_size_in_bytes < 0.5e9
