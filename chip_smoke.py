"""Smoke run of the federated round on a TPU: the quickest proof that the
system still starts on the chip.

Everything runs in this one process, through ``make_engine(...)`` and
``engine.rounds()``:

  a. device check — the platform must be ``tpu``; there is no CPU fallback;
  b. the paper's FedLECC round (``get_preset("fedlecc")``: K=100 clients,
     m=10, the (200, 200) MLP, SGD lr 0.005, shards at HD 0.9) on
     MNIST-shaped synthetic data, 5 rounds on ``backend="compiled"``,
     then on ``"host"`` and fused (``fuse_rounds=5``) from the same seed:
     the same clients every round and allclose params; the Hellinger
     strip behind FedLECC's clustering must be the compiled Pallas kernel;
  c. federated LM at published width: xlstm-125m (12 layers, d_model
     768, vocab 50 304), K=16, m=4, 512-token sequences, 2 rounds, the
     loss poll cut to 8 sequences per client (``LM_SIZES``);
  d. with ``--four-chips`` and nothing else: phase b's config on
     ``backend="scaleout"`` over a pod=4 mesh of four chips, checked
     against ``backend="compiled"`` on one chip.

Any failed check raises and the script exits non-zero.  The last line
of standard output is, on success only,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py               # one chip: phases a, b, c
    python chip_smoke.py --four-chips  # four chips: phases a, d
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data import make_classification  # noqa: E402
from repro.data.synthetic import make_token_stream  # noqa: E402
from repro.engine import get_preset, make_engine  # noqa: E402

# the cross-backend tolerance of tests/test_backend_conformance.py
PARAMS_ATOL = 1e-5
LM_VOCAB = 50_304


class SmokeCheckFailed(Exception):
    """A phase produced a wrong result."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckFailed(what)


def _max_abs_diff(a, b) -> float:
    return max(
        float(jnp.max(jnp.abs(jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _run(cfg, train, test, n_classes: int, **engine_kwargs):
    engine = make_engine(cfg, train, test, n_classes=n_classes, **engine_kwargs)
    return engine, list(engine.rounds())


def _check_selections(results, m: int, name: str) -> None:
    for r in results:
        _require(len(r.selected) == m,
                 f"{name}: round {r.round} selected {len(r.selected)} clients, not m={m}")
    evaluated = [r for r in results if r.evaluated]
    _require(bool(evaluated) and all(np.isfinite(r.test_acc) for r in evaluated),
             f"{name}: accuracy is missing or not finite")


def _check_matches(name, ref, other) -> float:
    """Same clients every round and allclose final params; returns the
    largest parameter difference."""
    (ref_engine, ref_results), (engine, results) = ref, other
    _require(len(ref_results) == len(results), f"{name}: round counts differ")
    for a, b in zip(ref_results, results):
        _require(a.selected == b.selected,
                 f"{name}: round {a.round} selected {a.selected} vs {b.selected}")
    diff = _max_abs_diff(ref_engine.params, engine.params)
    print(f"  {name}: same selections in {len(results)} rounds, "
          f"max |params diff| = {diff:.3e} (atol {PARAMS_ATOL:g})")
    _require(diff <= PARAMS_ATOL, f"{name}: final params differ by {diff:.3e}")
    return diff


def _paper_setup(n_train: int, n_test: int, n_features: int, rounds: int,
                 overrides: dict):
    """Phases b and d: MNIST-shaped data from the seed, and the paper's
    FedLECC config maker (``overrides`` shrink the ``FLConfig``)."""
    train = make_classification(n_train, n_features=n_features, seed=0)
    test = make_classification(n_test, n_features=n_features, seed=1)
    preset = get_preset("fedlecc")

    def cfg(**kw):
        return preset.make_config(rounds=rounds, seed=0, **overrides, **kw)

    return train, test, cfg


def paper_round(*, n_train: int = 60_000, n_test: int = 10_000,
                n_features: int = 784, rounds: int = 5, **overrides) -> None:
    """Phase b: the paper's FedLECC config on compiled, host and fused
    backends from one seed."""
    train, test, cfg = _paper_setup(n_train, n_test, n_features, rounds,
                                    overrides)
    compiled_cfg = cfg(backend="compiled")
    print(f"[b] fedlecc K={compiled_cfg.n_clients} m={compiled_cfg.m} "
          f"hidden={compiled_cfg.hidden} rounds={rounds} "
          f"train={n_train}x{n_features}")
    compiled = _run(compiled_cfg, train, test, 10)
    _check_selections(compiled[1], compiled_cfg.m, "compiled")
    host = _run(cfg(backend="host"), train, test, 10)
    _check_selections(host[1], compiled_cfg.m, "host")
    _check_matches("host vs compiled", compiled, host)
    fused = _run(cfg(backend="compiled", fuse_rounds=rounds), train, test, 10)
    _check_selections(fused[1], compiled_cfg.m, "fused")
    _check_matches("fused vs compiled", compiled, fused)
    accs = [r.test_acc for r in compiled[1] if r.evaluated]
    print(f"  compiled test accuracy: {accs}")


# Phase c's sizes.  ``eval_samples`` (sequences each client scores in
# the loss poll) is cut from the FLConfig default of 128 to 8: the poll
# vmaps one forward over all K clients and holds K x eval_samples x 512
# x 50 304 float32 logits at once, which the v5e compiler refuses past
# 8 per client (16 needs a 26.4 GB buffer, 128 needs 211 GB).
LM_SIZES = dict(n_clients=16, m=4, seq_len=512, seqs_per_client=32,
                batch_size=8, max_steps_cap=2, rounds=2, eval_samples=8)


def lm_engine(*, n_clients: int, m: int, seq_len: int, seqs_per_client: int,
              batch_size: int, max_steps_cap: int, rounds: int,
              eval_samples: int, vocab: int = LM_VOCAB, task_kwargs=None):
    """Phase c's engine: federated LM through the compiled backend, on
    token streams from the seed."""
    task_kwargs = task_kwargs or {"model": "xlstm-125m", "reduced": False}
    train = make_token_stream(n_clients * seqs_per_client, seq_len, vocab, seed=0)
    test = make_token_stream(2 * batch_size, seq_len, vocab, seed=1)
    cfg = get_preset("fedlecc").make_config(
        task="lm", task_kwargs=task_kwargs, backend="compiled",
        n_clients=n_clients, m=m, rounds=rounds, batch_size=batch_size,
        max_steps_cap=max_steps_cap, eval_samples=eval_samples,
        eval_every=1, seed=0,
    )
    return make_engine(cfg, train, test, n_classes=vocab)


def lm_programs(engine) -> dict:
    """The LM round's two largest programs with arguments of the shapes
    a round passes them: the K-wide loss poll and the m-client cohort
    train."""
    key = jax.random.PRNGKey(0)
    cohort = jnp.arange(engine.cfg.m, dtype=jnp.int32)
    return {
        "loss poll": (engine._poll_losses,
                      (engine.params, engine.xs, engine.ys, engine.mask, key)),
        "cohort train": (engine._train_cohort, (engine.params, cohort, key)),
    }


def program_bytes(fn, args) -> dict:
    """Device bytes the compiler plans for one program: arguments,
    outputs and temporaries.  ``args`` may be arrays or shape specs."""
    mem = fn.lower(*args).compile().memory_analysis()
    return {"arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes}


def _peak_bytes():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def lm_round(**sizes) -> dict:
    """Phase c: full-width xlstm-125m rounds on the compiled backend.
    Returns the timings and device bytes (information, not metrics)."""
    sizes = {**LM_SIZES, **sizes}
    m, rounds = sizes["m"], sizes["rounds"]
    live = sum(a.nbytes for a in jax.live_arrays())
    t0 = time.perf_counter()
    engine = lm_engine(**sizes)
    build_s = time.perf_counter() - t0
    mc = engine.task.model_cfg
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.params))
    print(f"[c] {mc.name}: {mc.n_layers} layers, d_model {mc.d_model}, "
          f"vocab {mc.vocab}, {n_params:,} params; K={sizes['n_clients']} "
          f"m={m} seq {sizes['seq_len']} batch {sizes['batch_size']} "
          f"steps<={sizes['max_steps_cap']} "
          f"polled sequences/client {sizes['eval_samples']}")
    before = engine.params
    round_s, results = [], []
    it = engine.rounds()
    for _ in range(rounds):
        t0 = time.perf_counter()
        results.append(next(it))  # evaluated every round: ends in a host sync
        round_s.append(time.perf_counter() - t0)
    _check_selections(results, m, "lm")
    losses = [r.test_loss for r in results]
    _require(all(np.isfinite(v) for v in losses), f"lm: test loss not finite: {losses}")
    moved = _max_abs_diff(before, engine.params)
    _require(moved > 0, "lm: params did not change")
    # round 0 includes the compilation of every program of the round
    info = {"params": n_params, "live_bytes_before": live, "build_s": build_s,
            "round_s": round_s, "test_loss": losses, "max_param_change": moved,
            "peak_bytes_in_use": _peak_bytes(),
            "compiled_bytes": {name: program_bytes(fn, args)
                               for name, (fn, args) in lm_programs(engine).items()}}
    print(f"  information, not metrics: {json.dumps(info)}")
    return info


def four_chip_round(*, n_train: int = 60_000, n_test: int = 10_000,
                    n_features: int = 784, rounds: int = 5, **overrides) -> None:
    """Phase d: the paper's config on ``backend="scaleout"`` over a pod=4
    mesh against ``backend="compiled"`` on one device."""
    from repro.launch.mesh import make_host_mesh

    _require(jax.device_count() == 4,
             f"the pod=4 mesh needs 4 devices, found {jax.device_count()}")
    train, test, cfg = _paper_setup(n_train, n_test, n_features, rounds,
                                    overrides)
    mesh = make_host_mesh(pod=4)
    print(f"[d] scaleout over mesh {dict(mesh.shape)} on "
          f"{[str(d) for d in mesh.devices.flat]}")
    scaleout_cfg = cfg(backend="scaleout")
    engine = make_engine(scaleout_cfg, train, test, n_classes=10, mesh=mesh)
    spans = engine.xs.sharding.device_set
    print(f"  client stack sharding: {engine.xs.sharding}, on {len(spans)} devices")
    _require(len(spans) == 4, f"client stack spans {len(spans)} devices, not 4")
    scaleout = (engine, list(engine.rounds()))
    _check_selections(scaleout[1], scaleout_cfg.m, "scaleout")
    compiled = _run(cfg(backend="compiled"), train, test, 10)
    _check_selections(compiled[1], scaleout_cfg.m, "compiled")
    _check_matches("scaleout (4 chips) vs compiled (1 chip)", compiled, scaleout)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod=4 scaleout round against compiled")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    print(f"[a] platform={device['platform']} device_kind={device['kind']} "
          f"count={device['count']}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU found (platform {dev.platform!r}); "
                         f"chip_smoke.py runs on the chip only")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chip_round()
    else:
        from repro.kernels.hellinger.ops import hellinger_strip_pallas

        paper_round()
        strips = hellinger_strip_pallas._cache_size()
        panel = jnp.zeros((100, 10), jnp.float32)  # phase b's K x classes
        custom = "tpu_custom_call" in hellinger_strip_pallas.lower(
            panel, panel).compile().as_text()
        print(f"  hellinger strip: {strips} Pallas variant(s) ran; "
              f"compiled as a tpu_custom_call: {custom}")
        _require(strips > 0 and custom,
                 "the Hellinger strip did not run as the compiled Pallas kernel")
        print(f"  peak_bytes_in_use after phase b: {_peak_bytes()}")
        lm_round()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
