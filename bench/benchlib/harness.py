"""One run of one cell: set-up, warm-up, the measured (or traced) window,
and the comparison with the plain reference.

The window drives ``engine.rounds()`` of ``repro.engine.make_engine``,
the program's own round loop, back to back: each round starts when the
last has ended (a closed loop, as a federated server runs).  The same
iterator runs the warm-up rounds in set-up, and those rounds are what
the reference follows afterwards.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import check, reference, spec, tracing

__all__ = ["NoChip", "device_info", "start", "follow", "run_cell"]

# far past any window, so that no terminal round falls inside a run
_ROUNDS = 1_000_000_000


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{info['count']} {info['platform']} device(s)")
    return info


# Programs above this size are never written to the persistent cache.
# The program's cohort training and fused chunk embed every client's
# data as constants (285 MB in the paper configuration), so they differ
# with the seed: kept, they would be found by a run of a seed seen
# before and compiled by one of a new seed, and set-up would measure
# which seeds ran earlier.  Left out, every run compiles them.
CACHE_MAX_BYTES = 192 * 2**20


def enable_compile_cache(repo: Path) -> str:
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache/bench``
    (a directory of its own: the size limit needs every entry there to
    have been written under it), every program up to ``CACHE_MAX_BYTES``
    kept, so that a second run of a cell finds all of those."""
    where = str(repo / ".jax_cache" / "bench")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Programs compiled, and programs loaded from the persistent cache,
    since it was made (JAX records its compile event for both)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        self.requests += event == self.EVENT

    def _on_event(self, event, **kwargs):
        self.hits += event == self.HIT

    @property
    def count(self) -> tuple[int, int]:
        """(compiled, loaded from the cache)."""
        return self.requests - self.hits, self.hits


# --------------------------------------------------------------- the system

def build_engine(cell: spec.Cell, seed: int, train, test):
    from repro.engine import get_preset, make_engine

    cfg, traffic = cell.config, cell.traffic
    preset = get_preset(traffic["preset"])
    if preset.strategy != ("random" if cell.strategy == "random" else "fedlecc"):
        raise spec.SpecError(f"preset {traffic['preset']!r} selects by "
                             f"{preset.strategy!r}, not {cell.strategy!r}")
    model_kw, n_classes = cell.model.engine_kwargs(cfg)
    kw = dict(n_clients=cfg["n_clients"], m=cfg["m"], rounds=_ROUNDS,
              local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
              lr=cfg["lr"], partition=cfg["partition"], target_hd=cfg["target_hd"],
              eval_samples=cfg["eval_samples"], max_steps_cap=cfg["max_steps_cap"],
              eval_every=cfg["eval_every"], seed=seed, backend=traffic["backend"],
              fuse_rounds=traffic["fuse_rounds"], **model_kw)
    if cell.strategy == "fedlecc":
        kw["strategy_kwargs"] = {**preset.strategy_kwargs, "J": cfg["J"]}
    return make_engine(preset.make_config(**kw), train, test, n_classes=n_classes)


def install_weights(engine, weights) -> None:
    """Hand the benchmark's seeded weights to the program."""
    want = jax.tree.structure(engine.params)
    shapes = [(a.shape, a.dtype) for a in jax.tree.leaves(engine.params)]
    if (jax.tree.structure(weights) != want
            or [(a.shape, a.dtype) for a in jax.tree.leaves(weights)] != shapes):
        raise spec.SpecError("the benchmark's weights do not have the "
                             "program's layout")
    engine.params = weights


class Hooks:
    """Host spans around the engine's round hooks, and what the compared
    rounds produce.  Wraps the instance's bound methods; the engine's
    code is not changed."""

    NAMES = ("poll_losses", "select", "local_train", "aggregate", "evaluate")
    SPAN = {"poll_losses": "poll", "local_train": "train"}

    def __init__(self, engine):
        self.polled: dict[int, np.ndarray] = {}
        self.record_polls = True
        for name in self.NAMES:
            setattr(engine, name, self._wrap(getattr(engine, name), name))

    def _wrap(self, fn, name):
        span = "bench." + self.SPAN.get(name, name)

        def hooked(*args, **kwargs):
            with jax.profiler.TraceAnnotation(span):
                out = fn(*args, **kwargs)
            if name == "poll_losses" and self.record_polls:
                self.polled[int(args[0])] = np.array(out)
            return out

        return hooked


def _leaf_norms_fn():
    return jax.jit(lambda p, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(p), jax.tree.leaves(b))])


def warm_up(engine, rounds_it, hooks, n_rounds: int, n_check: int, base):
    """The first ``n_rounds`` rounds, through the window's own iterator:
    compiles every program the window uses, and records what the first
    ``n_check`` rounds produce for the comparison."""
    norms = _leaf_norms_fn()
    program: dict[int, dict] = {}
    for _ in range(n_rounds):
        res = next(rounds_it)
        r = res.round
        if r >= n_check:
            continue
        rec = {"selected": res.selected, "train_loss": res.mean_selected_loss,
               "losses": hooks.polled.get(r)}
        if res.evaluated:
            rec["test_loss"], rec["test_acc"] = res.test_loss, res.test_acc
        if engine._round == r + 1:  # this round's state is committed
            rec["change"] = np.asarray(jax.device_get(norms(engine.params, base)),
                                       np.float64)
        program[r] = rec
    hooks.record_polls = False
    return program


def measure(engine, rounds_it, seconds: float, counter: CompileCounter,
            trace_dir: str | None = None) -> dict:
    """Whole rounds back to back for ``seconds``; the clock stops after
    ``block_until_ready`` on the parameters of the last round, which ends
    on a committed round (a fused chunk's last)."""
    attempted = failed = 0
    times = []
    before = counter.count
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    window = jax.profiler.TraceAnnotation(tracing.WINDOW)
    window.__enter__()
    t0 = last = time.perf_counter()
    try:
        while True:
            res = next(rounds_it)
            now = time.perf_counter()
            times.append(now - last)
            last = now
            attempted += 1
            failed += not math.isfinite(res.mean_selected_loss)
            if now - t0 >= seconds and engine._round == res.round + 1:
                break
    except Exception as exc:  # a round that raises is a failed round
        print(f"round {attempted} raised {exc!r}", file=sys.stderr)
        attempted += 1
        failed += 1
    jax.block_until_ready(engine.params)
    t1 = time.perf_counter()
    window.__exit__(None, None, None)
    if trace_dir:
        jax.profiler.stop_trace()
    return {"attempted": attempted, "failed": failed, "window_s": t1 - t0,
            "round_times": times,
            "compiles": [a - b for a, b in zip(counter.count, before)]}


def _read(kind: str, names, ctx: dict, root: Path) -> dict:
    """The metrics that find something to read in ``ctx``."""
    out = {}
    for name in names:
        mod = spec.metric(kind, name, root)
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": mod.UNIT}
    return out


def _peak_bytes() -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------------- a run

def start(cell: spec.Cell, seed: int):
    """Data and weights from the seed, the engine, and its warm-up
    rounds through the iterator the window goes on with.  Returns the
    engine, that iterator, the hooks, the first rounds' record for the
    comparison, and the data."""
    cfg, n_check = cell.config, cell.workload["check_rounds"]
    train, test = cell.model.make_data(cfg, seed)
    # the engine build (partition, Hellinger matrix, OPTICS) at the
    # matrix-product precision the configuration states for it
    with jax.default_matmul_precision(cfg["setup_matmul_precision"]):
        engine = build_engine(cell, seed, train, test)
    install_weights(engine, cell.model.init_params(cfg, seed))
    base = jax.tree.map(jnp.copy, engine.params)
    hooks = Hooks(engine)
    rounds_it = engine.rounds()
    program = warm_up(engine, rounds_it, hooks,
                      max(cell.traffic["warmup_rounds"], n_check), n_check, base)
    return engine, rounds_it, hooks, program, (train, test)


def follow(cell: spec.Cell, seed: int, data, run: dict | None = None,
           **kwargs) -> list[dict]:
    """The reference's first rounds; given the record of a ``run`` under
    test, it trains the clients that run selected (``kwargs``: its
    ``dtype`` or a planted ``fault``)."""
    ref = reference.Reference(cell.config, cell.strategy, cell.model, *data, seed,
                              **kwargs)
    picked = polled = None
    if run is not None:
        picked = [run[r]["selected"] for r in sorted(run)]
        polled = [run[r].get("losses") for r in sorted(run)]
        polled = None if any(p is None for p in polled) else polled
    return ref.run(cell.model.init_params(cell.config, seed),
                   cell.workload["check_rounds"], picked, polled)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = spec.ROOT, require_tpu: bool = True,
             keep_trace: str | None = None, overrides: dict | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``overrides`` (tests, at a size the CPU holds) replace keys of the
    configuration."""
    cell = spec.load_cell(name, root)
    if overrides:
        cell = spec.Cell(cell.name, cell.workload, {**cell.config, **overrides},
                         cell.traffic, cell.root)
    wl, cfg = cell.workload, cell.config
    info = require_chips(wl["chips"]) if require_tpu else device_info()
    counter = CompileCounter()
    engine, rounds_it, hooks, program, data = start(cell, seed)
    setup_s = time.perf_counter() - t_start
    compiled, loaded = counter.count
    print(f"set-up {setup_s:.3f} s; programs compiled in set-up {compiled}, "
          f"loaded from the persistent cache {loaded}", file=sys.stderr)

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        seconds = min(seconds, wl["trace_seconds"])
    win = measure(engine, rounds_it, seconds, counter, trace_dir)
    rounds_it.close()
    compiled, loaded = win["compiles"]
    print(f"window: {win['attempted']} rounds in {win['window_s']:.3f} s; inside the "
          f"window programs compiled {compiled}, loaded {loaded}", file=sys.stderr)
    peak = _peak_bytes()

    device = {**info, "memory_peak_bytes": peak}
    ctx = {"setup_s": setup_s, "rounds": win["attempted"],
           "window_s": win["window_s"], "round_times": win["round_times"],
           "chips": wl["chips"], "flops": spec.flops_counter(cfg["flops"], root)(
               cfg, cell.strategy)}
    metrics, breakdown = {}, None
    if trace:
        xplane = tracing.find_xplane(trace_dir)
        reduced = (tracing.reduce_events(tracing.read_xplane(xplane))
                   if xplane else None)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = reduced
        if reduced and info["platform"] == "tpu":
            ctx["peaks"] = spec.device_peaks(info["kind"], root)
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            breakdown = reduced["breakdown"]
            metrics = _read("metrics", wl["per_layer"], ctx, root)
    elif info["platform"] == "tpu":
        metrics = _read("end_to_end", wl["end_to_end"], ctx, root)

    # the reference runs once the program's state is freed
    del engine, rounds_it, hooks
    gc.collect()
    readings = check.compare(program, follow(cell, seed, data, program),
                             jax.device_get(cell.model.init_params(cfg, seed)))
    correct, checks = check.judge(readings, wl["limits"])
    correct = correct and win["failed"] == 0 and win["attempted"] > 0
    out = {"correct": bool(correct), "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
