"""Jaxpr / compile contract checks (tracecheck layer 2, DESIGN.md §11).

Where the AST lint (``repro.analysis.lint``) checks what the *source*
promises, this module checks what the *tracer and compiler* actually
produce, on tiny canonical configs:

- **mask-shape** — for every registered mask strategy × task shape,
  ``select_mask_jax`` (and ``select_mask_traced`` where supported)
  traces under ``jax.make_jaxpr`` / ``jax.eval_shape`` to a static
  ``(K,)`` boolean mask.  A shape or dtype drift here breaks the static
  cohort gather silently (wrong weights), not loudly.
- **no-callback** — the traced masks contain no host-callback
  primitives (``pure_callback`` / ``io_callback``) anywhere in the
  jaxpr, including nested pjit sub-jaxprs: a callback inside the fused
  chunk reintroduces the per-round host sync the fused engine exists to
  remove.
- **donation** — the fused chunk's *compiled* executable really aliases
  the donated ``(params, key)`` carry: its HLO text declares
  ``input_output_alias`` (the lowering-level marker; jax only emits it
  when ``donate_argnums`` survived to XLA).
- **retrace** — driving multi-round ``rounds()`` on each backend stays
  within ``RETRACE_BUDGET`` compilations per jitted callable, across
  *separate* ``rounds()`` calls; the fused engine compiles at most
  ``FUSED_CHUNK_BUDGET`` distinct chunk lengths (round-0 chunk,
  steady-state chunk, tail — see ``FusedEngine``).

Everything here needs jax and a few seconds of CPU compile time, so the
module is imported lazily by the CLI (never by ``repro.analysis``'s
package ``__init__``).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

__all__ = [
    "BANNED_CALLBACK_PRIMITIVES",
    "ContractReport",
    "ContractResult",
    "FUSED_CHUNK_BUDGET",
    "RETRACE_BUDGET",
    "TASK_SHAPES",
    "run_contracts",
]

BANNED_CALLBACK_PRIMITIVES = ("pure_callback", "io_callback")

# One compile per jitted callable per engine lifetime — the budget the
# no-retrace guard tests pin per backend; violating it means a traced
# value (python scalar, changing shape) leaked into the trace signature.
RETRACE_BUDGET = 1
# Distinct fused chunk lengths with an aligned fuse_rounds/eval_every:
# the round-0 chunk, the steady-state chunk, and the tail.
FUSED_CHUNK_BUDGET = 3

# The task axis enters mask selection through its canonical shape
# triple: (K clients, cohort m, feature-histogram bins) — classification
# clusters on n_classes-bin label histograms, LM on hist_bins topic
# histograms (the conformance-grid configs in tests/conftest.py).
TASK_SHAPES: dict[str, tuple[int, int, int]] = {
    "classification": (12, 4, 10),
    "lm": (8, 3, 16),
}


@dataclass(frozen=True)
class ContractResult:
    """One contract check: ``name`` passed/failed/skipped with detail."""

    name: str
    ok: bool
    detail: str = ""
    skipped: bool = False

    def to_dict(self) -> dict:
        return {
            "name": self.name, "ok": self.ok,
            "skipped": self.skipped, "detail": self.detail,
        }

    def __str__(self) -> str:
        status = "SKIP" if self.skipped else ("ok" if self.ok else "FAIL")
        return f"[{status}] {self.name}" + (f" — {self.detail}" if self.detail else "")


@dataclass
class ContractReport:
    results: list[ContractResult] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(r.ok or r.skipped for r in self.results)

    def to_dict(self) -> dict:
        return {"ok": self.ok, "results": [r.to_dict() for r in self.results]}


class SkipContract(Exception):
    """Raised by a check that cannot run in this environment."""


def _run(report: ContractReport, name: str, fn) -> None:
    try:
        detail = fn() or ""
        report.results.append(ContractResult(name, True, detail))
    except SkipContract as e:
        report.results.append(ContractResult(name, True, str(e), skipped=True))
    except Exception as e:  # noqa: BLE001 — a contract check failing IS the signal
        report.results.append(
            ContractResult(name, False, f"{type(e).__name__}: {e}")
        )


# ---------------------------------------------------------------- fixtures
def _planted_histograms(K: int, C: int, G: int = 3, seed: int = 0) -> np.ndarray:
    """Label histograms with G planted modes (same construction as the
    cluster tests) so OPTICS-based strategies see real density structure."""
    rng = np.random.default_rng(seed)
    modes = rng.dirichlet(np.ones(C) * 0.2, size=G)
    assign = np.arange(K) % G
    return np.stack([rng.dirichlet(modes[g] * 200.0 + 1e-3) for g in assign])


def _strategy(name: str, K: int, m: int, C: int):
    from repro.core.strategies import get_strategy

    strat = get_strategy(name, m=m)
    rng = np.random.default_rng(0)
    strat.setup(_planted_histograms(K, C), rng.integers(20, 61, size=K))
    return strat


def _tiny_engine(**overrides):
    """A tiny classification engine (12 clients, 16-dim features) —
    seconds to compile, enough to exercise every jit in a backend."""
    from repro.data import make_classification
    from repro.engine import FLConfig, make_engine

    cfg_kw = dict(
        n_clients=12, m=4, rounds=4, strategy="fedlecc",
        strategy_kwargs={"J": 3}, hidden=(16,), eval_samples=16,
        eval_every=2, target_hd=0.8, seed=0,
    )
    cfg_kw.update(overrides)
    cfg = FLConfig(**cfg_kw)
    train = make_classification(240, n_features=16, n_classes=10, seed=0)
    test = make_classification(80, n_features=16, n_classes=10, seed=1)
    return make_engine(cfg, train, test, n_classes=10)


# ---------------------------------------------------------------- jaxpr walk
def _sub_jaxprs(val):
    if hasattr(val, "jaxpr") and hasattr(getattr(val, "jaxpr"), "eqns"):
        yield val.jaxpr  # ClosedJaxpr
    elif hasattr(val, "eqns"):
        yield val  # raw Jaxpr
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _sub_jaxprs(item)


def _walk_eqns(jaxpr):
    """Every equation in a jaxpr, recursing into sub-jaxprs carried in
    eqn params (pjit bodies, scan bodies, cond branches, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _walk_eqns(sub)


def _assert_no_callbacks(closed, what: str) -> None:
    for eqn in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name in BANNED_CALLBACK_PRIMITIVES:
            raise AssertionError(
                f"{what}: banned host-callback primitive "
                f"{eqn.primitive.name!r} in the traced mask"
            )


def _assert_mask_aval(avals, K: int, what: str) -> None:
    import jax.numpy as jnp

    if len(avals) != 1:
        raise AssertionError(f"{what}: expected one output, got {len(avals)}")
    aval = avals[0]
    if tuple(aval.shape) != (K,):
        raise AssertionError(
            f"{what}: mask shape {tuple(aval.shape)} != static ({K},)"
        )
    if aval.dtype != jnp.bool_:
        raise AssertionError(f"{what}: mask dtype {aval.dtype} != bool")


# ---------------------------------------------------------------- checks
def _check_masks(report: ContractReport) -> None:
    import jax
    import jax.numpy as jnp

    from repro.engine.registry import (
        mask_selection_strategies,
        traced_selection_strategies,
    )

    traced_names = set(traced_selection_strategies())
    for task, (K, m, C) in TASK_SHAPES.items():
        losses = jnp.linspace(0.1, 2.0, K).astype(jnp.float32)
        for name in mask_selection_strategies():
            strat = _strategy(name, K, m, C)

            def compiled_check(strat=strat, name=name, task=task, K=K,
                               losses=losses):
                what = f"{name}×{task}.select_mask_jax"
                # Some strategies legitimately make *host* decisions from
                # the concrete loss vector before staging the mask math
                # (fedlecc's static J, the host-rng score draws): the
                # backends call select_mask_jax eagerly once per round.
                # Try the stronger abstract-losses trace first; fall back
                # to staging with losses held concrete (a nullary
                # make_jaxpr), which still proves the mask computation is
                # host-sync-free with a static (K,) bool output.
                try:
                    rng = np.random.default_rng(0)
                    closed = jax.make_jaxpr(
                        lambda l: strat.select_mask_jax(l, rng)
                    )(losses)
                    out = jax.eval_shape(
                        lambda l: strat.select_mask_jax(
                            l, np.random.default_rng(0)
                        ),
                        losses,
                    )
                    _assert_mask_aval([out], K, what + " (eval_shape)")
                    mode = "abstract losses"
                except (jax.errors.TracerArrayConversionError,
                        jax.errors.ConcretizationTypeError):
                    losses_np = np.asarray(losses)
                    rng = np.random.default_rng(0)
                    closed = jax.make_jaxpr(
                        lambda: strat.select_mask_jax(losses_np, rng)
                    )()
                    mode = "host-static losses"
                _assert_mask_aval(closed.out_avals, K, what)
                _assert_no_callbacks(closed, what)
                return f"(K,)=({K},) bool, no callbacks ({mode})"

            _run(report, f"mask-jaxpr/{task}/{name}/compiled", compiled_check)

            if name in traced_names:
                def traced_check(strat=strat, name=name, task=task, K=K,
                                 losses=losses):
                    what = f"{name}×{task}.select_mask_traced"
                    key = jax.random.PRNGKey(0)
                    closed = jax.make_jaxpr(strat.select_mask_traced)(
                        losses, key
                    )
                    _assert_mask_aval(closed.out_avals, K, what)
                    _assert_no_callbacks(closed, what)
                    out = jax.eval_shape(strat.select_mask_traced, losses, key)
                    _assert_mask_aval([out], K, what + " (eval_shape)")
                    return f"(K,)=({K},) bool, no callbacks"

                _run(report, f"mask-jaxpr/{task}/{name}/traced", traced_check)


def _check_donation(report: ContractReport) -> None:
    def donation() -> str:
        import jax

        eng = _tiny_engine(backend="compiled", fuse_rounds=2)
        step = eng._chunk_step(2)
        lowered = step.lower(eng.params, jax.random.PRNGKey(0))
        txt = lowered.compile().as_text()
        if "input_output_alias" not in txt:
            raise AssertionError(
                "fused chunk executable declares no input_output_alias — "
                "the (params, key) carry donation was dropped"
            )
        return "chunk(len=2) HLO declares input_output_alias for the carry"

    _run(report, "donation/fused-chunk-carry", donation)


def _drive_twice(eng, per_call: int = 2) -> None:
    """Two separate rounds() calls — retraces *across* calls are exactly
    the regression this guard exists for."""
    for _ in eng.rounds(per_call):
        pass
    for _ in eng.rounds(per_call):
        pass


def _check_retrace(report: ContractReport) -> None:
    def host() -> str:
        eng = _tiny_engine(backend="host")
        _drive_twice(eng)
        return _assert_budget(eng, ("_round_train", "_poll_losses", "_evaluate"))

    def compiled() -> str:
        eng = _tiny_engine(backend="compiled")
        _drive_twice(eng)
        return _assert_budget(
            eng, ("_train_cohort", "_aggregate_round", "_poll_losses", "_evaluate")
        )

    def fused() -> str:
        eng = _tiny_engine(backend="compiled", fuse_rounds=2)
        # 4 rounds in one call hits both the round-0 length-1 chunk and
        # the steady-state length-2 chunk; the second call must reuse
        # both cache entries, not recompile.
        for _ in eng.rounds(4):
            pass
        for _ in eng.rounds(2):
            pass
        if len(eng._chunk_cache) > FUSED_CHUNK_BUDGET:
            raise AssertionError(
                f"{len(eng._chunk_cache)} distinct fused chunk lengths "
                f"compiled (budget {FUSED_CHUNK_BUDGET})"
            )
        sizes = {
            length: fn._cache_size() for length, fn in eng._chunk_cache.items()
        }
        over = {k: v for k, v in sizes.items() if v > RETRACE_BUDGET}
        if over:
            raise AssertionError(f"fused chunk retraced: {over}")
        extra = _assert_budget(eng, ("_poll_losses", "_evaluate"))
        return f"chunk lengths {sorted(sizes)} × 1 compile; {extra}"

    def scaleout() -> str:
        import jax

        if len(jax.devices()) < 2:
            raise SkipContract(
                "scaleout needs >1 device (covered by the tier-1 subprocess "
                "tests with XLA_FLAGS=--xla_force_host_platform_device_count)"
            )
        eng = _tiny_engine(backend="scaleout")
        _drive_twice(eng)
        return _assert_budget(eng, ("_round_fn", "_poll_losses", "_evaluate"))

    _run(report, "retrace/host", host)
    _run(report, "retrace/compiled", compiled)
    _run(report, "retrace/fused", fused)
    _run(report, "retrace/scaleout", scaleout)


def _assert_budget(eng, attrs: tuple[str, ...]) -> str:
    sizes = {}
    for attr in attrs:
        fn = getattr(eng, attr, None)
        if fn is None or not hasattr(fn, "_cache_size"):
            continue
        sizes[attr] = fn._cache_size()
    over = {k: v for k, v in sizes.items() if v > RETRACE_BUDGET}
    if over:
        raise AssertionError(
            f"compile budget {RETRACE_BUDGET} exceeded: {over} "
            f"(a traced value leaked into the trace signature)"
        )
    return ", ".join(f"{k}×{v}" for k, v in sorted(sizes.items()))


def run_contracts() -> ContractReport:
    """Run every contract check; never raises — failures land in the
    report (the CLI turns them into a non-zero exit)."""
    report = ContractReport()
    _check_masks(report)
    _check_donation(report)
    _check_retrace(report)
    return report
