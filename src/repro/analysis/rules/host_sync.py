"""Rule ``no-host-sync`` — no host synchronization inside traced code.

Scope: the jit hot-path modules (``HOT_PATH_MODULES`` in
``repro.analysis.lint`` — the compiled/fused/scaleout engines, the
selection core, and the Pallas kernels).  Inside functions that are
*traced* — jit-decorated, passed to ``jax.jit`` / ``vmap`` / ``scan`` /
``shard_map`` / ``pallas_call``, or nested within one — the idioms that
force a device→host sync (or silently constant-fold a tracer) are bugs:

    float(x)   .item()   .tolist()   np.asarray(x)   np.array(x)
    jax.device_get(x)

On a traced value these either raise ``TracerConversionError`` at run
time or, worse, sync the device once per round inside what is supposed
to be a device-resident chunk.  The host-side halves of the same
modules (methods driving the round loop) use these idioms freely and
are out of scope.

Traced-function detection is a small flow analysis: direct decoration,
by-name wrapping (``jax.jit(f)``), and the builder pattern the fused
engine uses (``self._round_body = f`` in one method, ``body =
self._round_body; lax.scan(body, ...)`` in another).
"""

from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.lint import FileContext, Violation
from repro.analysis.rules import (
    Rule,
    canonical_call_name,
    register_rule,
    resolve_aliases,
)

# Wrappers whose first function argument is traced.
_TRACING_WRAPPERS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.map", "jax.lax.fori_loop", "jax.lax.while_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.associative_scan",
    "jax.experimental.shard_map.shard_map", "jax.shard_map",
    "jax.experimental.pallas.pallas_call", "pl.pallas_call",
    "jax.make_jaxpr", "jax.eval_shape",
}
# Unqualified names that count as wrappers too (e.g.
# ``from jax import shard_map``).
_WRAPPER_TAILS = {"shard_map", "pallas_call"}

_SYNC_CALLS = {"float"}
_SYNC_METHODS = {"item", "tolist"}
_SYNC_DOTTED = {"numpy.asarray", "numpy.array", "jax.device_get"}


def _is_wrapper(name: str | None) -> bool:
    if name is None:
        return False
    return name in _TRACING_WRAPPERS or name.split(".")[-1] in _WRAPPER_TAILS


class _FnInfo:
    def __init__(self, node: ast.FunctionDef | ast.AsyncFunctionDef):
        self.node = node
        self.traced = False


@register_rule
class NoHostSync(Rule):
    name = "no-host-sync"
    description = (
        "no host-sync idioms (float()/.item()/.tolist()/np.asarray/"
        "jax.device_get) inside traced functions in the jit hot-path modules"
    )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterable[Violation]:
        if not ctx.is_hot_path:
            return
        aliases = resolve_aliases(tree)

        # -- collect every function definition, keyed by name (scope-blind:
        # shadowing across scopes is rare and over-marking only widens the
        # checked surface, never misses it) --
        fns: dict[str, list[_FnInfo]] = {}
        infos: list[_FnInfo] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = _FnInfo(node)
                infos.append(info)
                fns.setdefault(node.name, []).append(info)

        def mark(name: str) -> None:
            for info in fns.get(name, []):
                info.traced = True

        # -- direct decoration: @jax.jit / @partial(jax.jit, ...) --
        for info in infos:
            for dec in info.node.decorator_list:
                target = dec
                if isinstance(dec, ast.Call):
                    name = canonical_call_name(dec.func, aliases)
                    if name in ("functools.partial", "partial") and dec.args:
                        target = dec.args[0]
                    else:
                        target = dec.func
                if _is_wrapper(canonical_call_name(target, aliases)) or (
                    canonical_call_name(target, aliases) in ("jax.jit",)
                ):
                    info.traced = True

        # -- by-name wrapping, plus the builder two-hop:
        #    self.attr = fn_name ... alias = self.attr ... scan(alias, ...)
        attr_fn: dict[str, str] = {}     # self.<attr> -> function name
        alias_attr: dict[str, str] = {}  # local alias -> self.<attr>
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt, val = node.targets[0], node.value
                if (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                    and isinstance(val, ast.Name)
                    and val.id in fns
                ):
                    attr_fn[tgt.attr] = val.id
                elif (
                    isinstance(tgt, ast.Name)
                    and isinstance(val, ast.Attribute)
                    and isinstance(val.value, ast.Name)
                    and val.value.id == "self"
                ):
                    alias_attr[tgt.id] = val.attr

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if not (
                _is_wrapper(canonical_call_name(node.func, aliases))
                or canonical_call_name(node.func, aliases) == "jax.jit"
            ):
                continue
            first = node.args[0]
            if isinstance(first, ast.Name):
                if first.id in fns:
                    mark(first.id)
                elif first.id in alias_attr and alias_attr[first.id] in attr_fn:
                    mark(attr_fn[alias_attr[first.id]])
            elif (
                isinstance(first, ast.Attribute)
                and isinstance(first.value, ast.Name)
                and first.value.id == "self"
                and first.attr in attr_fn
            ):
                mark(attr_fn[first.attr])

        # -- propagate: nested defs inside traced functions are traced --
        changed = True
        while changed:
            changed = False
            for info in infos:
                if not info.traced:
                    continue
                for sub in ast.walk(info.node):
                    if (
                        isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and sub is not info.node
                    ):
                        for other in fns.get(sub.name, []):
                            if other.node is sub and not other.traced:
                                other.traced = True
                                changed = True

        # -- flag sync idioms inside traced bodies --
        seen: set[int] = set()
        for info in infos:
            if not info.traced:
                continue
            for sub in ast.walk(info.node):
                if not isinstance(sub, ast.Call) or id(sub) in seen:
                    continue
                msg = None
                fname = canonical_call_name(sub.func, aliases)
                if isinstance(sub.func, ast.Name) and sub.func.id in _SYNC_CALLS:
                    msg = (
                        f"{sub.func.id}() on a value inside a traced function "
                        f"forces a host sync (or fails on a tracer)"
                    )
                elif (
                    isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _SYNC_METHODS
                    and not sub.args
                ):
                    msg = (
                        f".{sub.func.attr}() inside a traced function forces "
                        f"a device→host sync"
                    )
                elif fname in _SYNC_DOTTED:
                    msg = (
                        f"{fname} inside a traced function pulls the value to "
                        f"host; use jnp.asarray / keep it on device"
                    )
                if msg is not None:
                    seen.add(id(sub))
                    yield self.violation(ctx, sub, msg)
