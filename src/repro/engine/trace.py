"""The names the round loop gives its work in a profiler trace.

Host spans are ``jax.profiler.TraceAnnotation``s named ``fl.<name>``
for a name in ``SPANS``.  Each eager round is the step annotation
``fl.round`` and each fused chunk ``fl.chunk``, numbered by its first
round, so that the spans of one round share a step.  No span stays open
across a ``yield`` of ``rounds()``: what the consumer does between
rounds lies outside every ``fl.*`` span.  Device operations carry the
named scope of their stage (``SCOPES``) in their HLO metadata.

With no profiler running a span costs about a microsecond on the host;
a scope changes only metadata, never the operations.  To record them,
run the rounds under ``jax.profiler.trace(directory)``.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["SPANS", "SCOPES", "span", "step", "scope", "to_host"]

SPANS = ("round", "chunk", "poll", "select", "train", "aggregate", "evaluate",
         "save", "gather", "sync", "unpack")
SCOPES = ("poll", "select", "train", "aggregate")

_NAMES = {name: "fl." + name for name in SPANS}


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``fl.<name>``."""
    return jax.profiler.TraceAnnotation(_NAMES[name])


def step(name: str, num: int) -> jax.profiler.StepTraceAnnotation:
    """The step span ``fl.<name>`` of round ``num``."""
    return jax.profiler.StepTraceAnnotation(_NAMES[name], step_num=num)


def scope(name: str):
    """The named scope of a stage's device operations (used while
    tracing a jitted function)."""
    if name not in SCOPES:
        raise KeyError(name)
    return jax.named_scope(name)


def to_host(x, read=np.asarray):
    """``read(x)``, the blocking read-back of device values to the host
    (``np.asarray``, or ``jax.device_get`` for a pytree), inside an
    ``fl.sync`` span: the round loop's waits on the device."""
    with span("sync"):
        return read(x)
