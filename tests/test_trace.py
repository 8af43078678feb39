"""The round loop's own spans in a profiler trace (``repro.engine.trace``):
which ``fl.*`` spans each execution mode opens, how they nest, how many
blocking read-backs (``fl.sync``) a round makes, and that the consumer's
time between rounds lies outside all of them.  CPU only: the spans are
host events, recorded on any platform."""

import glob
import re
import time

import jax
import numpy as np
import pytest

from conftest import fl_cfg
from repro.engine import make_engine
from repro.engine.trace import SCOPES, SPANS, span, to_host

CONSUMER = "test.consumer"


def _trace(it, directory) -> list[tuple[str, int, int, int | None]]:
    """Drain ``it`` under the profiler, doing the consumer's work
    between rounds; the ``fl.*`` and consumer host events as (name,
    start_ns, end_ns, step_num)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(directory))
    for _ in it:
        with jax.profiler.TraceAnnotation(CONSUMER):
            time.sleep(0.001)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fl.") or ev.name == CONSUMER:
                    step = dict(ev.stats).get("step_num")
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   None if step is None else int(step)))
    return sorted(events, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outers) -> bool:
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def _overlaps(a, b) -> bool:
    return a[1] < b[2] and b[1] < a[2]


def _consumer_outside_spans(events):
    consumer = _named(events, CONSUMER)
    spans = [e for e in events if e[0] != CONSUMER]
    assert consumer and spans
    assert not any(_overlaps(c, s) for c in consumer for s in spans)


def _engine(data, **kw):
    train, test = data
    # rounds far past the traced ones: evaluation only on the cadence
    return make_engine(fl_cfg(rounds=1000, eval_every=5, **kw), train, test,
                       n_classes=10)


def test_span_names_are_fixed():
    assert {"round", "chunk", "poll", "select", "train", "aggregate",
            "evaluate", "save", "gather", "sync", "unpack"} == set(SPANS)
    assert set(SCOPES) == {"poll", "select", "train", "aggregate"}
    with pytest.raises(KeyError):
        span("polling")


def test_to_host_reads_back_inside_a_sync_span(tmp_path):
    x = jax.numpy.arange(3.0)
    jax.profiler.start_trace(str(tmp_path))
    got = to_host(x)
    pair = to_host((x, x + 1), jax.device_get)
    jax.profiler.stop_trace()
    assert isinstance(got, np.ndarray) and got.tolist() == [0.0, 1.0, 2.0]
    assert [a.tolist() for a in pair] == [[0.0, 1.0, 2.0], [1.0, 2.0, 3.0]]


def test_compiled_round_spans(data, tmp_path):
    """Each eager round is the step ``fl.round`` numbered by the round;
    the stages nest in it, every read-back is an ``fl.sync`` inside a
    stage, and a round makes poll + select mask + train losses = 3 of
    them, plus one on every 5th round's evaluation: 3.2 a round."""
    eng = _engine(data, backend="compiled")
    it = eng.rounds(11)
    next(it)  # round 0 compiles; rounds 1..10 are traced
    events = _trace(it, tmp_path)
    rounds = _named(events, "fl.round")
    assert [e[3] for e in rounds] == list(range(1, 11))
    for name in ("fl.poll", "fl.select", "fl.train", "fl.aggregate"):
        stage = _named(events, name)
        assert len(stage) == 10
        assert all(_inside(e, rounds) for e in stage)
    evaluate = _named(events, "fl.evaluate")
    assert len(evaluate) == 2  # rounds 5 and 10
    assert all(_inside(e, rounds) for e in evaluate)
    syncs = _named(events, "fl.sync")
    assert len(syncs) / len(rounds) == pytest.approx(3.2)
    stages = [e for e in events
              if e[0] in ("fl.poll", "fl.select", "fl.train", "fl.evaluate")]
    assert all(_inside(e, stages) for e in syncs)
    assert not _named(events, "fl.chunk") and not _named(events, "fl.gather")
    _consumer_outside_spans(events)


def test_fused_chunk_spans(data, tmp_path):
    """A fused chunk is the step ``fl.chunk`` (its first round) around the
    dispatch; its three read-backs follow it, then ``fl.unpack`` with the
    chunk-final ``fl.evaluate`` and its read-back nested in it: 4 syncs a
    chunk of 5, 0.8 a round."""
    eng = _engine(data, backend="compiled", fuse_rounds=5)
    it = eng.rounds(11)
    next(it)  # the round-0 chunk; chunks 1..5 and 6..10 are traced
    events = _trace(it, tmp_path)
    chunks = _named(events, "fl.chunk")
    assert [e[3] for e in chunks] == [1, 6]
    unpack = _named(events, "fl.unpack")
    evaluate = _named(events, "fl.evaluate")
    assert len(unpack) == len(evaluate) == 2
    assert all(_inside(e, unpack) for e in evaluate)
    syncs = _named(events, "fl.sync")
    assert len(syncs) / 10 == pytest.approx(0.8)
    assert sum(_inside(e, evaluate) for e in syncs) == 2
    assert not any(_inside(e, chunks) for e in syncs)
    for c, u in zip(chunks, unpack):
        assert c[2] <= u[1]  # dispatch, read-backs, then unpacking
    assert not _named(events, "fl.round") and not _named(events, "fl.poll")
    _consumer_outside_spans(events)


@pytest.mark.parametrize("backend", ["host", "compiled"])
def test_population_mode_opens_gather(backend, data, tmp_path):
    """Population mode gathers the resident members' stacks from the
    host store for the poll and the cohort for training."""
    eng = _engine(data, backend=backend,
                  population={"n_shards": 2, "shards_per_round": 2})
    it = eng.rounds(3)
    next(it)
    events = _trace(it, tmp_path)
    gather = _named(events, "fl.gather")
    assert len(gather) == 4  # poll and train, two rounds
    assert sum(_inside(e, _named(events, "fl.poll")) for e in gather) == 2
    assert sum(_inside(e, _named(events, "fl.train")) for e in gather) == 2
    _consumer_outside_spans(events)


def test_checkpoint_policy_runs_in_a_save_span(data, tmp_path):
    """``fl.save`` covers the checkpoint policy after each committed
    round, and with it every save the policy makes."""
    import os

    from repro.checkpoint import CheckpointPolicy, Checkpointer

    eng = _engine(data, backend="compiled")
    eng.checkpointer = Checkpointer(str(tmp_path / "ckpt"),
                                    CheckpointPolicy(every_rounds=2))
    it = eng.rounds(5)
    next(it)
    events = _trace(it, tmp_path / "trace")
    saves = _named(events, "fl.save")
    assert len(saves) == 4
    assert all(_inside(e, _named(events, "fl.round")) for e in saves)
    assert os.listdir(tmp_path / "ckpt")


def _scopes(compiled_text: str) -> set[str]:
    """Every component of the operations' ``op_name`` paths."""
    return {part for path in re.findall(r'op_name="([^"]*)"', compiled_text)
            for part in path.split("/")}


def test_device_stages_are_named_scopes(data):
    """The fused chunk's operations carry the stage scopes in their HLO
    metadata, and the compiled backend's poll, training and aggregation
    programs theirs."""
    eng = _engine(data, backend="compiled", fuse_rounds=2)
    key = eng._carry_key()
    chunk = eng._chunk_step(2).lower(eng.params, key).compile().as_text()
    assert set(SCOPES) <= _scopes(chunk)
    poll = eng._poll_losses.lower(eng.params, eng.xs, eng.ys, eng.mask, key)
    assert "poll" in _scopes(poll.compile().as_text())
    idx = jax.numpy.arange(4, dtype=jax.numpy.int32)
    train = eng._train_cohort.lower(eng.params, idx, key)
    assert "train" in _scopes(train.compile().as_text())
    stacked = jax.tree.map(lambda p: jax.numpy.stack([p] * 4), eng.params)
    mask = np.zeros((eng.cfg.n_clients,), np.bool_)
    mask[:4] = True
    agg = eng._aggregate_round.lower(stacked, eng.params, idx, mask,
                                     np.int32(4), eng.agg_state, None)
    assert "aggregate" in _scopes(agg.compile().as_text())
