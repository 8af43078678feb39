"""The reduction of the program's own spans and scopes to per-stage
times (``benchlib.stages``).  CPU only."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import stages, tracing  # noqa: E402

CHIP_EVENTS = BENCH / "testdata" / "chip_events.json.gz"
FUSED_EVENTS = BENCH / "testdata" / "chip_events_fused.json.gz"
FUSED_EXPECTED = BENCH / "testdata" / "chip_events_fused.expected.json"


@pytest.mark.parametrize("path, stage", [
    ("jit(run)/while/body/closed_call/poll/jit(_poll_losses)/poll/vmap()/dot_general",
     "poll"),
    ("jit(run)/while/body/closed_call/select/jit(fedlecc_select_jax)/sort", "select"),
    ("jit(run)/while/body/closed_call/train/train/vmap()/while/body/mul", "train"),
    ("jit(run)/while/body/closed_call/aggregate/reduce_sum", "aggregate"),
    ("jit(_cohort_train)/train/vmap()/transpose(jvp())/dot_general", "train"),
    ("jit(run)/while", None),
    ("jit(_evaluate)/select_n", None),
    ("", None),
])
def test_a_stage_is_a_scope_in_the_op_path(path, stage):
    assert stages.scope_stage(path) == stage


def _hand_made():
    """One device: a scan [100, 700] holding poll, select, train and
    aggregate operations, a lone operation [800, 900]; idle 0..100,
    700..800 and 900..1000 of the window 0..1000."""
    return {
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit_run(1)", 100, 600], ["jit__evaluate(2)", 800, 100]],
            "ops": [["while.1", 100, 600], ["fusion.1", 110, 100],
                    ["while.2", 150, 100], ["fusion.3", 260, 40],
                    ["while.4", 300, 300], ["fusion.5", 320, 100],
                    ["fusion.7", 650, 50], ["fusion.6", 800, 100]],
            # poll nested [110, 210] and [150, 250]: 140, not 200
            "scoped": [["poll", 110, 100], ["poll", 150, 100],
                       ["select", 260, 40], ["train", 300, 300],
                       ["train", 320, 100], ["aggregate", 650, 50]],
        }],
        "spans": [["bench.window", 0, 1000], ["bench.aggregate", 720, 40]],
        "program_spans": [["fl.chunk", 20, 80], ["fl.sync", 105, 700],
                          ["fl.unpack", 810, 150], ["fl.evaluate", 830, 90],
                          ["fl.sync", 840, 60]],
    }


def test_reduction_of_hand_made_spans_and_scopes():
    ev = _hand_made()
    red = stages.reduce_stages(ev)
    base = tracing.reduce_events(ev)
    for key in ("window_s", "busy_s", "programs", "spans"):
        assert red[key] == base[key]
    assert red["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]
    assert red["busy_s"] == pytest.approx(700e-9)
    assert red["program_spans"] == pytest.approx(
        {"chunk": 80e-9, "sync": 760e-9, "unpack": 150e-9, "evaluate": 90e-9})
    assert red["program_counts"] == {"chunk": 1, "sync": 2, "unpack": 1,
                                     "evaluate": 1}
    # unpack less evaluate (which holds a sync), evaluate less its sync
    assert red["program_self"] == pytest.approx(
        {"chunk": 80e-9, "sync": 760e-9, "unpack": 60e-9, "evaluate": 30e-9})
    assert red["scopes"] == pytest.approx(
        {"poll": 140e-9, "select": 40e-9, "train": 300e-9, "aggregate": 50e-9})
    # a benchmark span keeps its label; the others name the program's
    # innermost span; the whole idle time splits by what was open
    assert red["breakdown"]["idle_gaps"] == [
        ["fl.chunk", pytest.approx(100e-9)], ["aggregate", pytest.approx(100e-9)],
        ["fl.unpack", pytest.approx(100e-9)]]
    assert base["breakdown"]["idle_gaps"][0][0] == "none"
    assert red["idle_by_label"] == pytest.approx(
        {"none": 60e-9, "fl.chunk": 80e-9, "fl.sync": 60e-9, "aggregate": 40e-9,
         "fl.evaluate": 20e-9, "fl.unpack": 40e-9})
    assert sum(red["idle_by_label"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_stage_metrics_per_round():
    got = stages.stage_metrics(stages.reduce_stages(_hand_made()), rounds=5)
    assert got == pytest.approx({
        "evaluate_ms": 90e-9 / 5 * 1e3, "sync_wait_ms": 760e-9 / 5 * 1e3,
        "syncs_per_round": 0.4, "unpack_ms": 60e-9 / 5 * 1e3,
        "poll_dev_ms": 140e-9 / 5 * 1e3, "select_dev_ms": 40e-9 / 5 * 1e3,
        "train_dev_ms": 300e-9 / 5 * 1e3, "aggregate_dev_ms": 50e-9 / 5 * 1e3})
    assert "aggregate_ms" not in got  # no fl.aggregate span: nothing read
    assert set(stages.METRICS) >= set(got)
    assert stages.stage_metrics(None, 5) == {}


def test_a_trace_without_program_spans_reduces_as_before():
    """The committed compiled-cell trace predates the program's spans."""
    ev = tracing.load_events(CHIP_EVENTS)
    assert stages.reduce_stages(ev) == tracing.reduce_events(ev)
    assert stages.stage_metrics(stages.reduce_stages(ev), 12) == {}


def test_read_stages_keeps_the_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.StepTraceAnnotation("fl.round", step_num=3):
            with jax.profiler.TraceAnnotation("fl.sync"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = stages.read_stages(tracing.find_xplane(tmp_path))
    assert sorted(s[0] for s in ev["program_spans"]) == ["fl.round", "fl.sync"]
    assert [s[0] for s in ev["spans"]] == [tracing.WINDOW]
    assert ev["devices"] == []  # the CPU has no device plane
    assert stages.reduce_stages(ev) is None


def test_reduction_of_the_fused_chip_trace():
    """A traced window recorded on one TPU v5e (paper-mlp.fused, three
    chunks of five rounds), the events kept by ``read_stages``; its
    reduction and every metric read from it are pinned."""
    from benchlib import spec

    red = stages.reduce_stages(tracing.load_events(FUSED_EVENTS))
    want = json.loads(FUSED_EXPECTED.read_text())
    for key in ("window_s", "busy_s", "programs", "spans", "program_spans",
                "program_self", "scopes", "idle_by_label"):
        assert red[key] == pytest.approx(want[key]), key
    assert red["program_counts"] == want["program_counts"]
    assert red["breakdown"]["idle_gaps"] == [
        [label, pytest.approx(s)] for label, s in want["idle_gaps"]]
    ctx = {"trace": red, "rounds": want["rounds"], "chips": 1,
           "flops": spec.flops_counter("mlp")(
               spec.load_json("configs", "paper-mlp"), "fedlecc"),
           "peaks": spec.device_peaks("TPU v5 lite")}
    got = {name: spec.metric("metrics", name).read(ctx)
           for name in spec.load_json("workloads", "paper-mlp.fused")["per_layer"]}
    got.update(stages.stage_metrics(red, want["rounds"]))
    assert got == pytest.approx(want["metrics"])
    # the stages' device time is the chunk's scan, all but its own loop
    scopes = sum(red["scopes"].values())
    assert 0.8 * red["programs"]["run"] <= scopes <= red["busy_s"]
    assert want["metrics"]["syncs_per_round"] == pytest.approx(0.8)
    assert not [g for g in red["breakdown"]["idle_gaps"]
                if g[0] == "none" and g[1] >= 1e-3]
