"""The reduction from a profiler trace to the per-layer metrics, and the
command's refusal to run without a chip.  CPU only."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import spec, tracing  # noqa: E402

CHIP_EVENTS = BENCH / "testdata" / "chip_events.json.gz"
CHIP_EXPECTED = BENCH / "testdata" / "chip_events.expected.json"


def test_program_names_are_the_jit_names():
    assert tracing.program_name("jit__poll_losses(12)") == "_poll_losses"
    assert tracing.program_name("jit__cohort_train.3") == "_cohort_train"
    assert tracing.program_name("jit_run") == "run"


def test_reduction_of_a_hand_made_trace():
    """Busy time is the union of operation intervals; programs and spans
    are summed inside the window; a gap is named by the span open in it."""
    ev = {"devices": [{"name": "/device:TPU:0",
                       "modules": [["jit__poll_losses(1)", 100, 300],
                                   ["jit__cohort_train(2)", 600, 250]],
                       "ops": [["fusion.1", 100, 200], ["fusion.2", 250, 150],
                               ["dot.3", 600, 250]]}],
          "spans": [["bench.window", 0, 1000], ["bench.poll", 50, 400],
                    ["bench.select", 450, 120], ["bench.train", 580, 300]]}
    red = tracing.reduce_events(ev)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(550e-9)  # [100, 400] and [600, 850]
    assert red["programs"] == pytest.approx({"_poll_losses": 300e-9,
                                             "_cohort_train": 250e-9})
    assert red["spans"]["select"] == pytest.approx(120e-9)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["select", pytest.approx(200e-9)]  # 400..600
    assert red["breakdown"]["device_ops"][0] == ["dot.3", pytest.approx(250e-9)]
    assert tracing.reduce_events({"devices": [], "spans": []}) is None


def test_reduction_of_the_chip_trace():
    """A traced window recorded on one TPU v5e (paper-mlp.compiled), the
    events kept by ``read_xplane``; its reduction is pinned."""
    red = tracing.reduce_events(tracing.load_events(CHIP_EVENTS))
    want = json.loads(CHIP_EXPECTED.read_text())
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["programs"] == pytest.approx(want["programs"])
    assert red["spans"] == pytest.approx(want["spans"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert {"_poll_losses", "_cohort_train"} <= set(red["programs"])
    ctx = {"trace": red, "rounds": want["rounds"], "chips": 1,
           "flops": spec.flops_counter("mlp")(
               spec.load_json("configs", "paper-mlp"), "fedlecc"),
           "peaks": spec.device_peaks("TPU v5 lite")}
    for name in spec.load_json("workloads", "paper-mlp.compiled")["per_layer"]:
        value = spec.metric("metrics", name).read(ctx)
        assert value == pytest.approx(want["metrics"][name])
    assert 0 < want["metrics"]["round_mfu"] <= 100


def test_a_cpu_trace_has_spans_and_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    d = str(tmp_path)
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("bench.poll"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tracing.read_xplane(tracing.find_xplane(d))
    names = [s[0] for s in ev["spans"]]
    assert tracing.WINDOW in names and "bench.poll" in names
    assert ev["devices"] == []
    assert tracing.reduce_events(ev) is None  # nothing to read: no metric


def _run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "paper-mlp.compiled", "--seed", "2147483653",
        "--seconds", "1", "--trace", "0"]


def test_run_exits_nonzero_without_a_chip():
    out = _run(ARGS, BENCH.parent)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no system."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    out = _run(ARGS, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
