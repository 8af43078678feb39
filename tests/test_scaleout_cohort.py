"""The scaleout engine's cohort round on four virtual devices (one
subprocess, so the device-count flag doesn't leak into other tests).

A pod=4 mesh holds the client stacks ``P("pod")``; each round trains
only the selected cohort, gathered from the pods that hold it and spread
evenly over them, and aggregates by each pod's weighted partial sum and
the ``psum``.  Checked against the compiled engine on one device:

  - the same selections, polled and local losses, and parameters;
  - a cohort wholly on one pod, gathered to all four;
  - one compiled program however the cohort splits over the pods;
  - the stacks sharded ``P("pod")``, placed from host arrays, and no
    K-stacked parameters among the round program's inputs;
  - seeded weights installed as the benchmark installs them (committed
    to device 0), then rounds, and the caller's own jit on device 0.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.data import make_classification
from repro.engine import FLConfig, make_engine
from repro.engine.scaleout import ScaleoutEngine

K, M = 32, 8
train = make_classification(1600, n_features=64, n_classes=10, seed=0)
test = make_classification(200, n_features=64, n_classes=10, seed=1)
kw = dict(n_clients=K, m=M, rounds=3, strategy="fedlecc",
          strategy_kwargs={"J": 3}, hidden=(16,), eval_samples=16,
          eval_every=1, target_hd=0.8, seed=0)

placed_from = []
place = ScaleoutEngine._place_clients
def recording(self, *arrays):
    placed_from.extend(type(a).__name__ for a in arrays)
    return place(self, *arrays)
ScaleoutEngine._place_clients = recording


def engines(**extra):
    comp = make_engine(FLConfig(backend="compiled", **kw, **extra), train, test, 10)
    scale = make_engine(FLConfig(backend="scaleout", **kw, **extra), train, test, 10)
    return comp, scale


def record_polls(engine):
    polls = []
    poll = engine.poll_losses
    def hooked(rnd, key):
        out = poll(rnd, key)
        polls.append(np.asarray(out))
        return out
    engine.poll_losses = hooked
    return polls


def force(engine, cohorts):
    engine.select = lambda rnd, losses: np.asarray(cohorts[rnd])


def gap(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


out = {}
comp, scale = engines()
pc, ps = record_polls(comp), record_polls(scale)
rc, rs = list(comp.rounds(3)), list(scale.rounds(3))
out["matches_compiled"] = {
    "pods": scale.n_pods,
    "selected": [list(a.selected) == list(b.selected) for a, b in zip(rc, rs)],
    "poll_gap": max(float(np.max(np.abs(a - b))) for a, b in zip(pc, ps)),
    "loss_gap": max(abs(a.mean_selected_loss - b.mean_selected_loss) for a, b in zip(rc, rs)),
    "eval_gap": max(abs(a.test_loss - b.test_loss) for a, b in zip(rc, rs)),
    "param_gap": gap(comp.params, scale.params),
}

# one pod holds the whole cohort, then the last one, then every pod
cohorts = [np.arange(M), np.arange(K - M, K), np.arange(0, K, K // M)]
comp, scale = engines()
force(comp, cohorts); force(scale, cohorts)
counters = [scale._train_counters(c) for c in cohorts]
rc, rs = list(comp.rounds(3)), list(scale.rounds(3))
out["one_pod"] = {
    "selected": [list(r.selected) for r in rs],
    "loss_gap": max(abs(a.mean_selected_loss - b.mean_selected_loss) for a, b in zip(rc, rs)),
    "param_gap": gap(comp.params, scale.params),
    "counters": counters, "row_bytes": scale._row_bytes,
}
out["no_retrace"] = {"round": scale._round_fn._cache_size(),
                     "poll": scale._poll_losses._cache_size(),
                     "evaluate": scale._evaluate._cache_size()}

# the program's inputs: sharded stacks, replicated global parameters
seen = []
round_fn = scale._round_fn
def spy(*args):
    seen.append(jax.tree.map(lambda a: (tuple(np.shape(a)), str(getattr(a, "sharding", None))), args))
    return round_fn(*args)
scale._round_fn = spy
scale.select = lambda rnd, losses: np.arange(M)
next(scale.rounds(1))
params_shapes = [tuple(np.shape(x)) for x in jax.tree.leaves(scale.params)]
got_params = [s for s, _ in jax.tree.leaves(seen[0][0], is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str))]
others = [s for s, _ in jax.tree.leaves(seen[0][1:], is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str))]
shards = sorted((int(s.data.shape[0]), s.device.id) for s in scale.xs.addressable_shards)
out["sharded"] = {
    "spec": [str(p) for p in (scale.xs.sharding.spec, scale.ys.sharding.spec, scale.mask.sharding.spec)],
    "shards": shards, "placed_from": placed_from[-3:],
    "params_in": got_params == params_shapes,
    "k_stacked": [s for s in others if len(s) > 1 and s[0] == K and s[1:] in params_shapes],
}

# the benchmark's way: seeded weights committed to device 0
dev0 = jax.devices()[0]
weights = jax.device_put(jax.tree.map(lambda p: jnp.asarray(p) * 0.5, comp.params), dev0)
comp, scale = engines()
comp.params = weights
scale.params = weights
base = jax.tree.map(jnp.copy, weights)
rc, rs = list(comp.rounds(2)), list(scale.rounds(2))
norms = jax.jit(lambda p, b: [jnp.sqrt(jnp.sum((x - y) ** 2)) for x, y in
                               zip(jax.tree.leaves(p), jax.tree.leaves(b))])
jax.block_until_ready(scale.params)
out["installed"] = {
    "selected": [list(a.selected) == list(b.selected) for a, b in zip(rc, rs)],
    "param_gap": gap(comp.params, scale.params),
    "norm_gap": gap(norms(comp.params, base), norms(scale.params, base)),
    "moved": float(sum(np.asarray(norms(scale.params, base)))),
}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return json.loads(lines[-1][len("RESULT "):])


def test_cohort_round_matches_compiled(result):
    r = result["matches_compiled"]
    assert r["pods"] == 4
    assert all(r["selected"])
    assert r["poll_gap"] < 1e-5
    assert r["loss_gap"] < 1e-5 and r["eval_gap"] < 1e-5
    assert r["param_gap"] < 1e-5


def test_cohort_wholly_on_one_pod(result):
    r = result["one_pod"]
    assert r["selected"] == [list(range(8)), list(range(24, 32)),
                             list(range(0, 32, 4))]
    assert r["loss_gap"] < 1e-5 and r["param_gap"] < 1e-5
    # slot j trains on pod j // 2: all but the first pod's two slots move
    # off pod 0 (round 0), off pod 3 (round 1); spread evenly, none move
    assert [c["cohort_per_pod"] for c in r["counters"]] == [
        "8/0/0/0", "0/0/0/8", "2/2/2/2"]
    assert [c["gathered_bytes"] for c in r["counters"]] == [
        6 * r["row_bytes"], 6 * r["row_bytes"], 0]


def test_no_retrace_whatever_the_split(result):
    assert result["no_retrace"] == {"round": 1, "poll": 1, "evaluate": 1}


def test_stacks_sharded_over_pods_without_stacked_params(result):
    r = result["sharded"]
    assert all(spec.startswith("PartitionSpec('pod'") for spec in r["spec"])
    assert [rows for rows, _ in r["shards"]] == [8, 8, 8, 8]
    assert sorted(dev for _, dev in r["shards"]) == [0, 1, 2, 3]
    assert r["placed_from"] == ["ndarray"] * 3
    assert r["params_in"] and r["k_stacked"] == []


def test_installed_device0_weights_then_rounds(result):
    r = result["installed"]
    assert all(r["selected"])
    assert r["param_gap"] < 1e-5 and r["norm_gap"] < 1e-5
    assert r["moved"] > 0
