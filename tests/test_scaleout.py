"""Scale-out federated round on a virtual 8-device mesh (subprocess so the
device-count flag doesn't leak into other tests).

Verifies the DESIGN.md §3b mapping end-to-end on a reduced config:
  - the round lowers and runs on a (pod=2, data=2, model=2) mesh,
  - aggregation equals the host-side weighted average of independently
    trained client params (vmap oracle),
  - a zero-weight (unselected) client does not influence the result.
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.inputs import dummy_batch
from repro.launch.mesh import make_host_mesh
from repro.federated.scaleout import make_federated_round, stack_for_clients
from repro.models.transformer import init_transformer, loss_fn

cfg = get_config("qwen3-14b", reduced=True)
mesh = make_host_mesh(data=2, model=2, pod=2)
params = init_transformer(jax.random.PRNGKey(0), cfg)
n_pods = 2
B, S = 4, 64

batches = [dummy_batch(cfg, B, S, seed=s) for s in (10, 11)]
batch = {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}
weights = jnp.asarray([0.25, 0.75], jnp.float32)

round_fn = make_federated_round(cfg, mesh, lr=0.05, local_steps=3)
stacked = stack_for_clients(params, n_pods)
with jax.set_mesh(mesh):
    new_stacked, losses = jax.jit(round_fn)(stacked, batch, weights)

# oracle: train each client independently on one device, average by hand
def local(params, b):
    p = params
    for _ in range(3):
        g = jax.grad(lambda q: loss_fn(q, cfg, b)[0])(p)
        p = jax.tree.map(lambda w, gw: w - 0.05 * gw, p, g)
    return p

locals_ = [local(params, b) for b in batches]
want = jax.tree.map(lambda a, b: 0.25 * a + 0.75 * b, locals_[0], locals_[1])

got = jax.tree.map(lambda a: a[0], new_stacked)
errs = [float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
assert max(errs) < 1e-3, f"aggregation mismatch: {max(errs)}"

# both slots carry the same aggregated params
diff = [float(jnp.max(jnp.abs(a[0].astype(jnp.float32) - a[1].astype(jnp.float32))))
        for a in jax.tree.leaves(new_stacked)]
assert max(diff) < 1e-6, "aggregated params must be identical across clients"

# zero-weight client is excluded: w=(0,1) → result == client 1 alone
with jax.set_mesh(mesh):
    only1, _ = jax.jit(round_fn)(stack_for_clients(params, 2), batch,
                                 jnp.asarray([0.0, 1.0], jnp.float32))
got1 = jax.tree.map(lambda a: a[0], only1)
errs1 = [float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
         for x, y in zip(jax.tree.leaves(got1), jax.tree.leaves(locals_[1]))]
assert max(errs1) < 1e-3, f"mask gating failed: {max(errs1)}"
assert losses.shape == (2,) and bool(jnp.all(jnp.isfinite(losses)))

# compressed (int8 delta) aggregation tracks the exact result
round_q8 = make_federated_round(cfg, mesh, lr=0.05, local_steps=3, compress_bits=8)
with jax.set_mesh(mesh):
    new_q8, _ = jax.jit(round_q8)(stack_for_clients(params, 2), batch, weights)
got_q8 = jax.tree.map(lambda a: a[0], new_q8)
rel = []
for x, y in zip(jax.tree.leaves(got_q8), jax.tree.leaves(want)):
    num = float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
    den = float(jnp.max(jnp.abs(y.astype(jnp.float32)))) + 1e-6
    rel.append(num / den)
assert max(rel) < 0.05, f"compressed aggregation too far from exact: {max(rel)}"
print("SCALEOUT_OK")
"""


@pytest.mark.slow
def test_federated_round_on_virtual_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert "SCALEOUT_OK" in r.stdout, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
