"""Scale-out federated round: FedLECC on the production mesh.

The paper's cross-device loop maps onto the multi-pod mesh as (DESIGN.md
§3b):

- **clients ↔ pods** — the ``pod`` mesh axis is *manual* (shard_map), so
  each pod's parameter replica evolves independently during local steps;
- ``data``/``model`` stay *auto* inside the body — GSPMD runs ordinary
  data/tensor parallelism within each client;
- **aggregation ≡ weighted psum over ``pod``** — the FedLECC selection
  mask enters as the per-client weight vector (0 = not selected), so
  "only m of K clients upload" becomes "the all-reduce carries zero
  weight for unselected clients";
- each client reports its local loss, feeding the next round's
  host-side Algorithm 1.

``make_federated_round`` builds the jit-able round; the dry-run lowers it
as the paper-representative artifact.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.transformer import loss_fn

__all__ = ["make_federated_round", "stack_for_clients"]


def stack_for_clients(params, n_clients: int):
    """Replicate global params into per-client stacks (leading axis)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (n_clients,) + p.shape), params)


def make_federated_round(cfg, mesh, lr: float, local_steps: int = 4,
                         compress_bits: int = 0):
    """Returns ``round_fn(stacked_params, batch, weights) ->
    (new_stacked_params, client_losses)``.

    stacked_params: per-client parameter stacks, leading axis = n_pods,
        sharded P("pod", ...).
    batch: leaves with leading client axis, e.g. tokens
        (n_pods, B_loc, S) sharded P("pod", "data", None).
    weights: (n_pods,) fp32 — FedLECC aggregation weights (sum to 1;
        zero = client not selected this round).
    compress_bits: 0 = exact fp32 psum of weighted params (baseline);
        8 = §Perf hillclimb 3: each client's *delta* is int8-quantized
        (per-leaf scale, deterministic round-to-nearest inside the
        compiled round) and aggregation becomes an int8 all-gather over
        the client axis + local weighted dequant-sum — 8× fewer bytes on
        the pod interconnect than the fp32 ring all-reduce.
    """
    n_pods = mesh.shape["pod"]

    def local_sgd(params, batch):
        def step(p, _):
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, cfg, batch, None)
            p = jax.tree.map(lambda w, gw: (w - lr * gw).astype(w.dtype), p, g)
            return p, l

        params, losses = jax.lax.scan(step, params, None, length=local_steps)
        return params, losses.mean()

    def body(stacked_params, batch, weights):
        # local (manual-over-pod) views carry a leading axis of size 1
        params = jax.tree.map(lambda a: a[0], stacked_params)
        local_batch = jax.tree.map(lambda a: a[0], batch)
        w = weights[0]
        params_end, mean_loss = local_sgd(params, local_batch)
        # FedAvg with the FedLECC participation mask: θ ← Σ_i w_i θ_i.
        # Unselected clients (w=0) contribute nothing but still receive
        # the aggregated model (the psum result is replicated over pod).
        agg = jax.tree.map(
            lambda p: jax.lax.psum((w * p.astype(jnp.float32)), "pod").astype(p.dtype),
            params_end,
        )
        losses = jax.lax.all_gather(mean_loss, "pod")
        return jax.tree.map(lambda a: a[None], agg), losses

    def train_body(stacked_params, batch, weights):
        """Compressed variant: local training only; aggregation happens in
        a second, manual-over-{pod,model} shard_map (quantize_agg) so the
        int8 all-gather moves exactly the per-device shard — GSPMD cannot
        replicate the operand first (§Perf hillclimb 3, iteration 2)."""
        params = jax.tree.map(lambda a: a[0], stacked_params)
        local_batch = jax.tree.map(lambda a: a[0], batch)
        params_end, mean_loss = local_sgd(params, local_batch)
        losses = jax.lax.all_gather(mean_loss, "pod")
        return jax.tree.map(lambda a: a[None], params_end), losses

    qmax = 2 ** (compress_bits - 1) - 1 if compress_bits else 0

    def agg_body(stacked_end, stacked_start, weights):
        p_end = jax.tree.map(lambda a: a[0], stacked_end)
        p_start = jax.tree.map(lambda a: a[0], stacked_start)
        w = weights[0]

        def one(e, s0):
            delta = e.astype(jnp.float32) - s0.astype(jnp.float32)
            # per-shard scale: cheap, local, and finer-grained than a
            # global per-leaf scale (documented algorithm variant)
            scale = jnp.maximum(jnp.max(jnp.abs(delta)), 1e-12) / qmax
            q = jnp.clip(jnp.round(delta / scale), -qmax - 1, qmax).astype(jnp.int8)
            q_all = jax.lax.all_gather(q, "pod")              # int8 on the wire
            s_all = jax.lax.all_gather(scale * w, "pod")      # (n_pods,) fp32
            wexp = s_all.reshape((-1,) + (1,) * delta.ndim)
            agg_delta = jnp.sum(q_all.astype(jnp.float32) * wexp, axis=0)
            return (s0.astype(jnp.float32) + agg_delta).astype(e.dtype)

        agg = jax.tree.map(one, p_end, p_start)
        return jax.tree.map(lambda a: a[None], agg)

    def round_fn(stacked_params, batch, weights):
        p_specs = jax.tree.map(lambda _: P("pod"), stacked_params)
        b_specs = jax.tree.map(lambda _: P("pod"), batch)
        if not compress_bits:
            f = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(p_specs, b_specs, P("pod")),
                out_specs=(p_specs, P()),
                axis_names={"pod"},
                check_vma=False,
            )
            return f(stacked_params, batch, weights)
        # compressed: train (manual pod, auto data/model), then aggregate
        # (manual pod+model: per-shard int8 quantize + gather + sum)
        f_train = jax.shard_map(
            train_body,
            mesh=mesh,
            in_specs=(p_specs, b_specs, P("pod")),
            out_specs=(p_specs, P()),
            axis_names={"pod"},
            check_vma=False,
        )
        ends, losses = f_train(stacked_params, batch, weights)
        # manual specs for the aggregation: leading pod axis + the storage
        # sharding of every leaf (so shards stay local through the gather)
        from repro.models.transformer import transformer_specs
        from repro.sharding import make_policy

        policy = make_policy(mesh, batch_size=0)
        pspecs_logical = transformer_specs(cfg)
        def is_axes(x):
            return isinstance(x, tuple) and all(
                isinstance(e, (str, tuple, type(None))) for e in x
            )

        flat_l = jax.tree.leaves(pspecs_logical, is_leaf=is_axes)
        flat_p = jax.tree.leaves(stacked_params)
        specs = [
            P("pod", *policy.spec_for(sp, leaf.shape[1:]))
            for sp, leaf in zip(flat_l, flat_p)
        ]
        mspecs = jax.tree.unflatten(jax.tree.structure(stacked_params), specs)
        f_agg = jax.shard_map(
            agg_body,
            mesh=mesh,
            in_specs=(mspecs, mspecs, P("pod")),
            out_specs=mspecs,
            axis_names={"pod", "model"},
            check_vma=False,
        )
        new_stacked = f_agg(ends, stacked_params, weights)
        return new_stacked, losses

    return round_fn
