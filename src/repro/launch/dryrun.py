import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production mesh, without allocating a single parameter.

For each pair this driver:
  1. builds the full config (long_500k gets the documented SWA variant
     for full-attention archs — DESIGN.md §5),
  2. eval_shape's params (and caches for decode shapes),
  3. assembles in/out shardings from the baseline policy (repro.sharding),
  4. ``jit(step).lower(**ShapeDtypeStructs).compile()``,
  5. records memory_analysis / cost_analysis / per-collective bytes
     (parsed from the compiled HLO) into a JSONL for the roofline pass.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
      --shape train_4k --mesh single --out results/dryrun.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

NOTE: the XLA_FLAGS line above MUST run before any other import — jax
locks the device count at first init.  Do not import this module from
code that already initialized jax with one device.
"""

import argparse
import json
import re
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_configs
from repro.configs.inputs import decode_specs, input_specs, long_context_variant
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import (
    cache_specs,
    decode_step,
    init_cache,
    init_transformer,
    loss_fn,
    prefill,
    transformer_specs,
)
from repro.sharding import make_policy

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(?:\.\d+)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum output bytes of every collective op in the HLO, by kind.

    Methodology (EXPERIMENTS.md §Dry-run): we count each collective's
    *result* size — for all-gather that is the gathered tensor, for
    all-reduce the reduced tensor, for reduce-scatter the scattered
    shard.  This approximates on-wire traffic to within the ring-factor
    (2(n−1)/n for all-reduce) which we fold into the roofline constant.
    """
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        lhs, rhs = line.split("=", 1)
        m = _COLLECTIVE_RE.search(rhs)
        if not m:
            continue
        kind, suffix = m.group(1), m.group(2)
        if suffix == "-done":
            continue  # async pair: the -start result already counted
        # the result type(s) sit between '=' and the op name
        shapes = _SHAPE_RE.findall(rhs[: m.start()])
        total = 0.0
        for dt, dims in shapes:
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + total
    return out


def _batch_logical_axes(cfg, kind):
    ax = {}
    if cfg.input_mode == "tokens":
        ax["tokens"] = ("batch", "seq_in")
    elif cfg.input_mode == "frames":
        ax["frames"] = ("batch", "seq_in", None)
    else:
        ax["patches"] = ("batch", None, None)
        ax["tokens"] = ("batch", "seq_in")
    if kind == "train":
        ax["labels"] = ("batch", "seq_in")
    return ax


def build_step(cfg, mesh, shape, lr=1e-3, policy_variant: str = "baseline"):
    """Returns (fn, arg_specs, arg_shardings, donate) for the shape kind."""
    if policy_variant == "fsdp" and not cfg.act_shard:
        from dataclasses import replace as _rep
        cfg = _rep(cfg, act_shard="dp_all")
    policy = make_policy(
        mesh, shape.global_batch,
        shard_seq=(shape.kind == "decode" and shape.global_batch == 1),
        variant=policy_variant,
    )
    pshapes = jax.eval_shape(partial(init_transformer, cfg=cfg), jax.random.PRNGKey(0))
    pspecs = transformer_specs(cfg)
    pshard = policy.shardings(pspecs, pshapes)

    if shape.kind == "train":
        batch = input_specs(cfg, shape)
        bspec = _batch_logical_axes(cfg, "train")
        bshard = {
            k: NamedSharding(mesh, policy.spec_for(bspec[k], batch[k].shape)) for k in batch
        }

        def train_step(params, batch):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, cfg, batch, mesh
            )
            params = jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype), params, grads)
            return params, loss

        return train_step, (pshapes, batch), ((pshard, bshard), (pshard, NamedSharding(mesh, P()))), (0,)

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        bspec = _batch_logical_axes(cfg, "prefill")
        bshard = {
            k: NamedSharding(mesh, policy.spec_for(bspec[k], batch[k].shape)) for k in batch
        }
        cshapes = jax.eval_shape(partial(init_cache, cfg, shape.global_batch, shape.seq_len))
        cspecs = cache_specs(cfg)
        cshard = policy.shardings(cspecs, cshapes)

        def prefill_step(params, batch):
            return prefill(params, cfg, batch, max_len=shape.seq_len, mesh=mesh)

        out_shard = (NamedSharding(mesh, P()), cshard)
        return prefill_step, (pshapes, batch), ((pshard, bshard), out_shard), ()

    # decode
    batch = decode_specs(cfg, shape)
    bshard = {k: NamedSharding(mesh, P()) for k in batch}
    cshapes = jax.eval_shape(partial(init_cache, cfg, shape.global_batch, shape.seq_len))
    cspecs = cache_specs(cfg)
    cshard = policy.shardings(cspecs, cshapes)

    def serve_step(params, batch, cache, pos):
        logits, cache = decode_step(params, cfg, batch, cache, pos, mesh=mesh)
        return logits, cache

    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    arg_specs = (pshapes, batch, cshapes, pos_spec)
    in_shard = (pshard, bshard, cshard, NamedSharding(mesh, P()))
    out_shard = (NamedSharding(mesh, P()), cshard)
    return serve_step, arg_specs, (in_shard, out_shard), (2,)


def _probe_cfg(cfg, shape, n_layers: int):
    """Loop-free cost-probe variant: XLA's cost_analysis counts a while
    body ONCE regardless of trip count, so the production lowering (scan
    over layers + chunked attention/loss scans) under-reports FLOPs.
    Probes remove every data-dependent loop: `n_layers` ∈ {1, 2} with the
    layer scan fully unrolled, attention/loss/ssm chunks = full sequence.
    Roofline totals are reconstructed as
        body = cost(P2) − cost(P1);  outside = cost(P1) − body;
        total = outside + L·body
    (per-layer costs, incl. per-layer FSDP gathers and grad reductions,
    are linear in L; methodology recorded in EXPERIMENTS.md §Dry-run).
    """
    from dataclasses import replace

    s = shape.seq_len
    kw = dict(
        n_layers=n_layers,
        scan_unroll=n_layers,
        attn_chunk=s,
        loss_chunk=s,
        remat=False,
    )
    if cfg.ssm is not None:
        from dataclasses import replace as rep

        if cfg.ssm.family == "xlstm" and s > 8192:
            # full-chunk mLSTM would create an S×S×H intra-chunk temp per
            # layer; cap at 8192 and accept a bounded (≤ S/8192×) undercount
            # of the recurrent-core term (EXPERIMENTS.md §Dry-run note)
            kw["ssm"] = rep(cfg.ssm, chunk=8192)
        else:
            kw["ssm"] = rep(cfg.ssm, chunk=s)
    return replace(cfg, **kw)


def _lower_cost(cfg, mesh, shape, policy_variant: str = "baseline"):
    fn, arg_specs, (in_shard, out_shard), donate = build_step(
        cfg, mesh, shape, policy_variant=policy_variant
    )
    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard,
                    donate_argnums=donate)
            .lower(*arg_specs)
            .compile()
        )
    cost = compiled.cost_analysis()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes(hlo),
    }


def _combine(outside, body, L):
    def add(a, b, s):
        return a + s * b

    coll = {}
    for k in set(outside["coll"]) | set(body["coll"]):
        coll[k] = outside["coll"].get(k, 0.0) + L * body["coll"].get(k, 0.0)
    return {
        "flops": outside["flops"] + L * body["flops"],
        "bytes": outside["bytes"] + L * body["bytes"],
        "coll": coll,
    }


def probe_costs(cfg, mesh, shape, policy_variant: str = "baseline") -> dict:
    """Loop-corrected cost model from two probe lowers (see _probe_cfg)."""
    p1 = _lower_cost(_probe_cfg(cfg, shape, 1), mesh, shape, policy_variant)
    p2 = _lower_cost(_probe_cfg(cfg, shape, 2), mesh, shape, policy_variant)
    body = {
        "flops": max(p2["flops"] - p1["flops"], 0.0),
        "bytes": max(p2["bytes"] - p1["bytes"], 0.0),
        "coll": {
            k: max(p2["coll"].get(k, 0.0) - p1["coll"].get(k, 0.0), 0.0)
            for k in set(p1["coll"]) | set(p2["coll"])
        },
    }
    outside = {
        "flops": max(p1["flops"] - body["flops"], 0.0),
        "bytes": max(p1["bytes"] - body["bytes"], 0.0),
        "coll": {
            k: max(p1["coll"].get(k, 0.0) - body["coll"].get(k, 0.0), 0.0)
            for k in set(p1["coll"]) | set(body["coll"])
        },
    }
    total = _combine(outside, body, cfg.n_layers)
    return {"per_layer": body, "outside": outside, "total": total}


def run_one(arch: str, shape_name: str, multi_pod: bool, record_hlo: bool = False,
            policy_variant: str = "baseline") -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    if shape_name == "long_500k":
        cfg = long_context_variant(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, arg_specs, (in_shard, out_shard), donate = build_step(
        cfg, mesh, shape, policy_variant=policy_variant
    )
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            fn, in_shardings=in_shard, out_shardings=out_shard, donate_argnums=donate
        )
        lowered = jitted.lower(*arg_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)
    # loop-corrected cost model (single-pod only: the roofline table reads
    # single-pod records; multi-pod entries prove lowering/sharding)
    probes = None
    if not multi_pod:
        try:
            probes = probe_costs(cfg, mesh, shape, policy_variant)
        except Exception as e:  # probes are best-effort; record why
            probes = {"error": f"{type(e).__name__}: {e}"}
    rec = {
        "arch": arch,
        "config_name": cfg.name,
        "shape": shape_name,
        "policy": policy_variant,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "kind": shape.kind,
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "probes": probes,
        "memory": {
            "argument_size": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "hlo_len": len(hlo),
    }
    if record_hlo:
        rec["hlo_head"] = hlo[:5000]
    return rec


def run_federated(arch: str, local_steps: int = 4, batch_per_client: int = 128,
                  seq: int = 4096, compress_bits: int = 0) -> dict:
    """Lower + compile the scale-out FedLECC round (DESIGN.md §3b): clients
    = pods, local SGD steps inside shard_map(manual={'pod'}), aggregation
    = selection-weighted psum over 'pod'.  The paper-representative
    dry-run artifact.  Built via the engine API (`repro.engine.scaleout`),
    the same entry `ScaleoutEngine` and every other consumer of the mesh
    round use."""
    from repro.engine.scaleout import make_scaleout_round

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=True)
    n_pods = mesh.shape["pod"]
    policy = make_policy(mesh, batch_per_client * n_pods)
    pshapes = jax.eval_shape(partial(init_transformer, cfg=cfg), jax.random.PRNGKey(0))
    pspecs = transformer_specs(cfg)

    def stacked_spec(axes, shape):
        inner = policy.spec_for(tuple(axes), shape[1:])
        return NamedSharding(mesh, P("pod", *inner))

    def is_axes(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, tuple, type(None))) for e in x
        )

    flat_specs = jax.tree.leaves(pspecs, is_leaf=is_axes)
    flat_shapes = jax.tree.leaves(pshapes)
    stacked_shapes = jax.tree.unflatten(
        jax.tree.structure(pshapes),
        [jax.ShapeDtypeStruct((n_pods,) + s.shape, s.dtype) for s in flat_shapes],
    )
    pshard = jax.tree.unflatten(
        jax.tree.structure(pshapes),
        [stacked_spec(sp, (n_pods,) + sh.shape) for sp, sh in zip(flat_specs, flat_shapes)],
    )
    batch = {
        "tokens": jax.ShapeDtypeStruct((n_pods, batch_per_client, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((n_pods, batch_per_client, seq), jnp.int32),
    }
    bshard = {k: NamedSharding(mesh, P("pod", "data", None)) for k in batch}
    w = jax.ShapeDtypeStruct((n_pods,), jnp.float32)
    wshard = NamedSharding(mesh, P("pod"))

    round_fn = make_scaleout_round(cfg, mesh, lr=1e-3, local_steps=local_steps,
                                   compress_bits=compress_bits)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            round_fn,
            in_shardings=(pshard, bshard, wshard),
            out_shardings=(pshard, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(stacked_shapes, batch, w)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    rec = {
        "arch": arch,
        "shape": f"fedround_b{batch_per_client}x{seq}_E{local_steps}_q{compress_bits}",
        "mesh": "multi", "kind": "federated_round",
        "n_devices": 512,
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": collective_bytes(hlo),
        "memory": {
            "argument_size": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size": int(getattr(mem, "temp_size_in_bytes", 0)),
        },
        "t_total_s": round(time.time() - t0, 2),
        "hlo_len": len(hlo),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="all (arch × shape) pairs")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--policy", default="baseline", choices=["baseline", "fsdp"])
    ap.add_argument(
        "--federated", action="store_true",
        help="lower the scale-out FedLECC round instead of plain steps",
    )
    args = ap.parse_args()

    if args.federated:
        arch = args.arch or "qwen3-14b"
        rc = 0
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        for bits in (0, 8):
            try:
                rec = run_federated(arch, compress_bits=bits)
                status = "OK"
            except Exception as e:
                rec = {"arch": arch, "shape": f"fedround_q{bits}", "mesh": "multi",
                       "error": f"{type(e).__name__}: {e}"}
                status = "FAIL"
                rc = 1
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            detail = rec.get("error") or (
                f"flops={rec.get('flops', 0):.3e} "
                f"coll={ {k: round(v/1e9,2) for k, v in rec.get('collective_bytes', {}).items()} }GB"
            )
            print(f"[{status}] federated_round {arch} q{bits}: {detail}")
        sys.exit(rc)

    archs = list_configs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                key = (arch, shape, mesh_kind)
                if key in done:
                    continue
                try:
                    rec = run_one(arch, shape, multi_pod=(mesh_kind == "multi"),
                                  policy_variant=args.policy)
                    status = "OK"
                except Exception as e:  # record failures — they are bugs
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "error": f"{type(e).__name__}: {e}",
                    }
                    status = "FAIL"
                    n_fail += 1
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                msg = rec.get("error", f"compile={rec.get('t_compile_s', '?')}s flops={rec.get('flops', 0):.3e}")
                print(f"[{status}] {arch} × {shape} × {mesh_kind}: {msg}", flush=True)
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
