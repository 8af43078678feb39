"""Training driver — runs any registered architecture on real devices.

On this CPU container it drives the *reduced* configs (the full ones are
dry-run-only); on a TPU slice the same entry point runs the full configs
under the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/x.ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.data.synthetic import make_token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_transformer, loss_fn
from repro.optim import adamw, clip_by_global_norm, chain, warmup_cosine
from repro.optim.optimizers import apply_updates


def make_train_step(cfg, optimizer, mesh=None):
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, cfg, batch, mesh
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss, metrics

    # params/opt_state are reassigned from the step's own outputs in the
    # train loop, so their input buffers can be donated.
    return jax.jit(step, donate_argnums=(0, 1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", default=None,
                    help="checkpoint written by a previous --ckpt run; "
                         "restores params + optimizer state and continues "
                         "from the stored step")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.input_mode != "tokens":
        raise SystemExit(
            f"{args.arch} is {cfg.input_mode}-input; use examples/serve_audio_vlm.py"
        )
    params = init_transformer(jax.random.PRNGKey(args.seed), cfg)
    opt = chain(
        clip_by_global_norm(1.0),
        adamw(warmup_cosine(args.lr, 10, args.steps), weight_decay=0.01),
    )
    opt_state = opt.init(params)
    start = 0
    if args.resume:
        # restore into the freshly initialized structures: the serializer
        # verifies treedef/dtype/shape, so an --arch mismatch fails loudly
        (params, opt_state), meta = load_checkpoint(
            args.resume, like=(params, opt_state)
        )
        if meta.get("arch") != cfg.name:
            raise SystemExit(
                f"--resume checkpoint is for arch {meta.get('arch')!r}, "
                f"not {cfg.name!r}"
            )
        start = int(meta.get("step", 0))
        print(f"resumed {cfg.name} from {args.resume} at step {start}")
    step = make_train_step(cfg, opt)

    data = make_token_stream(args.steps * args.batch, args.seq, cfg.vocab, seed=args.seed)
    t0 = time.time()
    for i in range(start, args.steps):
        lo = i * args.batch
        batch = {
            "tokens": jnp.asarray(data.x[lo : lo + args.batch]),
            "labels": jnp.asarray(data.y[lo : lo + args.batch]),
        }
        params, opt_state, loss, metrics = step(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f} ce {float(metrics['ce']):.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
    if args.ckpt:
        save_checkpoint(
            args.ckpt, (params, opt_state),
            meta={"arch": cfg.name, "step": args.steps},
        )
        print(f"checkpoint written to {args.ckpt}")


if __name__ == "__main__":
    main()
