"""End-to-end LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On real hardware drop --reduced and pass --mesh data,model=16,16 (the
launcher shards state/batches with training.shardspec). On this CPU box the
reduced config exercises the identical code path end-to-end.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.models.sharding import set_rules
from repro.training import optimizer as O
from repro.resilience.checkpoint import Checkpointer
from repro.training.data import DataCfg, make_dataset
from repro.training.shardspec import batch_pspecs, named, state_pspecs
from repro.training.train_step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 'data,model=4,2' (default: single device)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    opt_cfg = O.OptCfg(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                       total_steps=args.steps,
                       grad_compress_bf16=args.grad_compress,
                       mixed_precision=not args.reduced)

    mesh = None
    if args.mesh:
        names, shape = args.mesh.split("=")
        mesh = make_mesh(tuple(int(x) for x in shape.split(",")),
                         tuple(names.split(",")))
        set_rules(mesh)
        jax.set_mesh(mesh)

    params = init_params(jax.random.PRNGKey(0), cfg, max_seq=args.seq)
    state = O.init_state(params, opt_cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"mesh={mesh.shape if mesh else 'single-device'}")

    dcfg = DataCfg(batch=args.batch, seq=args.seq, vocab=cfg.vocab,
                   frames=(cfg.enc_seq, cfg.d_model) if cfg.family == "encdec" else None,
                   mrope=cfg.mrope)
    data = make_dataset(dcfg)

    ck = Checkpointer(args.ckpt_dir, async_save=True) if args.ckpt_dir else None
    start = 0
    if ck and args.resume and ck.latest_step() is not None:
        like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        shardings = named(mesh, state_pspecs(state, mesh)) if mesh else None
        state, start = ck.restore(like, shardings=shardings)
        data.restore(ck.extra()["data"])
        print(f"resumed from step {start}")

    step_fn = make_train_step(cfg, opt_cfg)
    if mesh:
        ex_batch = next(data)
        step_fn = jax.jit(step_fn,
                          in_shardings=(state_pspecs(state, mesh),
                                        batch_pspecs(ex_batch, mesh)),
                          donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    t0 = time.time()
    for i in range(start, args.steps):
        batch = {k: jax.numpy.asarray(v) for k, v in next(data).items()}
        if cfg.embed_inputs:  # vlm stub: tokens -> fake patch embeddings
            rng = np.random.default_rng(i)
            batch["inputs"] = jax.numpy.asarray(
                rng.standard_normal((args.batch, args.seq, cfg.d_model),
                                    ).astype(np.float32))
        state, metrics = step_fn(state, batch)
        if (i + 1) % args.log_every == 0 or i == start:
            dt = (time.time() - t0) / max(i + 1 - start, 1)
            print(f"step {i+1:5d} loss={float(metrics['loss']):.4f} "
                  f"ce={float(metrics['ce']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f} ms/step")
        if ck and (i + 1) % args.ckpt_every == 0:
            ck.save(state, i + 1, extra={"data": data.state()})
    if ck:
        ck.save(state, args.steps, extra={"data": data.state()})
        ck.wait()
    print("done.")


if __name__ == "__main__":
    main()
