"""Training launcher.

Two modes:
- host (default): really train on the local devices — reduced variant of the
  selected architecture unless --full is passed.
- dryrun: lower+compile train_4k for the production mesh (delegates to
  repro.launch.dryrun so the 512-device XLA flag is set correctly).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --steps 50
"""

import argparse
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the full (not reduced) config")
    ap.add_argument("--mode", choices=("host", "dryrun"), default="host")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()

    if args.mode == "dryrun":
        from subprocess import run
        # a CPU harness (512 virtual devices), pinned to the CPU so it
        # never contends for a chip this host may hold
        sys.exit(run([sys.executable, "-m", "repro.launch.dryrun",
                      "--arch", args.arch, "--shape", "train_4k"],
                     env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode)

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import init_params, param_count
    from repro.training.checkpoint import save_checkpoint
    from repro.training.trainer import make_train_step

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    print(f"training {cfg.name}: {param_count(params)/1e6:.1f}M params, "
          f"batch {args.batch} x seq {args.seq}, {args.steps} steps")
    init_fn, step_fn = make_train_step(cfg, remat=True, lr=args.lr,
                                       warmup=min(20, args.steps // 4 + 1))
    state = init_fn(params)
    step = jax.jit(step_fn)
    data = SyntheticLM(DataConfig(cfg.vocab_size, seq_len=args.seq,
                                  batch_size=args.batch, n_symbols=256))
    t0 = time.time()
    for i, raw in zip(range(args.steps), data.batches()):
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.frontend_embed_len:
            fe_len = (cfg.encoder_seq_len if cfg.n_encoder_layers
                      else cfg.frontend_embed_len)
            batch["frontend"] = jnp.zeros(
                (args.batch, fe_len, cfg.frontend_embed_dim), jnp.float32)
        state, m = step(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"({(i+1)*args.batch*args.seq/(time.time()-t0):,.0f} tok/s)")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, state.params, step=args.steps)
        print("saved", args.checkpoint)


if __name__ == "__main__":
    main()
