"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch hymba-1.5b --full \\
      --devices 8 --mesh 4x2          # spawns with fake devices

Uses the REDUCED config by default (CPU-trainable); --full selects the
assigned full config (only sensible on real accelerators).  --devices k
takes the first k accelerator devices in-process; on the CPU backend the
launcher re-executes itself with
XLA_FLAGS=--xla_force_host_platform_device_count=k.
"""

import argparse
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (accelerators only)")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--mesh", default="",
                    help="DxM mesh, e.g. 4x2 (defaults to devicesx1)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import take_devices
    enable_compile_cache()
    devices = take_devices(args.devices,
                           ["-m", "repro.launch.train", *sys.argv[1:]])

    from repro.configs import get_arch
    from repro.data import SyntheticLMDataset, make_train_iterator
    from repro.dist.ctx import sharding_rules
    from repro.dist.sharding import param_shardings, train_batch_shardings
    from repro.optim import cosine_schedule, make_optimizer
    from repro.train import make_train_state, make_train_step
    from repro.train.trainer import Trainer

    arch = get_arch(args.arch)
    cfg = arch.config if args.full else arch.reduced
    print(f"arch={args.arch} cfg={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={len(devices)}")

    opt = make_optimizer(lr=cosine_schedule(3e-3, 10, args.steps))
    step_fn, _ = make_train_step(cfg, opt, n_loss_chunks=2)
    state = make_train_state(cfg, jax.random.PRNGKey(0), opt)
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            global_batch=args.batch, seed=0)

    if len(devices) > 1:
        d, m = (map(int, args.mesh.split("x")) if args.mesh
                else (len(devices), 1))
        mesh = jax.make_mesh((d, m), ("data", "model"), devices=devices)
        ps = param_shardings(cfg, mesh)
        state = state._replace(
            params=jax.device_put(state.params, ps),
            opt=state.opt._replace(m=jax.device_put(state.opt.m, ps),
                                   v=jax.device_put(state.opt.v, ps)))
        bs = train_batch_shardings(cfg, mesh)

        def wrapped(state, batch):
            with sharding_rules(mesh):
                return step_fn(state, batch)

        trainer = Trainer(cfg, wrapped, args.ckpt_dir, checkpoint_every=50)
        with mesh:
            it = make_train_iterator(ds, shardings=bs)
            state, rep = trainer.run(state, it, args.steps)
    else:
        trainer = Trainer(cfg, step_fn, args.ckpt_dir, checkpoint_every=50)
        state, rep = trainer.run(state, make_train_iterator(ds), args.steps)

    print(f"done: {rep.steps_done} steps, loss {rep.losses[0]:.3f} -> "
          f"{rep.final_loss:.3f}, stragglers={len(rep.straggler_steps)}")


if __name__ == "__main__":
    main()
