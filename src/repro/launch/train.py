"""Training launcher: config -> mesh -> sharded train loop under the
fault-tolerant supervisor (checkpoint/restart, straggler watchdog).

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
      --smoke --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

On a real TPU slice the same entry point runs under
``jax.distributed.initialize()``; in this container it runs on the local
device(s). ``--data-par/--model-par`` set the mesh; elastic restarts may use a
different mesh shape (checkpoints reshard on load).
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data.synthetic import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import registry as R
from repro.runtime import supervisor
from repro.train import optim, steps


def main(argv=None) -> supervisor.RunReport:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify-ckpt", action="store_true",
                    help="skip the per-leaf CRC check on checkpoint "
                         "restore (verification is the default)")
    args = ap.parse_args(argv)

    print(f"[train] compile cache: {enable_compile_cache()}")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(args.data_par, args.model_par)
    print(f"[train] {cfg.name}: mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    state_specs = steps.train_state_specs(cfg)
    n_params = R.param_count(state_specs["params"])
    print(f"[train] params: {n_params/1e6:.1f}M")

    ocfg = optim.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                           total_steps=args.steps)
    batch0 = {"tokens": np.zeros((args.batch, args.seq), np.int32)}
    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = np.zeros((args.batch, cfg.encoder_seq, cfg.d_model),
                                   np.dtype(cfg.dtype))
    if cfg.family == "vlm":
        n_img = min(cfg.vlm_image_tokens, args.seq // 2)
        extra["image_embeds"] = np.zeros((args.batch, n_img, cfg.d_model),
                                         np.dtype(cfg.dtype))
    batch0.update(extra)
    fn, state_sh, batch_sh = steps.jit_train_step(cfg, ocfg, mesh,
                                                  state_specs, batch0)
    # Born sharded: each device makes only its own shards of the state, so
    # no device ever holds the whole of it.
    state = jax.jit(functools.partial(steps.train_state_init, cfg=cfg),
                    out_shardings=state_sh)(jax.random.PRNGKey(args.seed))

    stream = TokenStream(cfg.vocab, args.seq, args.batch, seed=args.seed)

    def batch_at(step: int):
        b = dict(stream.batch_at(step))
        for k, v in extra.items():
            b[k] = v
        return jax.device_put(b, batch_sh)

    scfg = supervisor.SupervisorConfig(ckpt_dir=args.ckpt,
                                       save_every=args.save_every,
                                       verify_ckpt=not args.no_verify_ckpt)
    state, report = supervisor.run(fn, state, batch_at, args.steps, scfg,
                                   state_shardings=state_sh)
    print(f"[train] done: steps={report.steps_run} failures={report.failures} "
          f"first loss={report.losses[0]:.4f} last loss={report.losses[-1]:.4f}")
    return report


if __name__ == "__main__":
    main()
