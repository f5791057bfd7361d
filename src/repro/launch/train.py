"""Training driver: --arch <id> [--shape train_4k] with the full production
stack (mesh, shardings, microbatching, AdamW, checkpointing, fault tolerance).

On CPU use --debug to train a reduced config on a 1x1 mesh — that is the
end-to-end example path. On a TPU host the same driver runs the full config
data-parallel over every chip present.

Run:  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --debug \
          --steps 30 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeSpec
from repro.launch import compile_cache, mesh as mesh_mod
from repro.launch.steps import TrainHParams, assemble_train, default_micro
from repro.models import get_model
from repro.train.loop import LoopConfig, train
from repro.data.pipeline import DataConfig, SyntheticLM


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--debug", action="store_true",
                    help="reduced config + tiny shape on local devices")
    ap.add_argument("--seq-len", type=int, default=64, help="debug seq len")
    ap.add_argument("--batch", type=int, default=4, help="debug global batch")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch]
    if args.debug:
        cfg = reduced(cfg)
        shape = ShapeSpec("debug", "train", args.seq_len, args.batch)
        mesh = mesh_mod.make_debug_mesh(1, 1)
    else:
        shape = cfg.shape(args.shape)
        mesh = mesh_mod.local_mesh()
    n_micro = args.n_micro or (1 if args.debug else default_micro(cfg, shape))
    hp = TrainHParams(n_micro=n_micro, peak_lr=args.lr,
                      total_steps=args.steps)
    step, arg_specs, in_sh, out_sh, hp = assemble_train(cfg, shape, mesh, hp)
    with mesh:
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0, 1))
        model = get_model(cfg)
        lc = LoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every)
        data = SyntheticLM(cfg, shape, DataConfig(n_micro=n_micro))
        stats = train(cfg, shape, jitted, model.init_params, lc,
                      n_micro=n_micro, data=data)
    print(f"done: {stats}")
    return stats


if __name__ == "__main__":
    compile_cache.enable()
    main()
