"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 32 --seq 512 [--reduced] [--pe-type lightpe1] \
      [--ckpt-dir /tmp/run1] [--resume]

On the CPU container use --reduced (same-family small config); the full
configs are exercised via the dry-run.  The same launcher drives a real
pod: the mesh comes from the runtime device set (jax.distributed is
initialized by the cluster bootstrap before main()).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get as get_cfg, reduced as get_reduced, list_archs
from repro.data import lm_pipeline
from repro.launch.mesh import make_mesh
from repro.models import family_module
from repro.models.layers import activation_sharding
from repro.optim import adamw, sgd_nesterov, warmup_cosine
from repro.train import trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--pe-type", default=None,
                    help="QADAM PE type for QAT numerics "
                         "(fp32|int16|lightpe1|lightpe2|int8)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "sgd_nesterov"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_cfg(args.arch)
    if args.pe_type:
        cfg = cfg.replace(pe_type=args.pe_type)
    mod = family_module(cfg)

    n_dev = jax.device_count()
    mesh = None
    dp = None
    if n_dev > 1:
        model_par = max(d for d in (1, 2, 4, 8, 16) if n_dev % d == 0
                        and cfg.n_heads % d == 0) if cfg.n_heads else 1
        mesh = make_mesh((n_dev // model_par, model_par),
                         ("data", "model"))
        dp = ("data",)

    opt = {"adamw": adamw(warmup_cosine(args.lr, 20, args.steps)),
           "sgd_nesterov": sgd_nesterov(warmup_cosine(args.lr, 20,
                                                      args.steps))}[
        args.optimizer]
    step_fn = trainer.make_train_step(cfg, mod, opt, n_micro=args.n_micro,
                                      dp=dp)

    pipe = lm_pipeline(cfg, args.batch, args.seq, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)

    state = None
    if args.resume and args.ckpt_dir:
        state = trainer.resume(cfg, mod, opt,
                               mesh or make_mesh((1, 1),
                                                 ("data", "model")),
                               args.ckpt_dir, pipe, key)
    if state is None:
        state = trainer.init_state(cfg, mod, opt, key)

    if mesh is not None:
        shardings = trainer.state_shardings_for(cfg, mod, mesh, opt, key)
        state = jax.device_put(state, shardings)
        jit_step = jax.jit(step_fn, in_shardings=(shardings, None),
                           out_shardings=(shardings, None),
                           donate_argnums=(0,))
        ctx = activation_sharding(dp, mesh.shape["data"])
    else:
        jit_step = jax.jit(step_fn, donate_argnums=(0,))
        import contextlib
        ctx = contextlib.nullcontext()

    with ctx:
        state = trainer.fit(state, jit_step, pipe, steps=args.steps,
                            ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every)
    return state


if __name__ == "__main__":
    main()
