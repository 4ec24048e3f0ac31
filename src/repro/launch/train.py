"""Multi-device training launcher.

Wraps train/loop.py's step function with the production mesh + sharding
rules.  On this CPU container it runs reduced configs on a debug mesh
(``--debug-mesh``); on a real pod slice the same code path runs the full
mesh (the dry-run proves every full config lowers & compiles).

Example (CPU; without ``JAX_PLATFORMS=cpu`` the launcher exits unless JAX
finds a TPU):
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b \
      --reduced --steps 20 --batch 4 --seq 64
"""
import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import params as P
from repro import runtime as RT
from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCHS, get_config, get_reduced
from repro.data.pipeline import SyntheticTokens, TokenPipelineConfig
from repro.models import lm
from repro.optim import adamw
from repro.optim import compression as comp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--debug-mesh", default="", help="e.g. 2x2 (data x model)")
    ap.add_argument("--rules", default="default", choices=("default", "fsdp"),
                    help="sharding preset (fsdp = EXPERIMENTS.md §Perf H1 winner)")
    args = ap.parse_args()
    RT.require_tpu()
    RT.configure_compilation_cache()

    cfg = (get_reduced if args.reduced else get_config)(args.arch)
    mesh = None
    rules = None
    if args.debug_mesh:
        d, m = (int(x) for x in args.debug_mesh.split("x"))
        mesh = RT.make_debug_mesh(d, m)
        rules = (
            RT.fsdp_rules(mesh, args.batch)
            if args.rules == "fsdp"
            else RT.batch_rules(mesh, args.batch)
        )

    data = SyntheticTokens(
        TokenPipelineConfig(vocab_size=cfg.vocab_size, batch=args.batch, seq_len=args.seq)
    )
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                                total_steps=args.steps)
    ptree = lm.init_params(jax.random.PRNGKey(0), cfg)
    pvals, paxes = P.values(ptree), P.axes(ptree)
    if mesh is not None:
        shardings = RT.tree_shardings(ptree, mesh, rules)
        pvals = jax.device_put(pvals, shardings)
    opt_state = adamw.init(pvals)
    ef = comp.init_error_buf(pvals) if args.grad_compression else None
    mgr = CheckpointManager(args.ckpt_dir, keep=3)

    from repro.train.loop import make_train_step

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, args.grad_compression),
                      donate_argnums=(0, 1, 2))

    with contextlib.ExitStack() as mesh_ctx:
        if mesh is not None:
            # make logical_constraint() live during tracing/execution
            mesh_ctx.enter_context(RT.use_mesh(mesh))
            mesh_ctx.enter_context(RT.active_rules(rules))
        _run_steps(args, data, step_fn, pvals, opt_state, ef, mgr, paxes)
    mgr.wait()
    print("done")


def _run_steps(args, data, step_fn, pvals, opt_state, ef, mgr, paxes):
    it = iter(data)
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        t0 = time.perf_counter()
        pvals, opt_state, ef, metrics = step_fn(pvals, opt_state, ef, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} ({dt*1e3:.0f} ms)", flush=True)
        if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
            mgr.save(step + 1, {"params": pvals, "opt": opt_state},
                     axes_tree={"params": paxes, "opt": None})


if __name__ == "__main__":
    main()
