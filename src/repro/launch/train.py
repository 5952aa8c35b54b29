"""Framework training CLI: any assigned arch on any mesh, fault tolerant.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b \
        --steps 100 --batch 8 --seq 256 [--reduced] [--resume]

On a multi-device runtime (TPU slice or forced host devices) the Dmap
sharding rules are applied to params/optimizer/batch exactly as in the
dry-run; on one device everything degrades to local execution.  The loop
checkpoints every ``--ckpt-every`` steps (async) and resumes from the
latest checkpoint (``--resume``).

A chip host runs ONE JAX process, which drives every chip of the host
through the mesh: a chip belongs to the process that first touched JAX,
so ``pRUN`` with this target at ``np>1`` on one host is not a chip path
(the other ranks fail or hang waiting for the chip).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..dist.hints import mesh_context
from ..dist.sharding import (
    batch_shardings,
    opt_state_shardings,
    param_shardings,
)
from ..models import init_params
from ..obs import trace as _trace
from ..train.checkpoint import CheckpointManager
from ..train.data import batch_iterator
from ..train.optimizer import AdamWConfig
from ..train.train_step import TrainStepConfig, init_opt_state, make_train_step
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh


def build_train_step(cfg, *, steps: int, batch: int, lr: float = 3e-4,
                     microbatches: int = 1, grad_compression: str = "none",
                     mesh=None):
    """This CLI's jitted train step: params and optimizer state donated,
    Dmap shardings on ``mesh`` (None: one device, no shardings).

    Returns ``(step_fn, ts, param_shardings, opt_state_shardings)``."""
    p_sh = o_sh = b_sh = None
    if mesh is not None:
        p_sh = param_shardings(cfg, mesh)
        o_sh = opt_state_shardings(cfg, mesh)
        b_sh = batch_shardings(cfg, mesh, "train", batch)

    opt = AdamWConfig(lr=lr, warmup_steps=min(10, steps), total_steps=steps,
                      schedule="wsd" if cfg.wsd_schedule else "cosine")
    ts = TrainStepConfig(microbatches=microbatches, remat=True,
                         grad_compression=grad_compression)
    step_fn = jax.jit(
        make_train_step(cfg, opt, ts, grad_shardings=p_sh),
        in_shardings=(p_sh, o_sh, b_sh) if mesh is not None else None,
        out_shardings=(p_sh, o_sh, None) if mesh is not None else None,
        donate_argnums=(0, 1),
    )
    return step_fn, ts, p_sh, o_sh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", choices=["none", "bf16", "int8_ef"],
                    default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data-model", type=int, nargs=2, default=None,
                    metavar=("DATA", "MODEL"),
                    help="mesh shape over local devices")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{jax.device_count()} device(s)")

    # mesh + shardings (identity on one device)
    if args.data_model:
        mesh = make_local_mesh(*args.data_model)
    elif jax.device_count() > 1:
        mesh = make_local_mesh(data=jax.device_count(), model=1)
    else:
        mesh = None
    step_fn, ts, p_sh, o_sh = build_train_step(
        cfg, steps=args.steps, batch=args.batch, lr=args.lr,
        microbatches=args.microbatches,
        grad_compression=args.grad_compression, mesh=mesh,
    )

    ckpt_dir = args.ckpt_dir or f"/tmp/repro_train_{cfg.name}"
    mgr = CheckpointManager(ckpt_dir, keep=2)
    start = 0
    if args.resume and mgr.latest_step() is not None:
        start, trees, _ = mgr.restore(
            shardings={"params": p_sh, "opt_state": o_sh} if mesh else None
        )
        params = jax.tree.map(jnp.asarray, trees["params"])
        opt_state = jax.tree.map(jnp.asarray, trees["opt_state"])
        print(f"[train] resumed from step {start}")
    else:
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        opt_state = init_opt_state(cfg, params, ts)
        if mesh is not None:
            params = jax.device_put(params, p_sh)
            opt_state = jax.device_put(opt_state, o_sh)

    t0 = time.perf_counter()
    with mesh_context(mesh):
        for step, batch in batch_iterator(cfg, args.batch, args.seq,
                                          start_step=start):
            if step >= args.steps:
                break
            # train.step times the dispatch; train.wait the pull of the
            # loss, which waits for the chip to finish the step
            with _trace.span("train.step", step=step,
                             tokens=args.batch * args.seq):
                params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % 5 == 0 or step == args.steps - 1:
                with _trace.span("train.wait", step=step):
                    m = jax.device_get(metrics)
                print(f"[train] step {step:4d} loss {float(m['loss']):8.4f} "
                      f"lr {float(m['lr']):.2e} "
                      f"gnorm {float(m['grad_norm']):.2f}", flush=True)
            if step and step % args.ckpt_every == 0:
                mgr.save(step, {"params": params, "opt_state": opt_state},
                         blocking=False)
    mgr.wait()
    mgr.save(args.steps, {"params": params, "opt_state": opt_state})
    dt = time.perf_counter() - t0
    toks = (args.steps - start) * args.batch * args.seq
    print(f"[train] done: {toks/dt:.0f} tok/s; checkpoints in {ckpt_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
