"""Production training launcher: mesh + sharded step + checkpoint/restart
+ fleet monitoring, in one driver.

    # real pod (or host-mesh rehearsal with 8 placeholder devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \\
        --reduced --mesh 2x4 --steps 20

On a TPU fleet this is the per-controller entry point: the mesh comes from
`make_production_mesh()`, params/opt/batch are placed with the plan's
NamedShardings, the step is jitted with donation, and every
`--ckpt-every` steps an atomic async checkpoint is written.  On restart
(`--resume`) the newest intact checkpoint is restored — onto a *smaller*
mesh if pods were lost (runtime/elastic.py rebalances microbatches so the
global batch, and therefore the counter-based data stream, is unchanged).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager, CheckpointPolicy
from ..checkpoint.store import config_hash
from ..configs.base import ARCH_IDS, SHAPES, get_config, get_plan, get_reduced
from ..data.pipeline import DataConfig, Prefetcher
from ..models import lm as M
from ..optim.adamw import OptConfig
from ..runtime.elastic import remesh_plan
from ..runtime.fault import FaultConfig, FleetMonitor, decide
from ..train.steps import TrainHParams, make_train_step
from . import specs as S
from .mesh import auto_mesh, make_production_mesh


def build_mesh(spec: str):
    if spec == "production":
        return make_production_mesh()
    if spec == "multipod":
        return make_production_mesh(multi_pod=True)
    parts = [int(x) for x in spec.split("x")]
    names = ("data", "model")[:len(parts)]
    return auto_mesh(parts, names)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU/CI rehearsal)")
    ap.add_argument("--mesh", default="1x1",
                    help="'production' | 'multipod' | e.g. '2x4'")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/cmm_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    plan = get_plan(args.arch, "train_4k")
    mesh = build_mesh(args.mesh)
    dp = S.dp_size(plan, mesh)
    while args.global_batch % (dp * plan.microbatches) or \
            plan.microbatches > args.global_batch // dp:
        plan = replace(plan, microbatches=max(1, plan.microbatches - 1))
    print(f"mesh {dict(mesh.shape)}  dp={dp}  mb={plan.microbatches}")

    hp = TrainHParams(opt=OptConfig(lr=args.lr, warmup=10,
                                    decay_steps=args.steps))
    step_fn, init_opt = make_train_step(cfg, plan, mesh, hp=hp)
    p_sh = S.params_shardings(cfg, plan, mesh)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with mesh:
        params = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
        opt = init_opt(params)

    mgr = CheckpointManager(args.ckpt_dir,
                            CheckpointPolicy(every_steps=args.ckpt_every,
                                             keep=2))
    meta = {"config_hash": config_hash(cfg)}
    start = 0
    if args.resume:
        got = mgr.maybe_restore(cfg, param_shardings=p_sh)
        if got:
            start, params, opt = got
            opt = jax.tree.map(jnp.asarray, opt)
            print(f"resumed from step {start}")

    monitor = FleetMonitor(mesh.shape.get("pod", 1))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.global_batch, seed=0,
                      microbatches=plan.microbatches)
    pf = Prefetcher(dcfg, start_step=start)
    try:
        t0 = time.perf_counter()
        for i in range(start, args.steps):
            s, batch = next(pf)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            ts = time.perf_counter()
            with mesh:
                params, opt, m = jitted(params, opt, batch)
            monitor.heartbeat(0, time.perf_counter() - ts)
            d = decide(monitor)
            if d.action not in ("continue",):
                print(f"[fleet] {d.action}: {d.reason}")
            mgr.step_hook(i + 1, params, opt, meta)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(m['loss']):7.4f} "
                      f"lr {float(m['lr']):.2e} "
                      f"{(i+1-start)*args.global_batch*args.seq/(time.perf_counter()-t0):8.0f} tok/s")
    finally:
        pf.close()
        mgr.store.wait()
    print("done")


if __name__ == "__main__":
    main()
