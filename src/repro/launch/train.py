"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b \
      --reduced --steps 100 --batch 8 --seq 128 --data /tmp/tokens.bin

On the CPU container use --reduced (smoke-scale config). On a real TPU
slice drop --reduced and point --data at the tokenized corpus; the mesh is
constructed over however many devices the runtime exposes.
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import numpy as np


def make_trainer(cfg, gsys, paths, mesh, *, batch: int, seq: int,
                 lr: float = 3e-4, microbatches: int = 1, ckpt=None,
                 ckpt_every: int = 50, seed: int = 0):
    """A data-parallel :class:`~repro.train.loop.Trainer` over ``mesh``:
    params and AdamW state are placed by their logical axes (replicated
    over ``data``), each batch is split over ``data``, and the step
    donates the state it replaces so it is never held twice. Returns
    ``(trainer, loader)``; the caller closes the loader."""
    from repro.config import TrainConfig
    from repro.data.pipeline import GenesysDataLoader
    from repro.models.registry import init_params
    from repro.sharding import named_sharding, rules_for
    from repro.train.loop import Trainer
    from repro.train.steps import make_train_step

    rules = rules_for(cfg, mesh)
    params = init_params(cfg, jax.random.PRNGKey(seed), mesh=mesh,
                         rules=rules)
    ts, opt = make_train_step(cfg, rules, TrainConfig(
        lr=lr, microbatches=microbatches))
    loader = GenesysDataLoader(gsys, list(paths), batch=batch, seq=seq,
                               seed=seed)
    tr = Trainer(gsys, jax.jit(ts, donate_argnums=(0, 1)), params,
                 jax.jit(opt.init)(params), loader, ckpt=ckpt,
                 ckpt_every=ckpt_every,
                 batch_sharding=named_sharding(mesh, rules, "batch", "seq"))
    return tr, loader


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data", default=None,
                    help="token shard (uint32); synthesized if omitted")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    from repro.checkpoint.ckpt import CheckpointManager
    from repro.configs import get_config
    from repro.core.genesys import Genesys, GenesysConfig
    from repro.data.pipeline import write_token_shard
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    gsys = Genesys(GenesysConfig(n_workers=2, coalesce_window_us=200,
                                 coalesce_max=8))
    data = args.data
    if data is None:
        data = tempfile.mktemp(suffix=".bin")
        write_token_shard(data, np.random.default_rng(0).integers(
            0, min(cfg.vocab_size, 32000),
            size=args.batch * (args.seq + 1) * 64).astype(np.uint32))
        print(f"synthesized corpus at {data}")

    mesh = make_host_mesh(data=jax.device_count(), model=1)
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(gsys, args.ckpt_dir)
    tr, loader = make_trainer(cfg, gsys, [data], mesh, batch=args.batch,
                              seq=args.seq, lr=args.lr,
                              microbatches=args.microbatches, ckpt=ckpt,
                              ckpt_every=args.ckpt_every)
    with mesh:
        placed = jax.tree_util.tree_map(
            lambda x: x.sharding, {"params": tr.params, "opt": tr.opt_state})
        if args.resume and ckpt is not None and tr.resume(shardings=placed):
            print(f"resumed from step {tr.step}")
        st = tr.run(args.steps)
    print(f"steps={st.steps} loss[0]={st.losses[0]:.4f} "
          f"loss[-1]={st.losses[-1]:.4f} ckpts={st.ckpts} "
          f"stragglers={st.straggler_steps}")
    print(f"GENESYS: {dict(gsys.table.stats)} "
          f"coalesce_hist={gsys.executor.stats.coalesce_hist}")
    loader.close()
    gsys.shutdown()


if __name__ == "__main__":
    main()
