"""Training launcher: supervised, checkpointed, restartable.

Runs on whatever devices exist (1 CPU for local runs; the production mesh on
real pods).  Demonstrates the full fault-tolerance story end-to-end:

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 20 --ckpt-dir /tmp/ckpt

* supervisor restarts from the last atomic checkpoint on any step failure
  (``--inject-failure-at N`` exercises this),
* async checkpointing off the training thread,
* heartbeat + straggler watchdog,
* data pipeline replays deterministically to the restored step.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import sharding as Sh
from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_arch, smoke_config
from repro.data.pipeline import PipelineState, SyntheticLM
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamW, cosine_schedule
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import (Heartbeat, StragglerDetector,
                                           Supervisor)


@dataclasses.dataclass
class Trainer:
    """The trainer's state and jitted step on one mesh (None: one device).

    With a mesh, parameters and optimizer state are made under ``jit``
    straight into their shardings, so no device ever holds the whole set.
    """
    model: Model
    opt: AdamW
    mesh: object = None
    rules: dict | None = None
    microbatches: int = 1
    remat: str = "none"

    def __post_init__(self):
        self.jit_step = jax.jit(
            S.make_train_step(self.model, self.opt,
                              num_microbatches=self.microbatches,
                              remat=self.remat),
            donate_argnums=(0, 1))

    def shardings(self):
        """(params, opt state) sharding trees, or (None, None) unmeshed."""
        if self.mesh is None:
            return None, None
        rep = NamedSharding(self.mesh, PartitionSpec())
        of = lambda specs: jax.tree.map(
            lambda s: rep if s.sharding is None else s.sharding, specs)
        return (of(S.sharded_param_specs(self.model, self.mesh, self.rules)),
                of(S.sharded_opt_specs(self.model, self.opt, self.mesh,
                                       self.rules)))

    def init(self, key):
        pshard, oshard = self.shardings()
        params = jax.jit(self.model.init, out_shardings=pshard)(key)
        return params, jax.jit(self.opt.init, out_shardings=oshard)(params)

    def place(self, params, ostate):
        """Host trees (a restored checkpoint) onto the devices."""
        pshard, oshard = self.shardings()
        if pshard is None:
            return jax.tree.map(jnp.asarray, (params, ostate))
        return jax.device_put(params, pshard), jax.device_put(ostate, oshard)

    def step(self, params, ostate, batch):
        with Sh.use_mesh_and_rules(self.mesh, self.rules):
            return self.jit_step(params, ostate, batch)

    def lower(self, params, ostate, batch):
        with Sh.use_mesh_and_rules(self.mesh, self.rules):
            return self.jit_step.lower(params, ostate, batch)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-6b")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-sized)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=25)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--remat", default="none")
    p.add_argument("--inject-failure-at", type=int, default=-1)
    p.add_argument("--data-model", type=int, nargs=2, default=(1, 1),
                   help="mesh (data, model) over local devices")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--autotune", action="store_true",
                   help="benchmark tile candidates for this run's GEMM "
                        "cells and persist the winners before training")
    p.add_argument("--tile-cache", default=None, metavar="PATH",
                   help="tile-plan cache file (also: $KRAKEN_TILE_CACHE); "
                        "without --autotune, replays it read-only")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.tile_cache or args.autotune:
        from repro import tuning
        from repro.core.unified import arch_cells, dedup_cells, tunable_cells
        tuning.set_tile_cache(args.tile_cache)
        if args.autotune:
            mb = max(args.batch // max(args.microbatches, 1), 1)
            cells = dedup_cells(tunable_cells(
                arch_cells(cfg, batch=mb, seq_q=args.seq, name="train")))
            tuning.warm_cells(cells, dtype_name=cfg.dtype, log=print,
                              verbose=False, label="train cells")
        tuning.set_tile_mode("cached")
    model = Model(cfg)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=20, total=args.steps))
    pipe = SyntheticLM(cfg.vocab_size, args.seq, args.batch)

    dm, tm = args.data_model
    mesh = make_host_mesh(dm, tm) if dm * tm > 1 else None
    rules = Sh.RULES_SINGLE_POD if mesh else None

    trainer = Trainer(model, opt, mesh=mesh, rules=rules,
                      microbatches=args.microbatches, remat=args.remat)

    hb = Heartbeat(os.path.join(args.ckpt_dir, "heartbeat.json"), interval_s=5)
    straggler = StragglerDetector()
    writer = ckpt.AsyncCheckpointer(args.ckpt_dir, keep=3)
    injected = {"done": False}

    def make_state():
        params, ostate = trainer.init(jax.random.key(0))
        return {"params": params, "opt": ostate, "pipe": PipelineState(0)}

    def run_one(state, step):
        if step == args.inject_failure_at and not injected["done"]:
            injected["done"] = True
            raise RuntimeError("injected failure (test)")
        batch_np, pstate = pipe(state["pipe"])
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        params, ostate, metrics = trainer.step(state["params"], state["opt"],
                                               batch)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        return {"params": params, "opt": ostate, "pipe": pstate}

    def save_state(step, state):
        writer.save(step, {"params": state["params"], "opt": state["opt"]},
                    extra={"pipe_step": state["pipe"].step})

    def restore_state():
        # Drain any in-flight async save first: a failure right after a
        # checkpoint step must not race the background write and restore
        # from one checkpoint earlier (or from scratch).
        try:
            writer.wait()
        except Exception as e:  # noqa: BLE001 - fall back to last durable
            print(f"[restore] pending checkpoint write failed "
                  f"({type(e).__name__}: {e}); using last durable checkpoint")
        last = ckpt.latest_step(args.ckpt_dir)
        if last is None:
            return None
        specs = {"params": model.param_specs(),
                 "opt": opt.state_specs(model.param_specs())}
        tree, step, extra = ckpt.restore(args.ckpt_dir, specs)
        params, ostate = trainer.place(tree["params"], tree["opt"])
        print(f"[restore] resumed from step {step}")
        return ({"params": params, "opt": ostate,
                 "pipe": PipelineState(extra["pipe_step"])}, step)

    sup = Supervisor(make_state=make_state, step_fn=run_one,
                     save_state=save_state, restore_state=restore_state,
                     checkpoint_every=args.ckpt_every, heartbeat=hb,
                     straggler=straggler)
    t0 = time.time()
    report = sup.run(args.steps)
    writer.wait()
    dt = time.time() - t0
    print(f"done: {report.steps_done} steps in {dt:.1f}s "
          f"({report.restarts} restarts, {report.straggler_steps} straggler steps)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
