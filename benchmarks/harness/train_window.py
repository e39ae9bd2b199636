"""The training window: the program's ``Solver.train`` pipelined loop,
driven from a pool of distinct seeded batches staged during set-up.

Set-up builds ONE solver, drives it from the seed through its first
steps by the window's own call and feed, and hands that same solver to
the window.  The plain reference later follows those first steps.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, weights


class Feed:
    """Host iterator the program's prefetcher pulls from.  ``play`` hands
    out listed batches; ``until`` cycles the pool while the clock is short
    of the deadline.  Only the staging thread calls ``__next__``."""

    def __init__(self, inputs, labels):
        self.inputs, self.labels = inputs, labels
        self.order, self.deadline, self.i = [], None, 0

    def play(self, order):
        self.order, self.deadline = list(order), None

    def until(self, deadline):
        self.order, self.deadline = [], deadline

    def __iter__(self):
        return self

    def __next__(self):
        if self.deadline is None:
            if not self.order:
                raise StopIteration
            j = self.order.pop(0)
        else:
            if time.perf_counter() >= self.deadline:
                raise StopIteration
            j = self.i % len(self.inputs)
            self.i += 1
        return self.inputs[j], self.labels[j]


def build_solver(cell, devices, telemetry=None):
    from npairloss_tpu.ops.npair_loss import (
        MiningMethod, MiningRegion, NPairLossConfig)
    from npairloss_tpu.train.solver import Solver, SolverConfig

    cfg, tr = cell.config, cell.traffic
    m, s = cfg["mining"], cfg["solver"]
    loss_cfg = NPairLossConfig(
        margin_ident=m["margin_ident"], margin_diff=m["margin_diff"],
        identsn=m["identsn"], diffsn=m["diffsn"],
        ap_mining_region=MiningRegion[m["ap_mining_region"]],
        ap_mining_method=MiningMethod[m["ap_mining_method"]],
        an_mining_region=MiningRegion[m["an_mining_region"]],
        an_mining_method=MiningMethod[m["an_mining_method"]])
    scfg = SolverConfig(
        base_lr=s["base_lr"], lr_policy=s["lr_policy"], gamma=s["gamma"],
        stepsize=s["stepsize"], momentum=s["momentum"],
        weight_decay=s["weight_decay"], display=0, test_iter=0,
        test_interval=0, test_initialization=False, snapshot=0,
        pipeline=True, pipeline_depth=tr.get("pipeline_depth", 2))
    mesh = None
    if len(devices) > 1:
        from npairloss_tpu.parallel.mesh import data_parallel_mesh

        mesh = data_parallel_mesh(devices)
    return Solver(
        cell.adapter.build_model(cfg), loss_cfg, scfg, mesh=mesh,
        input_shape=cell.adapter.input_shape(cfg),
        engine=tr.get("engine", "dense"),
        precision=cfg["program"]["precision"], telemetry=telemetry)


def load_state(solver, adapter, params):
    """Hand the seed's weights to the program, in its own layout."""
    tree = adapter.to_program(params, xp=jnp)
    state = {"params": tree, "batch_stats": {},
             "opt": jax.jit(solver.tx.init)(tree)}
    solver.state = solver._place_state(state)


def first_steps(solver, cell, params0, feed):
    """Hand the seed's weights to the solver, drive it through the check
    steps by the window's own call and feed, and read the program's side
    of ``correct``: each step's loss, the first gradient as the optimizer
    got it (v1 = lr (g + wd w0), from the momentum buffer after one
    step) and the parameters' change.  Returns (host copy of the
    weights, numbers)."""
    cfg, adapter = cell.config, cell.adapter
    load_state(solver, adapter, params0)
    host0 = weights.widened(params0)
    prog = {"losses": []}
    quiet = lambda *_a, **_k: None
    for i in range(cell.traffic["check_steps"]):
        feed.play([i % len(feed.inputs)])
        last = solver.train(feed, num_iters=i + 1, log_fn=quiet)
        prog["losses"].append(float(last["loss"]))
        if i == 0:
            velocity = adapter.from_program(
                weights.widened(solver.state["opt"].momentum_buf))
    after = adapter.from_program(weights.widened(solver.state["params"]))
    lr, wd = cfg["solver"]["base_lr"], cfg["solver"]["weight_decay"]
    prog["grad"] = {n: {l: velocity[n][l] / lr - wd * host0[n][l]
                        for l in leaves} for n, leaves in host0.items()}
    prog["delta"] = compare.tree_sub(after, host0)
    return host0, prog


def seeded_inputs(cell, seed):
    """(weights on the device, Feed over the staged pool) of the seed."""
    cfg, adapter = cell.config, cell.adapter
    params0 = weights.make_params(adapter, cfg, seed)
    return params0, Feed(*adapter.train_batches(cfg, cell.traffic, seed))


def setup(cell, devices, seed, telemetry=None):
    """Solver with the seed's state, the staged pool, and the program's
    side of ``correct`` read off its first steps."""
    solver = build_solver(cell, devices, telemetry)
    params0, feed = seeded_inputs(cell, seed)
    host0, prog = first_steps(solver, cell, params0, feed)
    return solver, feed, host0, prog


def window(solver, feed, seconds):
    """Steps until the clock passes ``seconds``; ends in block_until_ready."""
    gc.collect()
    gc.freeze()
    start_iter = solver.iteration
    quiet = lambda *_a, **_k: None
    t0 = time.perf_counter()
    feed.until(t0 + seconds)
    try:
        solver.train(feed, num_iters=10 ** 9, log_fn=quiet)
    except StopIteration:
        pass
    jax.block_until_ready(solver.state)
    t1 = time.perf_counter()
    steps = solver.iteration - start_iter
    return {"steps": steps, "seconds": t1 - t0, "t0": t0, "t1": t1}


def reference_numbers(cell, host0, inputs, labels, quant=None):
    """The plain reference follows the first steps from the same weights
    and rows, on one device, a block of rows at a time.  Run after the
    window, with the program's state freed."""
    from benchmarks.reference.npair import Trainer

    cfg, tr = cell.config, cell.traffic
    trainer = Trainer(cell.adapter.embed, host0, cfg["mining"], cfg["solver"],
                      ranks=cell.chips, block=tr["reference_block"], quant=quant)
    losses = []
    for i in range(tr["check_steps"]):
        j = i % len(inputs)
        losses.append(trainer.step(inputs[j], labels[j]))
    after = jax.tree_util.tree_map(np.asarray, trainer.params)
    return {"losses": losses, "grad": trainer.first_grads,
            "delta": compare.tree_sub(after, host0)}
