"""Training cells: the jitted step that ``make_train_step`` builds, with
``launch/train.py::run_training``'s shardings and donation.

Set-up builds one compiled step and its state, and drives it through the
mix's first steps on distinct rows from the seed; those steps are what the
reference follows. The same object then runs the window: it keeps one step
in flight, and the rate is the tokens of every step completed in the window
over the window, which closes when the last step's loss is on the host.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import registry, traffic, weights, work
from benchlib.common import CompileCounter, span


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def build_step(cell, devices):
    """The compiled step, params and optimizer state, as ``run_training``
    builds them, with the benchmark's weights and optimizer settings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import TrainPolicy
    from repro.launch import specs as S
    from repro.launch.mesh import make_debug_mesh
    from repro.optim import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_train_step

    mix = cell.traffic
    cfg = registry.model_config(cell.config["model"])
    policy = TrainPolicy.from_model(cfg, **mix["policy"])
    mesh = make_debug_mesh(devices=devices)
    ocfg = OptimizerConfig(**mix["optimizer"])

    def make(params):
        opt = init_opt_state(params)
        pspec = S.param_specs(params, cfg, mesh)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        ospec = type(opt)(step=P(), m=pspec, v=pspec)
        step = jax.jit(make_train_step(cfg, ocfg, policy=policy),
                       in_shardings=(sh(pspec), sh(ospec), None),
                       out_shardings=(sh(pspec), sh(ospec), None),
                       donate_argnums=(0, 1))
        return step, opt

    return cfg, mesh, make


def run(cell, ctx):
    out, readings = train(cell, ctx)
    out["checks"] = check(cell, ctx.seed, *readings)
    return out


def train(cell, ctx, built=None):
    """Set-up, the first steps and the window. Returns the result's pieces
    and the program's readings of the first steps. ``built`` (a dict) keeps
    the compiled step for another seed in the same process."""
    from repro.distributed.sharding import axis_rules

    mix = cell.traffic
    b, n = int(mix["batch"]), int(mix["seq_len"])
    nsteps = int(mix["check"]["steps"])
    cfg, mesh, make = build_step(cell, ctx.devices)
    counter = CompileCounter()
    with mesh, axis_rules(mesh):
        params = weights.make(ctx.seed, cell.layout)
        registry.check_layout(cfg, params)
        step, opt = make(params)
        batches = traffic.markov_batches(ctx.seed, int(mix["batches"]), b, n,
                                         cfg.vocab_size)
        if built is not None and "compiled" in built:
            compiled = built["compiled"]
        else:
            compiled = step.lower(params, opt, batches[0]).compile()
        if built is not None:
            built["compiled"] = compiled
        b1 = float(mix["optimizer"]["b1"])
        losses, grad1 = [], None
        for i in range(nsteps):
            params, opt, met = compiled(params, opt, batches[i])
            losses.append(float(met["loss"]))
            if i == 0:
                grad1 = [float(x) / (1 - b1) for x in _leaf_norms(opt.m)]
        p0 = weights.make(ctx.seed, cell.layout)
        change = [float(x) for x in _change_norms(params, p0)]
        del p0
        i = nsteps
        tracing = None
        ctx.setup_done()
        counter.reset()
        if ctx.trace:
            tracing = ctx.start_trace()
        t0 = time.perf_counter()
        done, pending, traced_steps, traced_s = 0, None, None, None
        while True:
            with span("bench.train_dispatch"):
                params, opt, met = compiled(params, opt,
                                            batches[i % len(batches)])
            i += 1
            if pending is not None:
                with span("bench.wait"):
                    pending.block_until_ready()
                done += 1
            pending = met["loss"]
            el = time.perf_counter() - t0
            if tracing is not None and el >= ctx.trace_seconds:
                pending.block_until_ready()
                done += 1
                pending = None
                traced_s, traced_steps = time.perf_counter() - t0, done
                ctx.stop_trace(tracing)
                tracing = None
            if el >= ctx.seconds:
                break
        if pending is not None:
            pending.block_until_ready()
            done += 1
        window = time.perf_counter() - t0
        if tracing is not None:
            ctx.stop_trace(tracing)
            traced_s, traced_steps = window, done
        final_loss = float(met["loss"])
    tokens = b * n
    host = {"train_tokens_per_s": done * tokens / window, "steps": done,
            "window_s": window, "compiles_in_window": counter.count,
            "final_loss": final_loss}
    traced_work = None
    if ctx.trace:
        host["traced_s"] = traced_s
        host["traced_tokens"] = traced_steps * tokens
        host["model_flops_per_token"] = work.train_flops_per_token(
            cfg, cell.layout, n)
        traced_work = {k: w.scaled(traced_steps)
                       for k, w in step_work(cfg, mix).items()}
    memory = ctx.memory_peak()
    del params, opt, met, compiled, step, batches, pending
    ctx.free()
    return ({"attempted": done, "failed": int(not np.isfinite(final_loss)),
             "host": host, "work": traced_work, "memory": memory},
            (losses, grad1, change))


def step_work(cfg, mix: dict) -> dict:
    """Work of one training step of each operation of ``ops/`` that counts
    its work for this configuration."""
    out = {}
    for name, op in registry.ops().items():
        w = op.work(cfg, mix)
        if w is not None:
            out[name] = w
    return out


def reference_readings(cell, seed, mode="f32", steps=None):
    """What the reference gives for the mix's first steps from ``seed``:
    each step's loss, the first step's clipped gradient norm per leaf, and
    the change of each leaf after the steps."""
    mix = cell.traffic
    m = cell.config["model"]
    ref = registry.reference(cell.config["reference"])
    steps = int(mix["check"]["steps"]) if steps is None else steps
    b, n = int(mix["batch"]), int(mix["seq_len"])
    batches = traffic.markov_batches(seed, int(mix["batches"]), b, n,
                                     registry.model_config(m).vocab_size
                                     )[:steps]
    p0 = weights.make(seed, cell.layout)
    opt = dict(mix["optimizer"])
    losses, g1, p = ref.train_steps(
        p0, m, [(x["tokens"], x["labels"]) for x in batches], opt,
        mode=mode, q_block=int(mix["check"]["q_block"]))
    grad1 = [float(x) for x in _leaf_norms(g1)]
    change = [float(x) for x in _change_norms(p, p0)]
    return losses, grad1, change


def gaps(prog, ref):
    """The three numbers compared: the worst relative loss gap over the
    steps, and by the worst leaf, the gap between the program's and the
    reference's norm of the first gradient and of the change, against the
    larger of the leaf's reference norm and the median leaf's. Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    pl, pg, pc = prog
    rl, rg, rc = ref
    loss = max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
    med_g = float(np.median(rg))
    grad = max(abs(a - b) / max(b, med_g) for a, b in zip(pg, rg))
    moving = [i for i, g in enumerate(rg) if g >= 1e-3 * med_g]
    med_c = float(np.median([rc[i] for i in moving]))
    change = max(abs(pc[i] - rc[i]) / max(rc[i], med_c) for i in moving)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "still_leaves": len(rg) - len(moving)}


def leaf_gaps(prog, ref):
    """Each leaf's gradient and change gap, for the calibration's look at
    which leaf sets the worst."""
    _, pg, pc = prog
    _, rg, rc = ref
    med_g, med_c = float(np.median(rg)), float(np.median(rc))
    return {"grad": [abs(a - b) / max(b, med_g) for a, b in zip(pg, rg)],
            "change": [abs(a - b) / max(b, med_c) for a, b in zip(pc, rc)]}


def check(cell, seed, losses, grad1, change):
    ref = reference_readings(cell, seed)
    g = gaps((losses, grad1, change), ref)
    lim = cell.traffic["limits"]
    return {k: {"value": g[k], "limit": lim[k]}
            for k in ("loss_gap", "grad_gap", "change_gap")}
