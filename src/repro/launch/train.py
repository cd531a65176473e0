"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 100 [--reduced] [--mesh debug|single-pod|multi-pod]

``--mesh debug`` runs on the visible devices (one TPU chip, or the CPU at
``--reduced`` size); the production meshes go through the same code and are
exercised via ``repro.launch.dryrun`` (lower+compile only). On a TPU
cluster the launcher runs per-host with jax.distributed initialization.

``run_training`` is the launcher's whole path as a function, so
``chip_smoke.py`` drives exactly what the command line drives.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ModelConfig, TrainPolicy
from repro.data import DataConfig, markov_batch
from repro.distributed.sharding import axis_rules
from repro.launch import specs as S
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import init as model_init
from repro.optim import OptimizerConfig, init_opt_state
from repro.train.train_step import make_train_step


@dataclasses.dataclass
class TrainRun:
    """What ``run_training`` hands back: per-step metrics, the compiled
    step's HLO text and compile seconds, and the final parameters."""
    metrics: list
    compile_seconds: float
    hlo_text: str
    params: dict
    cfg: ModelConfig
    data: DataConfig


def run_training(cfg: ModelConfig, *, steps: int, batch: int, seq_len: int,
                 lr: float = 3e-3, mesh: str = "debug", tp: int = 1,
                 ring: int = 1, devices=None, log=None,
                 **policy_overrides) -> TrainRun:
    """Build the mesh, params and jitted step, compile it, run ``steps``.

    ``devices`` limits the debug mesh (default: every visible device);
    ``policy_overrides`` are ``TrainPolicy`` fields (remat, bwd_emit,
    fwd_fuse, backend). Parameters come from ``PRNGKey(0)`` and batch ``s``
    from ``markov_batch(.., s)``, so two runs see the same weights and data.
    """
    if mesh != "debug" and (tp > 1 or ring > 1):
        raise ValueError("--tp/--ring shape the debug mesh only; production "
                         "meshes fix their own axes (launch/mesh.py)")
    mesh = (make_debug_mesh(model=tp, seq=ring, devices=devices)
            if mesh == "debug" else
            make_production_mesh(multi_pod=mesh == "multi-pod"))
    overrides = {"tp": tp, **{k: v for k, v in policy_overrides.items()
                              if v is not None}}
    if ring > 1:
        overrides["ring"] = True
    policy = TrainPolicy.from_model(cfg, **overrides)

    with mesh, axis_rules(mesh):
        params = model_init(jax.random.PRNGKey(0), cfg)
        opt = init_opt_state(params)
        pspec = S.param_specs(params, cfg, mesh)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        ocfg = OptimizerConfig(lr=lr, warmup_steps=max(steps // 10, 2),
                               total_steps=steps)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                          global_batch=batch)
        step = jax.jit(
            make_train_step(cfg, ocfg, policy=policy),
            in_shardings=(sh(pspec),
                          sh(type(opt)(step=P(), m=pspec, v=pspec)),
                          None),
            # pin outputs to the input layouts: the shard_map'd kernel
            # paths can tip GSPMD's inference toward resharding a param's
            # round-trip, which donation then rejects
            out_shardings=(sh(pspec),
                           sh(type(opt)(step=P(), m=pspec, v=pspec)),
                           None),
            donate_argnums=(0, 1))
        batches = ({k: jnp.asarray(v) for k, v in markov_batch(dcfg, s).items()}
                   for s in range(steps))
        first = next(batches)
        t0 = time.perf_counter()
        compiled = step.lower(params, opt, first).compile()
        compile_s = time.perf_counter() - t0
        metrics = []
        for s, b in enumerate([first, *batches]):
            params, opt, m = compiled(params, opt, b)
            metrics.append({k: float(v) for k, v in m.items()})
            if log is not None:
                log(s, metrics[-1])
    return TrainRun(metrics=metrics, compile_seconds=compile_s,
                    hlo_text=compiled.as_text(), params=params, cfg=cfg,
                    data=dcfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mesh", default="debug",
                    choices=["debug", "single-pod", "multi-pod"])
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree of the debug mesh's model "
                         "axis (shard_map'd kernels + TP-eligible compact "
                         "seam, distributed/shard.py; DESIGN.md \u00a79)")
    ap.add_argument("--ring", type=int, default=1,
                    help="ring degree of the debug mesh's seq axis: > 1 "
                         "enables Ring-SFA context parallelism on eligible "
                         "SFA layers (code-payload hops, distributed/"
                         "ring.py; DESIGN.md \u00a79)")
    ap.add_argument("--attn-backend", default=None,
                    choices=["xla", "pallas", "auto"],
                    help="override cfg.attention.backend for the step")
    ap.add_argument("--bwd-emit", default=None,
                    choices=["dense", "compact", "compact2"],
                    help="FlashSFA backward emit layout (DESIGN.md §3): "
                         "compact = (n, k) code-gradients + projection seam "
                         "(rope'd layers auto-widen to the (n, 2k) pair-"
                         "closure emit); compact2 = force the pair-widened "
                         "emit everywhere (parity/bench surface)")
    ap.add_argument("--fwd-fuse", dest="fwd_fuse", action="store_true",
                    default=None,
                    help="force the fused forward on seam-eligible layers: "
                         "projection -> rope -> top-k in one kernel (no "
                         "dense q/k HBM round-trip) + FlashSFA block "
                         "skipping (DESIGN.md §2; config default: on)")
    ap.add_argument("--no-fwd-fuse", dest="fwd_fuse", action="store_false",
                    help="force the unfused rtopk+FlashSFA composition")
    ap.add_argument("--remat", default=None,
                    choices=["none", "full", "codes"],
                    help="checkpoint policy for the layer scan "
                         "(core/remat.py): none = save every linearization "
                         "point; full = recompute whole layers; codes = "
                         "save only the compact (n, k) SFA codes as named "
                         "residuals — d/k x smaller than the dense q/k "
                         "they summarize (DESIGN.md §10)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    every = max(args.steps // 10, 1)

    def log(s, m):
        if s % every == 0:
            print(f"step {s:4d} loss {m['loss']:.4f} "
                  f"gnorm {m['grad_norm']:.3f}")

    run = run_training(cfg, steps=args.steps, batch=args.batch,
                       seq_len=args.seq_len, lr=args.lr, mesh=args.mesh,
                       tp=args.tp, ring=args.ring, log=log,
                       backend=args.attn_backend, remat=args.remat,
                       bwd_emit=args.bwd_emit, fwd_fuse=args.fwd_fuse)
    print(f"compile {run.compile_seconds:.1f} s")
    print(f"done: final loss {run.metrics[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
