"""shard_map routing for the Pallas kernels (tensor parallelism).

XLA cannot partition a ``pallas_call``: under pjit a sharded operand reaching
a kernel is silently all-gathered onto every device and the kernel runs fully
replicated. This module routes the kernels through ``jax.shard_map`` over the
mesh of the active ``axis_rules`` context instead, so each device runs the
kernel on its own slice:

  * ``tp_flash_sfa`` / ``tp_flash_sfa_bwd`` — the folded (b·h, n, ...) batch
    axis splits over the ``model`` mesh axis. Every (b·h) row is an
    independent attention problem, so per-device whole-head slices need NO
    cross-device reduction for the dQ/dK code gradients — this is what makes
    the compact projection seam TP-eligible (models/attention.py;
    eligibility = pallas backend + heads divisible by the TP degree).
  * ``tp_proj_rtopk`` — the fused projection+top-k kernel splits its head
    axis (column-parallel projection: each device projects and sparsifies
    its own head block; the activations stay replicated).
  * ``run_tp`` — the generic helper behind both, also used by
    ``models/layers.py::sparse_proj_bwd`` where the *only* cross-device
    reduction of the seam backward lives: the dL/dx partial sums over the
    model axis (the classic column-parallel backward all-reduce). dW stays
    local per head shard.

Outside a mesh context — or when a sharded dimension does not divide the TP
degree — every wrapper falls through to the plain kernel call, so the same
model code runs single-device tests and TP meshes unchanged.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh

# NOTE the kernel imports live inside the wrappers: kernels/ops.py routes
# through this module, so a module-level kernel import here would close an
# import cycle through repro.kernels.__init__.


def tp_degree(axis_name: str = "model") -> int:
    """Size of the TP mesh axis under the active rules context (1 if none)."""
    mesh = current_mesh()
    return 1 if mesh is None else mesh.shape.get(axis_name, 1)


def _spec(ax, ndim):
    if ax is None:
        return P()
    return P(*[("model" if i == ax else None) for i in range(ndim)])


def run_tp(fn, args, in_axes, out_axes, *, reduce_out=(),
           axis_name: str = "model"):
    """Run ``fn(*args)`` through shard_map over the model axis.

    ``in_axes`` / ``out_axes``: per-arg / per-output int axis to split over
    the mesh axis (None = replicate). ``reduce_out``: output positions whose
    per-device partials are psum'd over the axis inside the region (their
    out_axes entry must be None). Falls back to a direct call outside a mesh
    context, on a 1-sized axis, or when any split dim does not divide the TP
    degree — the wrappers stay total."""
    mesh = current_mesh()
    tp = 1 if mesh is None else mesh.shape.get(axis_name, 1)
    if tp == 1 or any(ax is not None and a.shape[ax] % tp
                      for a, ax in zip(args, in_axes)):
        return fn(*args)

    single = not isinstance(out_axes, (tuple, list))
    out_axes_t = (out_axes,) if single else tuple(out_axes)

    def body(*local_args):
        out = fn(*local_args)
        out_t = (out,) if single else tuple(out)
        if reduce_out:
            out_t = tuple(
                jax.lax.psum(o, axis_name) if i in reduce_out else o
                for i, o in enumerate(out_t))
        return out_t

    in_specs = tuple(_spec(ax, a.ndim) for a, ax in zip(args, in_axes))
    # shapes only (psum never changes them): eval the raw fn, which is
    # collective-free, so this works outside the shard_map region
    shapes = jax.eval_shape(fn, *args)
    shapes_t = (shapes,) if single else tuple(shapes)
    out_specs = tuple(_spec(ax, len(s.shape))
                      for s, ax in zip(shapes_t, out_axes_t))
    out = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=False)(*args)
    return out[0] if single else out


def tp_flash_sfa(q_vals, q_idx, k_vals, k_idx, v, **kw):
    """``flash_sfa`` with the folded (b·h) axis split over the model axis."""
    from repro.kernels.flash_sfa import flash_sfa

    def fn(qv, qi, kv_, ki, vf):
        return flash_sfa(qv, qi, kv_, ki, vf, **kw)
    n_out = 2 if kw.get("return_residuals") else 1
    out_axes = (0, 0) if n_out == 2 else 0
    return run_tp(fn, (q_vals, q_idx, k_vals, k_idx, v),
                  in_axes=(0, 0, 0, 0, 0), out_axes=out_axes)


def tp_flash_sfa_bwd(q_vals, q_idx, k_vals, k_idx, v, o, lse, g, **kw):
    """``flash_sfa_bwd`` with the folded (b·h) axis split over the model
    axis: dQ/dK code grads and dV are per-slice — no reduction."""
    from repro.kernels.flash_sfa_bwd import flash_sfa_bwd

    def fn(*a):
        return flash_sfa_bwd(*a, **kw)
    return run_tp(fn, (q_vals, q_idx, k_vals, k_idx, v, o, lse, g),
                  in_axes=(0,) * 8, out_axes=(0, 0, 0))


def tp_proj_rtopk(x, w_heads, positions, **kw):
    """``proj_rtopk`` with the head axis of w (and of the emitted codes)
    split over the model axis — column-parallel fused projection."""
    from repro.kernels.rtopk import proj_rtopk

    def fn(xx, ww, pp):
        return proj_rtopk(xx, ww, pp, **kw)
    return run_tp(fn, (x, w_heads, positions),
                  in_axes=(None, 0, None), out_axes=(1, 1))
