"""Names of the train step's layers, as ``jax.named_scope``s.

Each scope sits on the function that does the work (``@jax.named_scope``
or a ``with`` block), so every path that calls it — the train step, the
ring, the serving prefill — carries the same name. A scope only adds
metadata at trace time: the compiled program is the same with or without
it, and the XLA op's ``op_name`` (and so the profiler trace) carries the
name stack, e.g. ``.../transpose(jvp())/.../checkpoint/mlp/dot_general``.
The benchmark attributes device time to the innermost of these names
(``bench/benchlib/scopes.py``).

Forward scopes also appear in the backward pass: the transposed ops keep
the forward's name, and the ops that ``jax.checkpoint`` recomputes carry
``rematted_computation`` in their name stack.
"""
from __future__ import annotations

EMBED = "embed"                    # token and position lookups
NORM = "norm"                      # layers.apply_norm
ATTN_PROJ = "attn.proj"            # dense q/k/v and output projections
PROJ_RTOPK = "sfa.proj_rtopk"      # projection -> [RoPE] -> top-k codes
BLOCK_MAPS = "sfa.block_maps"      # FlashSFA's block-skip pre-pass
SFA_FWD = "sfa.fwd"                # FlashSFA forward kernels
SFA_BWD = "sfa.bwd"                # FlashSFA backward kernels
CODE_GRAD = "sfa.code_grad"        # compact code-gradient projection vjp
MLP = "mlp"                        # layers.mlp
LOSS = "loss"                      # LM head and cross-entropy
OPTIM = "optim"                    # clipping and the optimizer update

SCOPES = (EMBED, NORM, ATTN_PROJ, PROJ_RTOPK, BLOCK_MAPS, SFA_FWD, SFA_BWD,
          CODE_GRAD, MLP, LOSS, OPTIM)
