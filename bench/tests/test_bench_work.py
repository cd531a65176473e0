"""Work counts equal the bytes of each operation's inputs and outputs at
small shapes, and the MFU formula's parameter count equals the weights'."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import registry, train_cell, work

BH, N, K, D = 2, 128, 4, 32
OPS = registry.ops()


def nbytes(*xs):
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in xs)


def codes(key, rows, n, k, d):
    a, b = jax.random.split(key)
    vals = jax.random.normal(a, (rows, n, k), jnp.float32).astype(jnp.bfloat16)
    idx = jnp.sort(jax.vmap(jax.vmap(lambda kk: jax.random.permutation(
        kk, d)[:k]))(jax.random.split(b, rows * n).reshape(rows, n, 2)), -1)
    return vals, idx.astype(jnp.int32)


def test_sfa_fwd_bytes():
    from repro.kernels.flash_sfa import flash_sfa
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    qv, qi = codes(ks[0], BH, N, K, D)
    kv, ki = codes(ks[1], BH, N, K, D)
    v = jax.random.normal(ks[2], (BH, N, D)).astype(jnp.bfloat16)
    out, lse = flash_sfa(qv, qi, kv, ki, v, d=D, return_residuals=True)
    w = OPS["sfa_fwd"].count(bh=BH, n=N, k=K, dv=D)
    assert w.bytes == nbytes(qv, qi, kv, ki, v, out, lse)
    assert w.flops == BH * N * (N + 1) // 2 * 2 * (K + D)


def test_sfa_bwd_bytes():
    from repro.kernels.flash_sfa import flash_sfa
    from repro.kernels.flash_sfa_bwd import flash_sfa_bwd
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    qv, qi = codes(ks[0], BH, N, K, D)
    kv, ki = codes(ks[1], BH, N, K, D)
    v = jax.random.normal(ks[2], (BH, N, D)).astype(jnp.bfloat16)
    o, lse = flash_sfa(qv, qi, kv, ki, v, d=D, return_residuals=True)
    g = jax.random.normal(ks[3], (BH, N, D)).astype(jnp.bfloat16)
    dq, dk, dv = flash_sfa_bwd(qv, qi, kv, ki, v, o, lse, g, d=D,
                               emit="compact")
    w = OPS["sfa_bwd"].count(bh=BH, n=N, k=K, dv=D,
                             grad_b=jnp.dtype(dq.dtype).itemsize)
    assert w.bytes == nbytes(qv, qi, kv, ki, v, o, lse, g, dq, dk, dv)
    assert dq.shape == (BH, N, K) and dv.dtype == v.dtype


def gpt2():
    path = registry.config_file(registry.benchmark(), "gpt2-small-sfa8")
    return (registry.model_config(registry.load_json(path)["model"]),
            registry.layout(path))


@pytest.mark.parametrize("config", ["gpt2-small-sfa8"])
def test_matmul_params_counts_the_weights(config):
    """On a dense configuration N_matmul is every matrix of the layout
    file, the token table as the tied logits matmul, and not the position
    table (a lookup)."""
    path = registry.config_file(registry.benchmark(), config)
    cfg = registry.model_config(registry.load_json(path)["model"])
    layout = registry.layout(path)
    mats = sum(math.prod(leaf["shape"]) for leaf in layout
               if leaf["path"][-1] == "w" and leaf["path"][0] != "pos")
    assert work.matmul_params(cfg, layout) == mats


def test_gpt2_train_flops_per_token():
    cfg, layout = gpt2()
    assert work.matmul_params(cfg, layout) == 123_532_032
    f = work.train_flops_per_token(cfg, layout, 4096)
    assert f == 6 * 123_532_032 + 3 * 12 * 12 * 4097 * (8 + 64)
    assert f == 868_625_280.0


def test_gpt2_step_work():
    """One step at batch 4 x 4096: the counts the cell's rooflines read
    before the operations became files of their own."""
    cfg, _ = gpt2()
    got = train_cell.step_work(cfg, {"batch": 4, "seq_len": 4096})
    assert got == {"sfa_fwd": work.Work(695_954_571_264, 839_909_376),
                   "sfa_bwd": work.Work(1_391_909_142_528, 1_519_386_624)}


@pytest.mark.parametrize("n, window", [(9, None), (9, 4), (9, 9), (9, 20),
                                       (128, 1), (128, 32)])
def test_causal_pairs(n, window):
    """A query sees itself and the keys before it, its last ``window`` at
    most (the system's mask: key > query - window)."""
    want = sum(1 for q in range(n) for k in range(q + 1)
               if window is None or k > q - window)
    assert work.causal_pairs(n, window) == want


def test_ops_are_files():
    """Each operation file names its kernels; the trace's kernel map is
    theirs."""
    assert set(OPS) >= {"sfa_fwd", "sfa_bwd", "proj_rtopk", "code_grad"}
    assert registry.kernel_map()["sfa_fwd"] == [r"^flash_sfa\.\d+$"]
    for op in OPS.values():
        assert op.NAMES and callable(op.work)


def test_roofline_takes_the_larger_bound():
    w = work.Work(flops=197e9, bytes=819e6)
    assert w.roofline_s(197e12, 819e9) == pytest.approx(1e-3)
    assert w.bound(197e12, 819e9) == "compute"
    assert work.Work(1.0, 819e9).bound(197e12, 819e9) == "bytes"
