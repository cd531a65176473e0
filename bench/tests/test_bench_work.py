"""Work counts equal the bytes of each operation's inputs and outputs at
small shapes, and the MFU formula's parameter count equals the weights'."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import registry, weights, work

BH, N, K, D = 2, 128, 4, 32


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
    w = work.sfa_fwd(bh=BH, n=N, k=K, dv=D)
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
    w = work.sfa_bwd(bh=BH, n=N, k=K, dv=D,
                     grad_b=jnp.dtype(dq.dtype).itemsize)
    assert w.bytes == nbytes(qv, qi, kv, ki, v, o, lse, g, dq, dk, dv)
    assert dq.shape == (BH, N, K) and dv.dtype == v.dtype


@pytest.mark.parametrize("config", ["gpt2-small-sfa8"])
def test_matmul_params_counts_the_weights(config):
    m = registry.load_json(registry.BENCH / "configs" / f"{config}.json")
    m = m["model"]
    shapes = jax.tree_util.tree_flatten_with_path(
        weights.layout(m), is_leaf=lambda x: isinstance(x, tuple))[0]
    mats = sum(int(np.prod(s)) for path, s in shapes
               if path[-1].key == "w" and path[0].key != "pos")
    assert work.matmul_params(m) == mats


def test_gpt2_train_flops_per_token():
    bench = registry.benchmark()
    m = registry.load_json(registry.config_file(bench, "gpt2-small-sfa8"))
    m = m["model"]
    assert work.matmul_params(m) == 123_532_032
    f = work.train_flops_per_token(m, 4096)
    assert f == 6 * 123_532_032 + 3 * 12 * 12 * 4097 * (8 + 64)


def test_roofline_takes_the_larger_bound():
    w = work.Work(flops=197e9, bytes=819e6)
    assert w.roofline_s(197e12, 819e9) == pytest.approx(1e-3)
    assert w.bound(197e12, 819e9) == "compute"
    assert work.Work(1.0, 819e9).bound(197e12, 819e9) == "bytes"
