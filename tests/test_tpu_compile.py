"""The main path's Pallas kernels compile for a v5e chip, at real widths.

Interpret mode cannot see what Mosaic refuses: blocks off the (8, 128)
tiling, vector shapes it cannot lay out, more VMEM or SMEM than a core has.
These tests compile each kernel for one chip of a *described* v5e (the TPU
compiler runs here without a chip) at d=64 and d=128 with the block sizes the
model uses. Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a decision made while
collecting would give xdist workers different tests to run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_sfa import flash_sfa
from repro.kernels.flash_sfa_bwd import flash_sfa_bwd
from repro.kernels.flash_sfa_decode import (
    flash_sfa_decode_fm_paged, flash_sfa_decode_multi, flash_sfa_decode_paged,
)
from repro.kernels.rtopk import proj_rtopk

# (head_dim, sfa_k): gpt2-small-sfa8's heads, and the paper's d=128 point
WIDTHS = [(64, 8), (128, 16)]
BH, N = 8, 2048                         # folded batch*heads, sequence
SLOTS, HEADS, PAGE, MAX_PAGES, CHUNK = 8, 12, 128, 16, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # not read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _codes(d, k):
    return [((BH, N, k), jnp.bfloat16), ((BH, N, k), jnp.int32)] * 2 + \
        [((BH, N, d), jnp.bfloat16)]


@pytest.mark.parametrize("d,k", WIDTHS)
def test_flash_sfa_lse_block_skip(one_chip, d, k):
    fn = functools.partial(flash_sfa, d=d, return_residuals=True,
                           block_skip=True, interpret=False)
    _compile(fn, one_chip, *_codes(d, k))


@pytest.mark.parametrize("d,k", WIDTHS)
def test_flash_sfa_bwd_compact(one_chip, d, k):
    fn = functools.partial(flash_sfa_bwd, d=d, emit="compact",
                           interpret=False)
    dense = ((BH, N, d), jnp.bfloat16)
    _compile(fn, one_chip, *_codes(d, k), dense, ((BH, N), jnp.float32),
             dense)


@pytest.mark.parametrize("d,k", WIDTHS)
def test_proj_rtopk_rope_batched(one_chip, d, k):
    fn = functools.partial(proj_rtopk, k=k, rope_spec=(10_000.0, d),
                           interpret=False)
    _compile(fn, one_chip, ((2, N, 768), jnp.bfloat16),
             ((12, 768, d), jnp.bfloat16), ((2, N), jnp.int32))


@pytest.mark.parametrize("d,k", WIDTHS)
def test_flash_sfa_decode_paged(one_chip, d, k):
    fn = functools.partial(flash_sfa_decode_paged, d=d, heads=HEADS,
                           interpret=False)
    pool = (HEADS, SLOTS * MAX_PAGES + 1, PAGE)
    _compile(fn, one_chip, ((SLOTS * HEADS, d), jnp.bfloat16),
             (pool + (k,), jnp.bfloat16), (pool + (k,), jnp.uint8),
             (pool + (d,), jnp.bfloat16), ((SLOTS, MAX_PAGES), jnp.int32),
             ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("d,k", WIDTHS)
def test_flash_sfa_decode_multi_chunk(one_chip, d, k):
    # chunked prefill and speculative verify: CHUNK queries of one slot
    fn = functools.partial(flash_sfa_decode_multi, d=d, heads=HEADS,
                           block_n=PAGE, interpret=False)
    n = MAX_PAGES * PAGE
    _compile(fn, one_chip, ((CHUNK * HEADS, d), jnp.bfloat16),
             ((HEADS, n, k), jnp.bfloat16), ((HEADS, n, k), jnp.int32),
             ((HEADS, n, d), jnp.bfloat16), ((CHUNK * HEADS,), jnp.int32))


@pytest.mark.parametrize("d,k", WIDTHS)
def test_flash_sfa_decode_fm_paged(one_chip, d, k):
    fn = functools.partial(flash_sfa_decode_fm_paged, heads=HEADS,
                           interpret=False)
    pages = SLOTS * MAX_PAGES + 1
    _compile(fn, one_chip, ((SLOTS * HEADS, k), jnp.float32),
             ((SLOTS * HEADS, k), jnp.int32),
             ((HEADS, pages, d, PAGE), jnp.bfloat16),
             ((HEADS, pages, PAGE, d), jnp.bfloat16),
             ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32))
