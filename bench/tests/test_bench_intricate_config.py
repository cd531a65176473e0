"""A configuration more intricate than the dense GPT-2 block enters the
benchmark as files alone. ``data/configs/deepseek-v2-lite-cut.json`` is
shaped like DeepSeek-V2-Lite: MLA latent attention whose value width differs
from its query/key width, a leading dense layer, then layers of routed and
shared experts, and scope names of its own. Cut to a size a CPU run holds,
it goes through the harness's own path: ``registry.model_config``, the
layout that ``tools/layout.py`` writes, ``weights.make``,
``registry.check_layout``, the training cell, the work counts."""
import math
from pathlib import Path

import numpy as np
import pytest

import run as bench_run
import small
from benchlib import registry, scopes, train_cell, weights, work

CONFIG = Path(__file__).resolve().parent / "data" / "configs" / \
    "deepseek-v2-lite-cut.json"


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    config = registry.load_json(CONFIG)
    out = tmp_path_factory.mktemp("layout") / "layout.json"
    assert small.layout_tool().main(["--config", str(CONFIG),
                                     "--out", str(out)]) == 0
    return (config, registry.model_config(config["model"]),
            registry.load_json(out)["leaves"])


def test_config_is_built_as_stated(built):
    _, cfg, layout = built
    a = cfg.attention
    assert (a.mla.q_lora_rank, a.mla.v_head_dim, a.sfa_k) == (24, 32, 4)
    assert a.mla.nope_head_dim + a.mla.rope_head_dim != a.mla.v_head_dim
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.num_shared,
            cfg.moe.first_dense) == (4, 2, 1, 1)
    assert a.window is None and a.sfa_rope_protect == 0
    roles = {leaf["role"] for leaf in layout}
    assert roles == {"matmul", "experts", "lookup", "norm"}


def test_trains_three_steps(built):
    config, cfg, layout = built
    seed = 2 ** 33 + 41
    registry.check_layout(cfg, weights.make(seed, layout))
    cell = registry.Cell(name="deepseek-v2-lite-cut.train", chips=1,
                         config=config, layout=layout,
                         traffic=small.mix("train-b4-n4096"),
                         end_to_end=[], per_layer=[])
    devices, peaks = bench_run.check_device(1, need_tpu=False)
    ctx = bench_run.Context(small.args(seed=seed, seconds=1), devices, peaks,
                            1.0)
    out, (losses, grad1, change) = train_cell.train(cell, ctx)
    assert len(losses) == 3 and np.all(np.isfinite(losses)), losses
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert len(grad1) == len(change) == len(layout)
    assert all(np.isfinite(grad1)) and all(np.isfinite(change))


def test_counts_by_hand(built):
    _, cfg, layout = built
    # per layer: MLA w_dq 64x24, w_uq_nope 24x(2*16), w_uq_pe 24x(2*8),
    # w_dkv 64x32, w_uk 32x(2*16), w_kpe 64x8, w_uv 32x(2*32), w_o 64x64
    attn = 1536 + 768 + 384 + 2048 + 1024 + 512 + 2048 + 4096
    dense = 64 * 320 + 160 * 64          # up_gate and down, d_ff 160
    experts = 3 * 4 * 64 * 16 * 2 / 4    # up, gate, down: 2 of 4 experts
    moe = 64 * 4 + experts + 3 * 64 * 16  # router, experts, 1 shared
    lm_head = 64 * 256                   # untied
    assert work.matmul_params(cfg, layout) == 3 * attn + dense + 2 * moe \
        + lm_head == 103_296
    # SFA at batch 2 x 128 on 3 layers of 2 heads: k 4, dv = v_head_dim 32,
    # P = 128 * 129 / 2 = 8256 pairs a head, RoPE emits 2k = 8
    bh, n, k, dv, p = 4, 128, 4, 32, 8256
    fwd_bytes = bh * (2 * n * k * 6 + 2 * n * dv * 2 + n * 4)
    bwd_bytes = bh * (2 * n * k * 6 + 3 * n * dv * 2 + n * 4
                      + 2 * n * 8 * 2 + n * dv * 2)
    got = train_cell.step_work(cfg, {"batch": 2, "seq_len": 128})
    assert got == {"sfa_fwd": work.Work(3 * bh * p * 2 * (k + dv),
                                        3 * fwd_bytes),
                   "sfa_bwd": work.Work(3 * bh * p * 4 * (k + dv),
                                        3 * bwd_bytes)}
    assert got["sfa_fwd"] == work.Work(7_133_184, 276_480)
    assert got["sfa_bwd"] == work.Work(14_266_368, 522_240)
    assert work.train_flops_per_token(cfg, layout, 128) == \
        6 * 103_296 + 3 * 3 * 2 * p * 2 * (k + dv) / n


def test_experts_held_are_scaled_by_the_router():
    """A layer that holds a share of the experts its router picks among
    counts top_k over the router's width of what it holds."""
    from repro.configs.base import MoEConfig
    cfg = type("Cfg", (), dict(moe=MoEConfig(num_experts=64, top_k=6,
                                             expert_dim=8),
                               tie_embeddings=False))
    layout = [{"path": ["r", "w"], "shape": [5, 16, 64], "role": "matmul"},
              {"path": ["up"], "shape": [5, 8, 16, 8], "role": "experts",
               "router": ["r", "w"]}]
    assert work.matmul_params(cfg, layout) == 5 * 16 * 64 + \
        math.prod([5, 8, 16, 8]) * 6 / 64


@pytest.mark.parametrize("extra", [{"n_routed_experts": 64},
                                   {"mla": {"kv_rank": 32}},
                                   {"moe": {"num_experts": 4, "topk": 2}}])
def test_unknown_model_key_raises(built, extra):
    config, _, _ = built
    model = dict(config["model"], **extra)
    with pytest.raises(registry.BenchError):
        registry.model_config(model)


def test_declared_scopes(built):
    config, _, _ = built
    vocab = scopes.Vocabulary.of([config])
    assert vocab.names[-2:] == ("moe.router", "moe.experts")
    assert vocab.attribute("jit(step)/jvp()/while/body/moe.experts/dot:") \
        == ("moe.experts", False)

