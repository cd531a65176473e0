"""Every cell resolves to its files by name, ``BENCHMARK.json`` keeps to
its contract, and a run without a TPU or with an unknown device kind
fails."""
import re
from pathlib import Path

import pytest

import run as bench_run
from benchlib import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = registry.benchmark()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_by_name(w):
    cell = registry.cell(w["name"])
    assert registry.config_file(BENCH, w["config"]).exists()
    assert registry.traffic_file(w["traffic"]).suffix in registry.DATA_SUFFIXES
    assert cell.traffic["kind"] in bench_run.drivers()
    registry.reference(cell.config["reference"])
    for m in cell.per_layer:
        assert callable(registry.metric_reader(m["name"]).read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    model = registry.model_config(cell.config["model"])
    assert model.attention.sfa_k == cell.config["model"]["sfa_k"]


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    seen = set()
    for k in ("configs", "workloads"):
        for x in BENCH[k]:
            assert x["name"] not in seen
            seen.add(x["name"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        data = registry.load_json(registry.ROOT / c["file"])
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_traffic_is_data():
    for w in BENCH["workloads"]:
        path = registry.traffic_file(w["traffic"])
        assert path.suffix == ".json" and path.parent.name == "traffic"


def test_no_tpu_fails(monkeypatch):
    with pytest.raises(registry.BenchError, match="no TPU"):
        bench_run.check_device(1)


def test_interpret_mode_fails(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(registry.BenchError, match="interpret"):
        bench_run.check_device(1)


def test_unknown_device_kind_fails(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    with pytest.raises(registry.BenchError, match="no peaks"):
        bench_run.check_device(1)


def test_too_few_chips_fails(monkeypatch):
    with pytest.raises(registry.BenchError, match="chips"):
        bench_run.check_device(64, need_tpu=False)


def test_main_prints_no_result_without_tpu(capsys):
    rc = bench_run.main(["--workload", BENCH["workloads"][0]["name"],
                         "--seed", str(2 ** 33), "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def test_peaks_table():
    peaks = registry.load_json(Path(registry.BENCH) / "peaks.json")
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_gpt2_model_config_is_unchanged():
    """The gpt2 block gives the ModelConfig that the fixed key list gave
    before every key of the block was applied by name."""
    from repro.configs.base import AttentionConfig, ModelConfig
    frozen = ModelConfig(
        name="gpt2-small-sfa8", family="dense", num_layers=12, d_model=768,
        d_ff=3072, vocab_size=50257,
        attention=AttentionConfig(
            num_heads=12, num_kv_heads=12, head_dim=64, sfa_k=8, window=None,
            local_global_pattern=None, mla=None, rope=False,
            rope_theta=10000.0, causal=True, qk_norm=False, backend="xla",
            decode_backend="auto", bwd_emit="dense", fwd_fuse=True,
            ring=False, sfa_rope_protect=0, sfa_draft_k=None),
        moe=None, ssm=None, rwkv=None, frontend=None, hybrid_period=None,
        hybrid_attn_index=None, norm="layernorm", act="gelu", glu=False,
        tie_embeddings=True, causal=True, pos_embedding="learned",
        max_seq_len=131072, dtype="bfloat16", remat="full", loss_chunk=512,
        sfa_distill=0.0)
    path = registry.config_file(BENCH, "gpt2-small-sfa8")
    assert registry.model_config(registry.load_json(path)["model"]) == frozen


def test_model_config_applies_every_field():
    """A key sets the field it names; a window and a nested block too, and
    a key that two config classes share sets both."""
    path = registry.config_file(BENCH, "gpt2-small-sfa8")
    m = dict(registry.load_json(path)["model"], window=256, causal=False,
             loss_chunk=1024, moe={"num_experts": 8, "top_k": 2,
                                   "expert_dim": 128})
    cfg = registry.model_config(m)
    assert cfg.attention.window == 256 and cfg.loss_chunk == 1024
    assert not cfg.causal and not cfg.attention.causal
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.num_shared) == (8, 2, 0)
