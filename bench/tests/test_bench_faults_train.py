"""The training check, at a size a CPU run can hold: the harness's run with
the timed path broken underneath comes out not correct, once for each fault
a training cell can have, and so does the control (the reference in the
program's place at the precision the mix names, below bfloat16)."""
import pytest

import run as bench_run
import small
from benchlib import train_cell

CELL = ("gpt2-small-sfa8", "train-b4-n4096")


def broken(monkeypatch, fault):
    import repro.train.train_step as ts
    real_make = ts.make_train_step

    def make(cfg, ocfg, **kw):
        real = real_make(cfg, ocfg, **kw)

        def step(params, opt, batch):
            if fault == "half_batch":
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return real(params, opt, half)
            new_params, new_opt, met = real(params, opt, batch)
            return params, opt, met          # state left unchanged

        return step

    monkeypatch.setattr(ts, "make_train_step", make)


def run_small(seed):
    return bench_run.run(small.args(seed=seed, seconds=1), need_tpu=False,
                         cell=small.cell(*CELL))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(monkeypatch, fault):
    broken(monkeypatch, fault)
    res = run_small(2 ** 33 + 22)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    cell = small.cell(*CELL)
    seed = 2 ** 33 + 23
    ref = train_cell.reference_readings(cell, seed)
    ctl = train_cell.reference_readings(
        cell, seed, mode=cell.traffic["check"]["control"])
    g = train_cell.gaps(ctl, ref)
    lim = cell.traffic["limits"]
    checks = {k: {"value": g[k], "limit": lim[k]} for k in lim}
    assert not bench_run.judge(checks), checks
