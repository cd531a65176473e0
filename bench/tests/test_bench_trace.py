"""The reduction from a trace record to device metrics, on a hand-made
record and on a trace recorded on the chip (``tests/data``)."""
import json
from pathlib import Path

import pytest

from benchlib import trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS = {"sfa_fwd": ["_flash_sfa_skip_kernel"], "decode": ["_decode_paged"]}


def record():
    ms = 1_000_000
    return {
        "devices": {
            "0": [["fusion.1", 0, 2 * ms],
                  ["_flash_sfa_skip_kernel.3", 1 * ms, 3 * ms],
                  ["collective-permute.2", 5 * ms, 2 * ms],
                  ["fusion.2", 6 * ms, 2 * ms]],
            "1": [["_flash_sfa_skip_kernel.3", 0, 4 * ms],
                  ["collective-permute.2", 6 * ms, 2 * ms]],
        },
        "spans": [["bench.window", 0, 10 * ms],
                  ["bench.eng_step", 0, 8 * ms],
                  ["bench.idle", 8 * ms, 2 * ms]],
    }


def test_busy_idle_and_kernels():
    red = trace.reduce(record(), KERNELS)
    assert red.window_s == pytest.approx(0.010)
    # device 0 busy [0,4] + [5,8] = 7 ms; device 1 [0,4] + [6,8] = 6 ms
    assert red.busy_per_device == {"0": pytest.approx(0.007),
                                   "1": pytest.approx(0.006)}
    assert red.busy_s == pytest.approx(0.0065)
    assert red.idle_share == pytest.approx(0.35)
    assert red.op_seconds["sfa_fwd"] == pytest.approx(0.0035)
    assert red.op_events["sfa_fwd"] == 2
    assert "decode" not in red.op_seconds


def test_exposed_collectives():
    red = trace.reduce(record(), KERNELS)
    # device 0: permute [5,7], compute covers [6,7] -> 1 ms exposed;
    # device 1: permute [6,8], nothing else -> 2 ms exposed
    assert red.collective_s == pytest.approx(0.002)
    assert red.exposed_collective_s == pytest.approx(0.0015)


def test_idle_gaps_name_the_host_span():
    red = trace.reduce(record(), KERNELS)
    gaps = {(name, round(s * 1e3, 6)) for name, s in red.idle_gaps}
    assert ("bench.idle", 2.0) in gaps          # [8, 10] on both devices
    assert ("bench.eng_step", 1.0) in gaps      # [4, 5] on device 0
    assert red.idle_gaps[0][1] >= red.idle_gaps[-1][1]


def test_control_flow_events_are_not_work():
    """A loop's event spans its whole body: the idle time and the exposed
    collective inside it stay idle and exposed."""
    rec = record()
    ms = 1_000_000
    rec["devices"]["0"].append(["while.15", 0, 10 * ms])
    rec["devices"]["1"].append(["conditional.3", 0, 10 * ms])
    red = trace.reduce(rec, KERNELS)
    assert red.busy_per_device == {"0": pytest.approx(0.007),
                                   "1": pytest.approx(0.006)}
    assert red.exposed_collective_s == pytest.approx(0.0015)
    assert all(not name.startswith(("while", "conditional"))
               for name, _ in red.top_ops)
    assert ("bench.idle", 2.0) in {(n, round(s * 1e3, 6))
                                   for n, s in red.idle_gaps}


def test_window_clips_events():
    rec = record()
    rec["spans"][0] = ["bench.window", 1_000_000, 2_000_000]
    red = trace.reduce(rec, KERNELS)
    assert red.window_s == pytest.approx(0.002)
    assert red.busy_s == pytest.approx(0.002)
    assert red.idle_share == pytest.approx(0.0)


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []}, KERNELS)


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_trace(name):
    """A trace recorded on one v5e: every device event lies in the window,
    the busy time is the union of events, and the kernels the map names
    are found."""
    rec = json.loads((DATA / name).read_text())
    red = trace.reduce(rec)
    assert 0 < red.busy_s <= red.window_s
    ops = rec["devices"]["0"]
    assert red.busy_s <= sum(e[2] for e in ops) / 1e9 + 1e-12
    assert red.op_seconds, "no kernel of ops/ in the trace"
    assert sum(red.op_seconds.values()) <= red.busy_s + 1e-9
    assert len(red.top_ops) <= 10 and len(red.idle_gaps) <= 10
