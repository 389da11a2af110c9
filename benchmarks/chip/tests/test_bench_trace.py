"""The trace reduction: on intervals made by hand, and on a small trace
recorded on a TPU v5e chip by ``record_trace.py`` (``data/``)."""
from pathlib import Path

import pytest

import trace_reduce as tr

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
MS = 1_000_000


def made_up():
    host = [("bench.window", 0, 100 * MS),
            ("bench.analytic", 0, 60 * MS),
            ("Transpose", 2 * MS, 10 * MS),
            ("bench.sleep", 70 * MS, 100 * MS)]
    dev0 = [("m:fusion.1 fusion f32[8]", "fusion", 10 * MS, 40 * MS),
            ("m:fusion.2 fusion f32[8]", "fusion", 30 * MS, 50 * MS),
            ("m:all-to-all.3 all-to-all f32[8]", "all-to-all",
             50 * MS, 60 * MS),
            ("m:fusion.5 fusion f32[8]", "fusion", 65 * MS, 70 * MS),
            ("m:fusion.1 fusion f32[8]", "fusion", 95 * MS, 120 * MS)]
    dev1 = [("m:collective-permute-done.4 collective-permute-done f32[8]",
             "collective-permute-done", 20 * MS, 30 * MS)]
    return {"devices": {0: dev0, 1: dev1}, "host": host}


def test_busy_is_the_union_inside_the_window():
    r = tr.reduce(made_up())
    assert r["window_s"] == pytest.approx(0.1)
    # 10-60, 65-70 and 95-100 ms (the op past the window is clipped)
    assert r["busy_s"][0] == pytest.approx(0.060)
    assert r["busy_s"][1] == pytest.approx(0.010)


def test_collectives_are_found_by_opcode():
    r = tr.reduce(made_up())
    assert r["collective_s"] == {0: pytest.approx(0.010),
                                 1: pytest.approx(0.010)}
    assert tr.is_collective("all-reduce-start")
    assert not tr.is_collective("fusion")


def test_idle_gaps_carry_the_host_span_under_them():
    gaps = dict(tr.reduce(made_up())["idle_gaps"])
    assert gaps["bench.analytic > Transpose"] == pytest.approx(0.010)
    assert gaps["bench.sleep"] == pytest.approx(0.025)
    assert gaps["bench.window"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.060)


def test_device_ops_rank_by_time_in_the_window():
    ops = tr.reduce(made_up())["device_ops"]
    assert ops[0] == ["m:fusion.1 fusion f32[8]", pytest.approx(0.035)]


def test_parse_hlo():
    assert tr.parse_hlo("%fusion.27 = f32[524288]{0:T(1024)S(1)} fusion("
                        "f32[65536]{0} %copy-done), kind=kCustom") == \
        ("fusion.27", "fusion", "f32[524288]")
    assert tr.parse_hlo("%while.5 = (f32[8]{0}, s32[]) while((f32[8]{0}, "
                        "s32[]) %tuple.72), condition=%c, body=%b") == \
        ("while.5", "while", "tuple")
    assert tr.parse_hlo("copy-start") == ("copy-start", "copy-start", "")


def test_a_window_span_is_required():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": [("other", 0, 1)]})


def test_recorded_chip_trace():
    """Three calls of a 50-step while loop, each followed by a 20 ms sleep
    in ``bench.sleep``: the device is busy in every call and idle in every
    sleep, and the gaps are put down to the sleeps."""
    r = tr.reduce(tr.extract(str(SMALL)))
    assert list(r["busy_s"]) == [0]
    busy, window = r["busy_s"][0], r["window_s"]
    assert 0 < busy < window
    gaps = dict(r["idle_gaps"])
    sleep = sum(v for k, v in gaps.items() if k.endswith("bench.sleep"))
    assert 0.055 < sleep < 0.1
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    assert r["collective_s"] == {0: 0.0}
    assert any(" while " in label for label, _ in r["device_ops"])
