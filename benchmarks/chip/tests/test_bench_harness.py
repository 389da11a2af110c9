"""The harness finds every part by name, prints the result line the
contract asks for, refuses to run without a TPU, and reads ``correct``
false when the timed path is broken underneath."""
import dataclasses
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import control
import run
from loader import HERE, ROOT, UnknownName, cell, load, read_json

# The four-chip cell waits under PERF.md's Open questions with its files in
# place; its entries are added here so that its path stays tested.
FOUR_CHIP = {"name": "delaunay-n16.sssp-4chip", "config": "delaunay-n16",
             "traffic": "sssp-4chip", "chips": 4}
COLLECTIVE_SHARE = {"name": "collective_share", "unit": "%",
                    "better": "lower", "source": "device_trace",
                    "layer": "exchange", "moves": "analytic_s",
                    "workloads": [FOUR_CHIP["name"]]}
_spec = read_json(ROOT / "BENCHMARK.json")
SPEC = dict(_spec, workloads=_spec["workloads"] + [FOUR_CHIP],
            per_layer=_spec["per_layer"] + [COLLECTIVE_SHARE])
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY_VERTICES = 256


def tiny(name: str) -> dict:
    c = cell(SPEC, name)
    c["config_file"] = dict(c["config_file"], vertices=TINY_VERTICES)
    return c


def run_tiny(name: str, trace=False) -> dict:
    c = tiny(name)
    return run.run_cell(c, 2**31 + 101, 0.05, trace,
                        jax.devices()[:int(c["chips"])], time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_every_part_of_a_cell_is_found_by_name(name):
    c = cell(SPEC, name)
    t = c["traffic_file"]
    load("drivers", t["driver"])
    for kind in ("analytics", "references"):
        load(kind, t["analytic"])
    for m in c["per_layer"]:
        assert callable(load("metrics", m["name"]).read)
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert c["config_file"]["name"] == c["config"]


def test_unknown_names_are_refused():
    with pytest.raises(UnknownName):
        cell(SPEC, "no-such.cell")
    with pytest.raises(UnknownName):
        load("metrics", "no_such_metric")


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_configuration_is_the_instance_its_source_names(entry):
    """``delaunay_nK`` in the source is 2**K points, and nothing is cut."""
    cfg = read_json(ROOT / entry["file"])
    k = int(re.search(r"/delaunay_n(\d+) ", entry["source"]).group(1))
    assert cfg["graph"] == "delaunay" and cfg["vertices"] == 2**k
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert entry["reduced"] == cfg["reduced"] == []


@pytest.mark.parametrize("name", CELLS)
def test_result_line_schema(name):
    res = run_tiny(name)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in cell(SPEC, name)
                                   ["end_to_end"]}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0 or \
            m["unit"] == "MiB"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    for chk in res["checks"].values():
        assert set(chk) == {"value", "limit"}
    json.loads(json.dumps(res))


def test_per_layer_readers_on_made_up_readings():
    r = {"build_s": 1.5, "setup_compile_s": 2.0, "runs": 4,
         "sweeps": [100, 100, 100, 100], "window_compiles": 0,
         "window_cache_loads": 4, "num_parts": 16,
         "full_sweep_bytes": 16 * 1000, "device_kind": "TPU v5 lite",
         "trace": {"window_s": 10.0, "busy_s": {0: 8.0},
                   "collective_s": {0: 0.0}}}
    read = {m["name"]: load("metrics", m["name"]).read(r)
            for m in SPEC["per_layer"]}
    assert read["sweeps_per_run"] == 100
    assert read["window_cache_loads"] == 1
    assert read["device_idle_share"] == pytest.approx(20.0)
    assert read["collective_share"] is None
    assert read["sweep_hbm_share"] == pytest.approx(
        100 * 400 * 1000 / (8.0 * 819e9))
    with pytest.raises(KeyError):
        load("metrics", "sweep_hbm_share").read(dict(r, device_kind="GPU"))


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    files, a run (with its look for a chip stood in for) fails before it
    prints a result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    code = ("import sys, jax, run; "
            "run.chips_or_none = lambda n: jax.devices()[:n]; "
            f"sys.exit(run.main(['--workload', {CELLS[0]!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=tmp_path / "benchmarks" / "chip",
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr


# ---- the timed path broken underneath: correct must read false ----

def _unchanged(real, analytic):
    """A step that returns its state unchanged: the run's initial state."""
    def fn(pg, *a, **k):
        out, tele = real(pg, *a, **k)
        if analytic == "sssp":
            x = np.full(out.shape, np.inf, np.float32)
            x[pg.part_of[a[0]], pg.local_of[a[0]]] = 0.0
        else:
            x = np.where(pg.vmask, 1.0 / pg.n_global, 0.0).astype(np.float32)
        return x, tele
    return fn


def _half(real, analytic):
    """Half of the partitions' answers left out (zero)."""
    def fn(pg, *a, **k):
        out, tele = real(pg, *a, **k)
        out = np.array(out)
        out[: pg.num_parts // 2] = 0.0
        return out, tele
    return fn


def _no_exchange(real, analytic):
    """The exchange between partitions left out: no remote edges."""
    from repro.gofs.formats import PAD

    def fn(pg, *a, **k):
        cut = dataclasses.replace(pg, re_src=np.full_like(pg.re_src, PAD))
        return real(cut, *a, **k)
    return fn


def _altered(real, analytic):
    """One answer altered where it is produced."""
    def fn(pg, *a, **k):
        out, tele = real(pg, *a, **k)
        out = np.array(out)
        p, l = np.argwhere(pg.vmask & np.isfinite(out) & (out > 0))[-1]
        out[p, l] = out[p, l] * (1 + 1e-3)
        return out, tele
    return fn


FAULTS = {"unchanged": _unchanged, "half": _half,
          "no_exchange": _no_exchange, "altered": _altered}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_reads_incorrect(monkeypatch, name, fault):
    from repro import algorithms
    analytic = cell(SPEC, name)["traffic_file"]["analytic"]
    real = getattr(algorithms, analytic)
    monkeypatch.setattr(algorithms, analytic, FAULTS[fault](real, analytic))
    res = run_tiny(name)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_in_the_programs_place_reads_incorrect(name):
    """The reference in bfloat16, put in the entry's place, goes through
    the harness's own set-up, window and check, and fails it."""
    c = tiny(name)
    res = control.run_control(c, 2**31 + 7, 0.05,
                              jax.devices()[:int(c["chips"])])
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    limits = load("references", c["traffic_file"]["analytic"]).LIMITS
    assert any(not chk["value"] <= limits[k]
               for k, chk in res["checks"].items())
    from repro import algorithms
    assert algorithms.sssp.__module__ == "repro.algorithms.sssp"
