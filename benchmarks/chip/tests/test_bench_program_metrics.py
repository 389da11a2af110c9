"""The per-layer readers of the program's own spans, counters and device
stages, on readings, registries and stage maps made by hand, and on a
program that records none of them."""
import pytest

from loader import load
from repro.obs import MetricsRegistry

SPANS = ("engine", "layout", "upload", "compose_mailbox", "dispatch")
STAGES = {"jit_gopher_megastep": {"fusion.27": "gopher.sweep",
                                  "fusion.26": "gopher.sweep",
                                  "fusion.20": "gopher.deliver",
                                  "while.38": "gopher.sweep",
                                  "fusion.9": "gopher.stats"}}


def readings(runs=2):
    ops = [["jit_gopher_megastep:while.38 while tuple", 31.0],
           ["jit_gopher_megastep:fusion.27 fusion f32[1048576]", 16.0],
           ["jit_gopher_megastep:fusion.26 fusion pred[1048576]", 14.0],
           ["jit_gopher_megastep:fusion.20 fusion f32[458752]", 0.2],
           ["jit_gopher_megastep:fusion.9 fusion s32[16,16]", 0.1],
           ["jit_compose_mailbox_arrays:fusion.27 fusion s32[8]", 0.3],
           ["fusion.26 fusion pred[8]", 0.4]]
    return {"runs": runs, "trace": {"device_ops": ops, "window_s": 31.4,
                                    "busy_s": {0: 31.1}}}


def registry(runs=2):
    """A warm-up run and ``runs`` window runs, each span 0.1 s more than
    the one before it; the lockstep count grows by one per run."""
    reg = MetricsRegistry()
    for i in range(runs + 1):
        for j, span in enumerate(SPANS):
            reg.histogram("gopher_span_seconds",
                          {"span": span}).observe(0.1 * (i + 1) + j)
        reg.histogram("engine_lockstep_sweeps").observe(600 + i)
    return reg


def test_host_prep_s_sums_the_newest_samples_of_each_span():
    got = load("metrics", "host_prep_s").read({"runs": 2},
                                              registry().recent)
    # runs 2 and 3 of five spans: (0.2 + 0.3) * 5 + 2 * (0+1+2+3+4)
    assert got == pytest.approx((0.5 * 5 + 2 * 10) / 2)


def test_host_prep_s_ignores_spans_outside_preparation():
    reg = registry()
    reg.histogram("gopher_span_seconds", {"span": "download"}).observe(99.0)
    reg.histogram("gopher_span_seconds", {"span": "entry"}).observe(99.0)
    assert load("metrics", "host_prep_s").read({"runs": 2}, reg.recent) \
        == pytest.approx((0.5 * 5 + 2 * 10) / 2)


def test_lockstep_sweeps_per_run_is_the_mean_of_the_window():
    got = load("metrics", "lockstep_sweeps_per_run").read(
        {"runs": 2}, registry().recent)
    assert got == pytest.approx(601.5)


@pytest.mark.parametrize("name", ("host_prep_s", "lockstep_sweeps_per_run"))
def test_registry_readers_find_nothing_to_read(name):
    read = load("metrics", name).read
    assert read({"runs": 2}, MetricsRegistry().recent) is None  # no samples
    # a program without the registry's recent(): program_obs reads None
    assert read({"runs": 2}, lambda *a: None) is None
    assert read({"runs": 0}, registry().recent) is None


def test_stage_readers_sum_their_stage_skipping_containers():
    r = readings()
    assert load("metrics", "sweep_device_s").read(r, STAGES) \
        == pytest.approx(30.0 / 2)
    assert load("metrics", "deliver_device_s").read(r, STAGES) \
        == pytest.approx(0.2 / 2)


def test_stage_readers_read_zero_where_no_long_op_is_in_the_stage():
    r = readings()
    stages = {"jit_gopher_megastep": {"fusion.27": "gopher.sweep"}}
    assert load("metrics", "deliver_device_s").read(r, stages) == 0.0


@pytest.mark.parametrize("name", ("sweep_device_s", "deliver_device_s"))
def test_stage_readers_find_nothing_to_read(name):
    read = load("metrics", name).read
    assert read(readings(), {}) is None                     # no stages
    assert read(readings(runs=0), STAGES) is None
    assert read({"runs": 2, "trace": {"window_s": 1.0}}, STAGES) is None
    # a program without op_stages: the stage map is None
    assert read(dict(readings(), op_stages=None)) is None


def test_the_stage_map_is_computed_once_per_result():
    sweep = load("metrics", "sweep_device_s")
    r = dict(readings(), op_stages=STAGES)
    assert sweep.op_stage_map(r) is STAGES
    assert sweep.read(r) == pytest.approx(15.0)
    assert load("metrics", "deliver_device_s").read(r) == pytest.approx(0.1)


def test_program_obs_reads_the_programs_registry():
    from repro.obs import default_registry
    obs = load("analytics", "program_obs")
    default_registry().histogram("gopher_span_seconds",
                                 {"span": "test-only"}).observe(0.25)
    assert obs.recent("gopher_span_seconds", 1, {"span": "test-only"}) \
        == [0.25]
    assert obs.recent("no_such_histogram", 3) == []
    assert isinstance(obs.op_stages(), dict)
