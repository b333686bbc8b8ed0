"""The trace reduction on a synthetic trace and on a small recorded one,
the FW counts from shapes, and the per-layer metric readers."""
import os

import pytest

from bench import reduce
from bench.run import metric_reader

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start_us, end_us):
    return reduce.Event(plane, line, name, start_us * 1e3, end_us * 1e3)


@pytest.fixture
def synthetic():
    return reduce.Reduction([
        _ev(HOST, "python", reduce.WINDOW, 100, 1100),
        _ev(HOST, "python", "bench.produce", 150, 400),
        _ev(HOST, "python", "bench.score", 420, 470),
        _ev(HOST, "python", "PjitFunction(score)", 430, 440),
        _ev(HOST, "python", "np.asarray(jax.Array)", 680, 880),
        _ev(DEV, "XLA Modules", "jit_score(7)", 450, 650),
        _ev(DEV, "XLA Modules", "jit__child(3)", 50, 120),
        _ev(DEV, "XLA Ops", "%vmap_fw_counts_vmem_.2 = (f32[16,1,512,512]{3}, "
            "f32[16,1,512,512]{3}) custom-call(f32[16,1,512,512] %b)",
            460, 600),
        _ev(DEV, "XLA Ops", "fusion.3", 590, 640),     # overlaps the kernel
        _ev(DEV, "XLA Ops", "fusion.9", 50, 120),      # half before window
        _ev(DEV, "XLA Ops", "fusion.3", 900, 950),
    ])


def test_window_and_busy_union(synthetic):
    r = synthetic
    assert r.devices == [DEV]
    assert r.window_s == pytest.approx(1000e-6)
    # [100,120] + [460,640] + [900,950] = 20 + 180 + 50 us
    assert r.busy_s == pytest.approx(250e-6)


def test_op_and_module_time(synthetic):
    assert synthetic.op_s(reduce.FW_KERNELS.pattern) == pytest.approx(140e-6)
    assert synthetic.module_s(r"^jit_score\b") == pytest.approx(200e-6)
    top = synthetic.top_ops(2)
    assert [n for n, _ in top] == ["vmap_fw_counts_vmem_.2", "fusion.3"]
    assert top[1][1] == pytest.approx(100e-6)
    assert reduce.op_name("%fusion.3 = f32[16]{0} fusion(f32[16] %p)") == \
        "fusion.3"


def test_self_time_of_nested_ops():
    r = reduce.Reduction([
        _ev(HOST, "python", reduce.WINDOW, 0, 100),
        _ev(DEV, "XLA Ops", "%while.4 = (s32[]) while(...)", 10, 90),
        _ev(DEV, "XLA Ops", "%fw_counts_vmem = f32[] custom-call()", 20, 60),
        _ev(DEV, "XLA Ops", "%fusion.1 = f32[] fusion()", 60, 70),
    ])
    assert dict(r.top_ops(3)) == pytest.approx(
        {"fw_counts_vmem": 40e-9 * 1e3, "while.4": 30e-9 * 1e3,
         "fusion.1": 10e-9 * 1e3})


def test_idle_gaps_named_by_host(synthetic):
    gaps = synthetic.idle_gaps(10)
    assert [round(s * 1e6) for _, s in gaps] == [340, 260, 150]
    assert gaps[0][0] == "bench.produce: untraced host work"  # [120, 460]
    assert gaps[1][0] == "driver: np.asarray(jax.Array)"      # [640, 900]
    assert gaps[2][0] == "driver: untraced host work"         # [950, 1100]


def test_fw_counts_from_shapes():
    assert reduce.fw_relaxations(512) == 512 ** 3
    assert reduce.fw_ops(512) == 14 * 512 ** 3
    assert reduce.fw_hbm_bytes(512) == 12 * 512 * 512


def test_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(reduce.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = reduce.latest_xplane(str(tmp_path))
    assert path and os.path.getsize(path) > 0
    r = reduce.Reduction.from_dir(str(tmp_path))
    assert r.window_s > 0
    assert any(e.name == reduce.WINDOW for e in r.host)
    assert r.busy_s <= r.window_s


def test_readers(synthetic):
    run = {"window_s": 1e-3, "n_evaluated": 30, "n_generated": 40,
           "score_calls": 6, "generations": 3, "compiles_in_window": 0,
           "trace": synthetic}
    assert metric_reader("score_calls_per_gen")(run) == 2.0
    assert metric_reader("connected_share")(run) == 75.0
    assert metric_reader("window_compiles")(run) == 0
    assert metric_reader("scorer_ms_per_placement")(run) == \
        pytest.approx(200e-6 * 1e3 / 40)
    assert metric_reader("fw_us_per_placement")(run) == \
        pytest.approx(140e-6 * 1e6 / 40)
    assert metric_reader("device_idle_share")(run) == pytest.approx(75.0)
    assert metric_reader("fw_relaxations_per_s")(run) == \
        pytest.approx(16 * 512 ** 3 / 140e-6)
    untraced = dict(run, trace=None)
    for name in ("scorer_ms_per_placement", "fw_us_per_placement",
                 "device_idle_share", "fw_relaxations_per_s"):
        assert metric_reader(name)(untraced) is None
