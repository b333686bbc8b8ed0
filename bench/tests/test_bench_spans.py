"""The readers of device-idle time under the program's host spans, on a
hand-built trace with known intervals."""
import pytest

from bench import idle, reduce
from bench.run import metric_reader

DEV, HOST = "/device:TPU:0", "/host:CPU"
READERS = ("fetch_idle_ms_per_call", "select_idle_ms_per_gen",
           "repair_idle_ms_per_gen", "untraced_idle_share")


def _ev(plane, line, name, start_us, end_us):
    return reduce.Event(plane, line, name, start_us * 1e3, end_us * 1e3)


def _host(name, a, b, line="python"):
    return _ev(HOST, line, name, a, b)


# Window [100, 1100] us; the device runs [200, 300], [500, 700] and
# [900, 950], so it is idle on [100, 200], [300, 500], [700, 900] and
# [950, 1100]: 650 us.
DEVICE = [_ev(DEV, "XLA Ops", "fusion.1", 200, 300),
          _ev(DEV, "XLA Ops", "fw_counts_vmem", 500, 700),
          _ev(DEV, "XLA Ops", "fusion.2", 900, 950)]
SPANS = [
    _host("placeit.produce", 50, 120),        # idle 20 inside the window
    _host("placeit.select", 150, 180),        # idle 30
    _host("bench.score", 270, 630),
    _host("placeit.score", 280, 620),         # idle 200
    _host("placeit.score.dispatch", 280, 290),
    _host("placeit.score.wait", 290, 400),    # idle 100
    _host("placeit.score.fetch", 400, 450),   # idle 50
    _host("placeit.score.fetch", 720, 760),   # idle 40
    _host("placeit.repair", 800, 850),        # idle 50
    _host("placeit.select", 960, 1000),       # idle 40
    _host("placeit.repair", 300, 500, line="other thread"),  # not counted
]


@pytest.fixture
def run():
    trace = reduce.Reduction(
        [_host(reduce.WINDOW, 100, 1100)] + DEVICE + SPANS)
    return {"trace": trace, "score_calls": 3, "generations": 2}


def test_idle_intervals(run):
    assert idle.idle_intervals(run["trace"]) == [
        (100e3, 200e3), (300e3, 500e3), (700e3, 900e3), (950e3, 1100e3)]


def test_idle_under_each_span(run):
    tr = run["trace"]
    assert idle.idle_ms(tr, "placeit.score.fetch") == pytest.approx(0.090)
    assert idle.idle_ms(tr, "placeit.score.wait") == pytest.approx(0.100)
    assert idle.idle_ms(tr, "placeit.select") == pytest.approx(0.070)
    assert idle.idle_ms(tr, "placeit.repair") == pytest.approx(0.050)
    assert idle.idle_ms(tr, "placeit.produce") == pytest.approx(0.020)
    # Nested spans count once: 20 + 30 + 200 + 40 + 50 + 40.
    assert idle.idle_ms(tr) == pytest.approx(0.380)


def test_readers(run):
    assert metric_reader("fetch_idle_ms_per_call")(run) == \
        pytest.approx(0.090 / 3)
    assert metric_reader("select_idle_ms_per_gen")(run) == \
        pytest.approx(0.070 / 2)
    assert metric_reader("repair_idle_ms_per_gen")(run) == \
        pytest.approx(0.050 / 2)
    # (650 - 380) us idle under no span, of a 1000 us window.
    assert metric_reader("untraced_idle_share")(run) == pytest.approx(27.0)


def test_readers_without_a_trace_or_span(run):
    for name in READERS:
        assert metric_reader(name)(dict(run, trace=None)) is None
    # A program that opens no span, as before it had any.
    bare = reduce.Reduction([_host(reduce.WINDOW, 100, 1100)] + DEVICE
                            + [_host("bench.score", 270, 630)])
    for name in READERS:
        assert metric_reader(name)(dict(run, trace=bare)) is None
    # A window with no slot repair.
    no_repair = reduce.Reduction(
        [_host(reduce.WINDOW, 100, 1100)] + DEVICE
        + [e for e in SPANS if e.name != "placeit.repair"])
    assert metric_reader("repair_idle_ms_per_gen")(
        dict(run, trace=no_repair)) is None
    assert metric_reader("select_idle_ms_per_gen")(
        dict(run, trace=no_repair)) == pytest.approx(0.035)


def test_readers_without_counts(run):
    assert metric_reader("fetch_idle_ms_per_call")(
        dict(run, score_calls=0)) is None
    for name in ("select_idle_ms_per_gen", "repair_idle_ms_per_gen"):
        assert metric_reader(name)(dict(run, generations=0)) is None


def test_idle_gaps_named_by_program_spans(run):
    gaps = [(n, round(s * 1e6)) for n, s in run["trace"].idle_gaps(10)]
    assert gaps == [
        ("bench.score: placeit.score", 200),             # [300, 500]
        ("driver: untraced host work", 200),             # [700, 900]
        ("driver: untraced host work", 150),             # [950, 1100]
        ("driver: untraced host work", 100)]             # [100, 200]
