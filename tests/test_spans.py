"""The host spans of the placement search: a few generations of the
batched GA under the profiler open every span, nest the scoring call's
phases inside it, hold no span across a yield, and leave the search's
results as they are without a trace."""
import jax
import numpy as np
import pytest

from bench import reduce
from repro.core import spans
from repro.core.chiplets import paper_arch
from repro.core.optimize import Evaluator, genetic_algorithm_batched
from repro.core.placement_homog import HomogRep

ARCH = paper_arch("homog32", "baseline")
GA = {"population": 8, "elitism": 2, "tournament": 3, "max_generations": 3}


@pytest.fixture(scope="module")
def searches(tmp_path_factory):
    """The same seeded search run without and then under a trace, with
    the trace's host spans of the program."""
    rep = HomogRep(ARCH, R=8, C=5)
    ev0 = Evaluator(rep, ARCH, rng=np.random.default_rng(0), norm_samples=8,
                    chunk=8)

    def search():
        ev = Evaluator(rep, ARCH, rng=np.random.default_rng(0),
                       scorer=ev0.scorer, norm=ev0.norm)
        return genetic_algorithm_batched(ev, np.random.default_rng(1), **GA)

    plain = search()
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        traced = search()
    finally:
        jax.profiler.stop_trace()
    events = [e for e in reduce.read_xplane(reduce.latest_xplane(out))
              if e.name in spans.ALL]
    return plain, traced, events


def _of(events, name):
    return [e for e in events if e.name == name]


def test_every_span_appears(searches):
    _, _, events = searches
    assert {e.name for e in events} == set(spans.ALL)
    # One selection span before each generation's children and one after.
    assert len(_of(events, spans.SELECT)) == 2 * (GA["max_generations"] - 1)\
        + 1


def test_score_phases_nest_in_the_scoring_call(searches):
    _, _, events = searches
    calls = _of(events, spans.SCORE)
    for name in (spans.SCORE_DISPATCH, spans.SCORE_WAIT, spans.SCORE_FETCH):
        inner = _of(events, name)
        assert len(inner) == len(calls)
        for e in inner:
            assert any(c.start <= e.start and e.end <= c.end for c in calls)


def test_no_span_across_a_yield(searches):
    _, _, events = searches
    calls = _of(events, spans.SCORE)
    for name in (spans.SELECT, spans.PRODUCE, spans.RESAMPLE, spans.REPAIR):
        for s in _of(events, name):
            assert not any(min(s.end, c.end) > max(s.start, c.start)
                           for c in calls), name


def test_results_unchanged_under_a_trace(searches):
    plain, traced, _ = searches
    assert traced.best_cost == plain.best_cost
    for a, b in zip(traced.best_sol, plain.best_sol):
        np.testing.assert_array_equal(a, b)
    assert [h[1:] for h in traced.history] == [h[1:] for h in plain.history]
    assert traced.n_generated == plain.n_generated
