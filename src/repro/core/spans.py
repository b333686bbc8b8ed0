"""Host spans of the placement search, by name.

Every span is a ``jax.profiler.TraceAnnotation``: outside a profiler
session it costs about a microsecond and records nothing; under
``jax.profiler.trace`` it becomes an event on the host plane of the trace,
on the same clock as the device's operations, so device idle time can be
put down to what the host was doing.  Spans in the search drivers carry
the generation as an argument (``span(SELECT, gen=g)``), which the trace
keeps as a stat beside the clean name.

No span is held open across a ``yield`` of a step generator: the caller
scores the request on the same thread between yields, and a span left
open would swallow that work.

| span | opened in | covers |
| --- | --- | --- |
| ``placeit.score`` | ``Evaluator.score_batch`` | the whole scoring call |
| ``placeit.score.dispatch`` | inside it | the scorer call that returns device arrays |
| ``placeit.score.wait`` | inside it | waiting for the device to finish them |
| ``placeit.score.fetch`` | inside it | copying them to the host |
| ``placeit.produce`` | ``DevicePipeline._until_connected_steps`` | index gathers and the produce/graph stage dispatch |
| ``placeit.resample`` | same, after each scoring round | connectivity flags, choice of the slots to resample, archive add |
| ``placeit.repair`` | same | writing resampled rows into their slots |
| ``placeit.select`` | the batched optimizers' host step | ranking, best update, selection, parent gathers, elite concat |
"""
from __future__ import annotations

import jax

SCORE = "placeit.score"
SCORE_DISPATCH = "placeit.score.dispatch"
SCORE_WAIT = "placeit.score.wait"
SCORE_FETCH = "placeit.score.fetch"
PRODUCE = "placeit.produce"
RESAMPLE = "placeit.resample"
REPAIR = "placeit.repair"
SELECT = "placeit.select"

ALL = (SCORE, SCORE_DISPATCH, SCORE_WAIT, SCORE_FETCH, PRODUCE, RESAMPLE,
       REPAIR, SELECT)

# ``span(name, **args)``: a context manager opening the named host span.
span = jax.profiler.TraceAnnotation
