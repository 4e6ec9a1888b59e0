"""Named spans on the profiler's host plane.

``span(name)`` marks a stretch of host work as ``sipipe/<name>`` on the
JAX profiler's clock, the one its device planes use, so a trace shows
what the host was doing while the device ran or sat idle.  With no
profiler running a span costs about a microsecond.

Stage work carries its stage in the name (``stage1/recv``), so a reader
of the trace tells stages apart without host thread names:

  stage{i}/prepare     TSEM input preparation (CPU executor thread)
  stage{i}/wait_input  device thread waiting for a prepared input
  stage{i}/recv        waiting for and loading the upstream hidden state
  stage{i}/h2d         the step's inputs to the device
  stage{i}/launch      dispatch of the jitted stage step
  stage{i}/device      waiting for the step's result
  stage{i}/d2h         hidden state or logits to the host
  stage{i}/send        hand-off of the hidden state downstream
  stage{i}/emit        hand-off of the logits to sampling
  schedule             the scheduler building an iteration
  gate                 the driver waiting for a slot's sampled tokens
  sample               host sampling of one iteration's logits
  complete             scheduler bookkeeping of the sampled tokens
"""
from __future__ import annotations

import jax

PREFIX = "sipipe/"


def span(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name)
