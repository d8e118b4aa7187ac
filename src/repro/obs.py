"""Named spans at the places where the program's host work happens.

``span(name)`` is ``jax.profiler.TraceAnnotation(name)``: it costs about
a microsecond when no trace runs, and while one does it lands on the
profiler's host plane, on the same timeline as the device's ``XLA Ops``.
So a trace of a running ``FleetService`` (``jax.profiler.start_trace``)
puts each stretch of device idle time down to the innermost span below.
Every name starts with ``diva.``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

# operator training (core/training.CloudTrainer, core/operators)
TRAIN = "diva.train"                    # one CloudTrainer.train call
TRAIN_INIT = "diva.train.init"          # init_operator, zeroed Adam state
TRAIN_UPLOAD = "diva.train.upload"      # crops, labels, counts, every step's
                                        # draws and schedule terms to device
TRAIN_STEP = "diva.train.step"          # one Adam iteration, all of it
TRAIN_GATHER = "diva.train.gather"      # the jitted _gather_step call
TRAIN_DISPATCH = "diva.train.dispatch"  # the jitted _adam_step call
TRAIN_VALIDATE = "diva.train.validate"  # validation crops, scores, AUC

# frame path (core/training.FrameBank)
FRAMES_CROP = "diva.frames.crop"        # crop, resize, convert, stack
FRAMES_RENDER = "diva.frames.render"    # render missing frames to uint8

# scoring (core/runtime)
SCORE_SUBMIT = "diva.score.submit"      # one demand: chunks, padding
SCORE_STACK = "diva.score.stack"        # a superbatch's params, inputs
SCORE_DISPATCH = "diva.score.dispatch"  # input placement, the jit call
SCORE_WAIT = "diva.score.wait"          # blocked on results, converting

# fleet and oracle (core/fleet, serving/)
FLEET_RUN = "diva.fleet.run"            # FleetScheduler.run's loop
FLEET_SUBMIT = "diva.fleet.submit"      # make_env and the executor
VERIFY = "diva.verify"                  # OracleService's entry points


def span(name: str) -> TraceAnnotation:
    """A profiler span named ``name`` (a context manager)."""
    return TraceAnnotation(name)
