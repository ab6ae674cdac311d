"""Host time of one call of ``TrainStep``'s compiled program: mean duration
of the ``jit.train_step`` spans whole inside the traced window.  The call
returns once the step is enqueued, so this is the host's part of a step.
``None`` for a program that writes no such span."""
import statistics

from chipbench import program_spans


def read(obs):
    spans = program_spans.named(obs.trace, program_spans.TRAIN_STEP,
                                obs.t0, obs.t1)
    if not spans:
        return None
    return statistics.fmean(d / 1e6 for _, _, _, d in spans)
