"""Seconds of the ``train/step`` spans that ended before the window
(the three checked steps and the warm-up) less the ``train/step_load``
inside them, plus ``train/epoch_end``: the task's hook after each
epoch, where the benchmark's probe waits for the device and reads the
norms (the program's spans)."""

from benchmarks.layer_metrics import process_timeline


def read(run):
    return process_timeline.first_steps_seconds(run)
