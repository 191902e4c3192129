"""Seconds of ``train/build_state`` before the window: the eager
``model.init``, the task's weights restored into it, the optimizer's
state and the placement on the device (the program's span)."""

from benchmarks import scope_times


def read(run):
    return scope_times.setup_seconds(run, "train/build_state",
                                     "setup.state_build_s")
