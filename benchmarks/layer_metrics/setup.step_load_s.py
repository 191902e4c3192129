"""Seconds of every ``train/step_load`` before the window: lowering the
train step, compiling it or reading it from the caches, and the
executable cache's write (the program's span around
``step_flops_and_fn``)."""

from benchmarks import scope_times


def read(run):
    return scope_times.setup_seconds(run, "train/step_load",
                                     "setup.step_load_s")
