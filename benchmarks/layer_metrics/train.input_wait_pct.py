"""Share of the traced window the trainer's loop spent in
``train/input_wait``, its pull from the (prefetching) loader: the
program's own span, summed over the window's steps."""

from benchmarks import scope_times


def read(run):
    return scope_times.input_wait_share(run)
