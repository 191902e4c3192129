"""Median over the traced window's steps of the host's own work in a
step: the program's spans ``train/input_wait`` + ``train/shard`` +
``train/dispatch`` + ``train/guard_sync`` + ``train/log`` under one
``train/step``. ``train/fence``, the host waiting for the device, is not
in it. With a fence every step this is what the device waits for
between two steps."""

from benchmarks import scope_times


def read(run):
    return scope_times.host_ms_per_step(run)
