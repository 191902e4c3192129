"""Seconds of the ``proc/gc`` spans (collections of Python's collector
over a millisecond, the program's spans) inside the whole window, over
the window's length."""

from benchmarks.layer_metrics import process_timeline


def read(run):
    return process_timeline.gc_pct(run)
