"""Share of the device's busy time in the traced window under
``optimizer``: the optimizer's update and its application to the
parameters, inside the train step."""

from benchmarks import scope_times


def read(run):
    return scope_times.class_share(run, "optimizer")
