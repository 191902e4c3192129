"""Share of the traced window in which no operation ran on the device:
one less the union of the device's operation intervals over the
window's length, averaged over the chips used."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
