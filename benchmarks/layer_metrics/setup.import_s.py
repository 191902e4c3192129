"""Self seconds, before the window, of the process's boot
(``proc/boot``: the kernel's start time of the process to the first
line of the program's package), of every heavy import the program makes
first (``proc/import``, by module) and of the accelerator runtime's
start where the program makes the first look for devices
(``proc/backend_init``): the program's spans. The line prints the
seconds by module, largest first."""

from benchmarks.layer_metrics import process_timeline


def read(run):
    return process_timeline.import_seconds(run)
