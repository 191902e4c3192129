"""Wall seconds from the process's start to the window's opening that
lie under no span of the program: the counter that keeps its timeline
closed. ``setup.import_s + setup.state_build_s + setup.step_load_s +
setup.first_steps_s + setup.uncovered_s`` is the run's set-up seconds;
the line prints both sides, the longest holes by the spans around them,
and the benchmark's own spans that overlap them (said, not taken
off)."""

from benchmarks.layer_metrics import process_timeline


def read(run):
    return process_timeline.uncovered_seconds(run)
