"""``100 x (mean - median) / mean`` of the ``interval_s`` of the whole
window's ``train/step`` spans (the first four left out, as
``train.step_ms`` does): the share of the window lost against one in
which every step kept the median's pace. It reconciles ``train.step_ms``
(a median) with ``train_tokens_per_s`` (a mean): 0.0 or a hair under
where nothing stalled. The line lists the steps the program's slow-step
rule named, each with its phase."""

from benchmarks.layer_metrics import process_timeline


def read(run):
    return process_timeline.stall_pct(run)
