"""Median interval between the trainer's consecutive batch pulls from
the benchmark's data module inside the window (the benchmark's span at
the trainer's boundary). The prefetch queue is bounded, so in steady
state the producer is released once a step; the first pulls of the
window fill the queue at once and are left out."""

import numpy as np

SKIP = 4  # pulls that fill the prefetch queue when the epoch starts


def read(run):
    pulls = run.outcome.data.get("pulls")
    if not pulls or len(pulls) < SKIP + 3:
        return None
    return float(np.median(np.diff(pulls[SKIP:]))) * 1e3
