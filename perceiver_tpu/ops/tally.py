"""Trace-time tallies of which form a call site took (the attention
core, the selective scan, the grouped product): a call site inside a
scanned or rematerialised layer counts once per trace of its body, not
once per execution."""

from __future__ import annotations

import collections
import contextlib
from typing import Iterator, List


class Tally:
    """``with tally.counting() as counts`` collects what ``tally.add``
    is given while the block traces."""

    _all: List["Tally"] = []   # every tally made: ``untallied`` mutes all

    def __init__(self) -> None:
        self._open = []
        Tally._all.append(self)

    @contextlib.contextmanager
    def counting(self) -> Iterator[collections.Counter]:
        counts = collections.Counter()
        self._open.append(counts)
        try:
            yield counts
        finally:
            self._open.remove(counts)

    def add(self, key) -> None:
        for counts in self._open:
            counts[key] += 1


@contextlib.contextmanager
def untallied() -> Iterator[None]:
    """A trace for shapes alone (a ``remat`` stack reckons what its
    names would hold from one): its call sites are the real trace's,
    seen a second time, and no tally counts them."""
    held = [(tally, tally._open[:]) for tally in Tally._all]
    for tally, _ in held:
        tally._open[:] = []
    try:
        yield
    finally:
        for tally, opened in held:
            tally._open[:] = opened


def format_tally(counts) -> str:
    """``fused[128x32]=4`` — one log line's worth; ``none traced``
    for an empty tally."""
    return " ".join(f"{key}={n}" for key, n in sorted(counts.items())) \
        or "none traced"
