"""The one traffic generator: it reads a mix's parameters
(``benchmarks/traffic/<mix>.json``) and a configuration and makes the
run's inputs from the seed. A new mix is a new data file.

Two families, chosen by the mix's ``runner``:

* ``train`` - a pool of ``pool_batches`` distinct batches of
  ``batch_rows`` rows, made ahead of the window by the task's
  ``make_batch`` (``benchmarks/tasks/<task>.py``) and cycled, so the
  host never sets the pace.
* ``decode`` - generation requests: prompt and output lengths from
  clipped log-normal distributions, all contents unique, sent by
  ``clients`` waiting callers, each of which sends its next request
  when the last has completed.

Every seed sees the same sizes (drawn once from the mix's
``sizes_seed``), dealt to the clients in another order and filled with
other contents, so that the seed does not change the amount of work.
Open-loop arrivals and prefixes shared within sessions come with the
cells that need them (PERF.md, Open questions).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Iterator, List

import numpy as np

# the tokenizer's special ids ([PAD] [UNK] [MASK]) lie below
# this; generated tokens never use them
N_SPECIAL_DEFAULT = 3


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


# --- tokens -----------------------------------------------------------------


def zipf_ids(rng, vocab_size: int, n_special: int, shape) -> np.ndarray:
    """Token ids with a 1/rank unigram distribution over the non-special
    part of the vocabulary (inverse-CDF sampling)."""
    cdf = _zipf_cdf(vocab_size - n_special)
    idx = np.searchsorted(cdf, rng.random(shape), side="right")
    return (np.minimum(idx, len(cdf) - 1) + n_special).astype(np.int32)


@functools.lru_cache(maxsize=8)
def _zipf_cdf(n: int) -> np.ndarray:
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64))
    return cdf / cdf[-1]


def train_batches(mix: dict, cfg: dict, seed: int, make_batch
                  ) -> List[dict]:
    """The run's pool of distinct batches, in the order they are fed;
    ``make_batch`` is the task's (``benchmarks/tasks/<task>.py``)."""
    rng = _rng(seed, 1)
    return [make_batch(rng, mix["batch_rows"], cfg)
            for _ in range(mix["pool_batches"])]


# --- generation requests -----------------------------------------------------

LANE_STRIDE = 1_000_000  # a request's index is lane * stride + its turn


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray        # int32 token ids
    max_new: int


def _clipped_lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def request_sizes(mix: dict) -> np.ndarray:
    """The mix's fixed set of (prompt, output) lengths, (n_sizes, 2):
    the same for every seed."""
    rng = _rng(mix.get("sizes_seed", 0), 2)
    n = mix["n_sizes"]
    return np.stack([_clipped_lognormal(rng, mix["prompt"], n),
                     _clipped_lognormal(rng, mix["output"], n)], axis=1)


def request_lanes(mix: dict, cfg: dict, seed: int
                  ) -> List[Iterator[Request]]:
    """The mix's requests as endless lanes, one per waiting client.

    The set of sizes and how it is dealt into lanes are the mix's own,
    drawn once from ``sizes_seed``: the same for every seed, so that a
    run's amount of work, and which long prompt meets which in the
    engine, do not turn on the seed. The seed decides which client
    sends which lane, and every token: all contents differ."""
    sizes = request_sizes(mix)
    lanes = int(mix["clients"])
    if len(sizes) % lanes:
        raise ValueError(f"n_sizes {len(sizes)} is not a multiple of the "
                         f"{lanes} clients")
    rows = sizes.reshape(lanes, -1, 2)
    dealt = _rng(seed, 3).permutation(lanes)
    n_special = cfg.get("num_special_tokens", N_SPECIAL_DEFAULT)

    def lane(c: int) -> Iterator[Request]:
        rng = _rng(seed, 100 + c)
        for k in itertools.count():
            n_prompt, n_out = (int(v) for v in
                               rows[dealt[c]][k % rows.shape[1]])
            yield Request(c * LANE_STRIDE + k,
                          zipf_ids(rng, cfg["vocab_size"], n_special,
                                   n_prompt), n_out)

    return [lane(c) for c in range(lanes)]


def warmup_requests(mix: dict, cfg: dict, seed: int) -> List[Request]:
    """The few requests sent before the window: the mix's first sizes,
    contents of their own."""
    rng = _rng(seed, 6)
    n_special = cfg.get("num_special_tokens", N_SPECIAL_DEFAULT)
    return [Request(-1 - i, zipf_ids(rng, cfg["vocab_size"], n_special,
                                     int(n_prompt)), int(n_out))
            for i, (n_prompt, n_out) in enumerate(
                request_sizes(mix)[:mix["warmup_requests"]])]
