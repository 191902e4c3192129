"""The traffic generator: the seed decides order and contents, never
the amount of work."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, traffic  # noqa: E402

MIX = harness.load_json(os.path.join(
    ROOT, "benchmarks", "traffic", "closed8_unique.json"))
CFG = {"task": "mlm", "vocab_size": 32000, "max_seq_len": 2048,
       "num_special_tokens": 3}
BIG = 3_000_000_019  # the driver's seeds pass 2**31


def take(mix, seed, n):
    """The first ``n`` requests of every lane."""
    return [[next(lane) for _ in range(n)]
            for lane in traffic.request_lanes(mix, CFG, seed)]


def flat(lanes):
    return [r for lane in lanes for r in lane]


def sizes(rs):
    return sorted((len(r.prompt), r.max_new) for r in rs)


def test_same_seed_same_requests():
    a, b = flat(take(MIX, BIG, 9)), flat(take(MIX, BIG, 9))
    assert len(a) == 72
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.index == y.index for x, y in zip(a, b))


def test_other_seed_same_work_other_contents_other_deal():
    a, b = take(MIX, 1, 8), take(MIX, 2, 8)
    # one turn round every lane is the mix's whole set of sizes
    assert sizes(flat(a)) == sizes(flat(b)) == sorted(
        map(tuple, traffic.request_sizes(MIX)))
    # each lane is some lane of the other seed, request by request ...
    lanes = lambda t: sorted([(len(r.prompt), r.max_new) for r in lane]  # noqa: E731
                             for lane in t)
    assert lanes(a) == lanes(b)
    # ... dealt to another client, and no prompt is the same
    assert [sizes(x) for x in a] != [sizes(x) for x in b]
    seen = {r.prompt.tobytes() for r in flat(a)}
    assert not any(r.prompt.tobytes() in seen for r in flat(b))


def test_a_lane_cycles_its_sizes_with_new_contents():
    (lane, *_rest) = take(MIX, 3, 16)
    assert [(len(r.prompt), r.max_new) for r in lane[:8]] == \
        [(len(r.prompt), r.max_new) for r in lane[8:]]
    assert not np.array_equal(lane[0].prompt, lane[8].prompt)
    assert [r.index for r in lane] == list(range(16))


def test_sizes_follow_the_mix():
    s = traffic.request_sizes(MIX)
    assert s.shape == (MIX["n_sizes"], 2)
    assert s[:, 0].min() >= MIX["prompt"]["min"]
    assert s[:, 0].max() <= MIX["prompt"]["max"]
    assert s[:, 1].min() >= MIX["output"]["min"]
    assert s[:, 1].max() <= MIX["output"]["max"]
    assert 150 < np.median(s[:, 0]) < 400
    assert (s.sum(axis=1) <= CFG["max_seq_len"]).all()


def test_prompts_are_unique_and_avoid_special_ids():
    rs = flat(take(MIX, 5, 16))
    assert all(r.prompt.min() >= 3 and r.prompt.max() < 32000 for r in rs)
    assert len({tuple(r.prompt[:16]) for r in rs}) == len(rs)
    assert len({r.index for r in rs}) == len(rs)


def test_warmup_requests_are_the_first_sizes():
    warm = traffic.warmup_requests(MIX, CFG, 7)
    assert len(warm) == MIX["warmup_requests"]
    assert [(len(r.prompt), r.max_new) for r in warm] == [
        tuple(x) for x in traffic.request_sizes(MIX)[:len(warm)]]
    assert all(r.index < 0 for r in warm)


@pytest.mark.parametrize("task,cfg,field", [
    ("mlm", {**CFG, "max_seq_len": 64, "vocab_size": 512}, "input_ids"),
    ("img_clf", {"task": "img_clf", "image_shape": [8, 8, 3],
                 "num_classes": 10}, "image"),
])
def test_train_batches_differ_and_repeat_by_seed(task, cfg, field):
    mix = {"batch_rows": 4, "pool_batches": 3}
    make = harness.load_task(task).make_batch
    a = traffic.train_batches(mix, cfg, BIG, make)
    b = traffic.train_batches(mix, cfg, BIG, make)
    c = traffic.train_batches(mix, cfg, BIG + 1, make)
    assert len(a) == 3 and a[0][field].shape[0] == 4
    assert all(np.array_equal(x[field], y[field]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][field], c[0][field])
    rows = np.concatenate([x[field].reshape(4, -1) for x in a])
    assert len({r.tobytes() for r in rows}) == 12  # rows all differ
    assert a[0]["valid"].all()


def test_zipf_is_skewed():
    ids = traffic.zipf_ids(np.random.default_rng(0), 32000, 3, 200000)
    assert ids.min() == 3 and ids.max() < 32000
    assert (ids == 3).mean() > 20 * (ids == 3000).mean()


def test_tokens_per_row():
    assert harness.load_task("mlm").tokens_per_row(CFG) == 2048
    assert harness.load_task("img_clf").tokens_per_row(
        {"task": "img_clf", "image_shape": [224, 224, 3]}) == 50176
