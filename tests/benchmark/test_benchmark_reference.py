"""The plain float32 reference against the program at toy width on the
CPU, both in float32, both from the benchmark's seeded weights: what the
chip run compares has been rehearsed here."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import comparisons, harness, traffic, weights  # noqa: E402
from benchmarks.reference import perceiver_io as ref  # noqa: E402

from perceiver_tpu.ops.policy import Policy  # noqa: E402

SEED = 2_500_000_123
FP32 = Policy.fp32()
token_batch = harness.load_task("mlm").make_batch
image_batch = harness.load_task("img_clf").make_batch
MLM = {"loss_sum": ref.mlm_loss_sum}


def toy(cell_name):
    cell = harness.load_cell(cell_name)
    cfg = harness.flat_config(cell.config, rehearse=True)
    cls, kwargs = harness.load_task(cfg["task"]).program_task(cfg)
    task = cls(**kwargs)
    model = task.build()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    return cfg, task, model, weights.make_weights(shapes, SEED)


@pytest.fixture(scope="module")
def lm():
    return toy("lm_train")


@pytest.fixture(scope="module")
def img():
    return toy("img_train")


def test_weights_repeat_and_differ_by_seed(lm):
    cfg, task, model, params = lm
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    again = weights.make_weights(shapes, SEED)
    other = weights.make_weights(shapes, SEED + 1)
    for a, b, c in zip(*map(jax.tree.leaves, (params, again, other))):
        assert np.array_equal(a, b)
        assert a.ndim == 0 or np.ptp(a) == 0 or not np.array_equal(a, c)
    scale = params["encoder"]["layer_1"]["cross"]["attn"]["norm_q"]["scale"]
    assert np.all(np.asarray(scale) == 1.0)


def test_lm_logits_match_the_program(lm):
    cfg, task, model, params = lm
    batch = token_batch(np.random.default_rng(1), 3, cfg)
    ids = jnp.asarray(batch["input_ids"])
    pad = jnp.arange(ids.shape[1])[None, :] >= jnp.array([64, 40, 17])[:, None]
    want = ref.mlm_logits(params, ids, pad, cfg)
    got, _ = model.apply(params, ids, pad, masking=False, policy=FP32)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_lm_masking_loss_and_gradient_match_the_program(lm):
    cfg, task, model, params = lm
    batch = token_batch(np.random.default_rng(2), 4, cfg)
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    key = ref.trainer_step_keys(1234, 2)[1]

    def program_loss(p):
        return task.loss_and_metrics(model, p, jbatch, rng=key,
                                     deterministic=False, policy=FP32)[0]

    loss, grads = jax.value_and_grad(program_loss)(params)
    masked, labels = ref.mlm_mask(key, jbatch["input_ids"],
                                  jbatch["pad_mask"], cfg)
    x_masked, x_labels = model.masking.apply(
        jax.random.split(key, 3)[0], jbatch["input_ids"], jbatch["pad_mask"])
    assert np.array_equal(masked, x_masked)
    assert np.array_equal(labels, x_labels)
    assert 0.05 < float((labels != ref.IGNORE).mean()) < 0.3
    rbatch = {"masked_ids": masked, "pad_mask": jbatch["pad_mask"],
              "labels": labels}
    rloss, rgrads = ref.loss_and_grads(
        params, rbatch, cfg, loss_sum=ref.mlm_loss_sum, block=3)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-4)
    assert comparisons.worst_leaf_gap(
        comparisons.leaf_norms(grads),
        comparisons.leaf_norms(rgrads)) < 2e-3


def test_trainer_step_keys_follow_the_trainer():
    rng = jax.random.split(jax.random.key(77))[1]
    rng, k1 = jax.random.split(rng)
    _, k2 = jax.random.split(rng)
    got = ref.trainer_step_keys(77, 2)
    assert np.array_equal(jax.random.key_data(got[0]), jax.random.key_data(k1))
    assert np.array_equal(jax.random.key_data(got[1]), jax.random.key_data(k2))


def test_image_logits_loss_and_gradient_match_the_program(img):
    cfg, task, model, params = img
    batch = image_batch(np.random.default_rng(3), 4, cfg)
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    want = ref.image_logits(params, jbatch["image"], cfg)
    got = model.apply(params, jbatch["image"], policy=FP32)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def program_loss(p):
        return task.loss_and_metrics(model, p, jbatch, policy=FP32)[0]

    loss, grads = jax.value_and_grad(program_loss)(params)
    rloss, rgrads = ref.loss_and_grads(
        params, {"image": jbatch["image"], "label": jbatch["label"]}, cfg,
        loss_sum=ref.image_loss_sum, block=2)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-4)
    assert comparisons.worst_leaf_gap(
        comparisons.leaf_norms(grads),
        comparisons.leaf_norms(rgrads)) < 2e-3


def test_fourier_encoding_matches_the_program(img):
    from perceiver_tpu.ops.fourier import fourier_position_encodings

    np.testing.assert_allclose(
        ref.fourier_encoding((8, 6), 5),
        fourier_position_encodings((8, 6), 5), rtol=1e-6, atol=1e-6)


def test_adamw_follows_optax(lm):
    import optax

    cfg, task, model, params = lm
    tx = optax.adamw(1e-3, weight_decay=0.01)
    state = tx.init(params)
    batch = token_batch(np.random.default_rng(4), 2, cfg)
    keys = ref.trainer_step_keys(5, 2)
    rbatches, p = [], params
    for key in keys:
        ids, pad = jnp.asarray(batch["input_ids"]), jnp.asarray(batch["pad_mask"])
        masked, labels = ref.mlm_mask(key, ids, pad, cfg)
        rbatches.append({"masked_ids": masked, "pad_mask": pad,
                         "labels": labels})
        _, g = ref.loss_and_grads(p, rbatches[-1], cfg, **MLM)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
    out = ref.train_steps(params, rbatches, cfg, **MLM, lr=1e-3,
                          weight_decay=0.01)
    moved = comparisons.leaf_norms(jax.tree.map(jnp.subtract, p, params))
    live = comparisons.live_leaves(out["grad_norms"])
    assert comparisons.worst_leaf_gap(moved[live],
                                      out["update_norms"][live]) < 1e-3


def test_decode_through_the_paged_cache_matches_the_full_forward(lm):
    """Prefill in chunks, then three tokens, through the engine's paged
    cache (float32 policy, the jax gather path): each served token is
    the reference's first choice and its logit gap is nil."""
    from perceiver_tpu.serving.decode import DecodeEngine, DecodeGeometry

    cfg, task, model, params = lm
    engine = DecodeEngine(
        task, params, policy=FP32, attn_impl="reference", auto_step=False,
        geometry=DecodeGeometry(max_streams=2, page_size=4, max_seq_len=64,
                                num_pages=33, max_chunk=8))
    prompt = traffic.zipf_ids(np.random.default_rng(5), cfg["vocab_size"],
                              3, 19)
    handle = engine.submit(prompt, max_new_tokens=3)
    engine.run_until_idle()
    tokens = handle.result(5.0).tokens
    engine.close()
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    ids = np.zeros((3, 64), np.int32)
    n = np.array([19, 20, 21], np.int32)
    for j, pos in enumerate(n):
        ids[j, :pos] = seq[:pos]
    logits = np.asarray(ref.next_token_logits(
        params, jnp.asarray(ids), jnp.asarray(n), cfg))
    assert list(logits.argmax(axis=1)) == list(tokens)
    assert comparisons.widest_logit_gap(logits, tokens) == 0.0


def test_lower_precisions_move_the_logits_in_order(lm):
    cfg, task, model, params = lm
    ids = jnp.asarray(token_batch(
        np.random.default_rng(6), 2, cfg)["input_ids"])
    pad = jnp.zeros(ids.shape, bool)
    exact = ref.mlm_logits(params, ids, pad, cfg, "f32")
    err = {p: float(jnp.abs(ref.mlm_logits(params, ids, pad, cfg, p)
                            - exact).max()) for p in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"]
    assert err["fp8"] > 4 * err["bf16"]
    with pytest.raises(ValueError):
        ref.matmul("ij,jk->ik", exact[0], exact[0].T, "int4")


def test_comparison_arithmetic():
    ref_norms = np.array([1.0, 2.0, 1e-9, 4.0])
    assert comparisons.worst_leaf_gap(ref_norms, ref_norms) == 0.0
    # the all-but-zero leaf is measured against the median leaf (1.5)
    assert comparisons.worst_leaf_gap([1.0, 2.0, 0.15, 4.0],
                                      ref_norms) == pytest.approx(0.1)
    assert comparisons.worst_leaf_gap([1.0, 2.2, 1e-9, 4.0],
                                      ref_norms) == pytest.approx(0.1)
    # one leaf of four out by 0.1: the root mean square weighs it by half
    assert comparisons.rms_leaf_gap([1.0, 2.2, 1e-9, 4.0],
                                    ref_norms) == pytest.approx(0.05)
    assert comparisons.rms_leaf_gap(ref_norms, ref_norms) == 0.0
    assert list(comparisons.live_leaves(ref_norms)) == [True, True, False, True]
    logits = np.array([[0.0, 3.0, 1.0], [5.0, 4.5, 0.0]])
    assert comparisons.widest_logit_gap(logits, [1, 1]) == pytest.approx(0.5)
    assert comparisons.widest_logit_gap(logits, [1, 0]) == 0.0
