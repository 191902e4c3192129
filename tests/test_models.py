"""Model tests: encoder/decoder/IO/MLM shapes, masking stats, recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.adapters import (
    ClassificationOutputAdapter,
    ImageInputAdapter,
    TextInputAdapter,
    TextOutputAdapter,
)
from perceiver_tpu.models import (
    PerceiverEncoder,
    PerceiverDecoder,
    PerceiverIO,
    PerceiverMLM,
    TextMasking,
)
from perceiver_tpu.models.masking import IGNORE_INDEX
from perceiver_tpu.ops import Policy

FP32 = Policy.fp32()


def make_image_io(num_layers=3):
    input_adapter = ImageInputAdapter(image_shape=(28, 28, 1),
                                      num_frequency_bands=32)
    output_adapter = ClassificationOutputAdapter(num_classes=10)
    encoder = PerceiverEncoder(
        input_adapter=input_adapter, latent_shape=(32, 128),
        num_layers=num_layers, num_self_attention_layers_per_block=3)
    decoder = PerceiverDecoder(output_adapter=output_adapter,
                               latent_shape=(32, 128),
                               num_cross_attention_heads=1)
    return PerceiverIO(encoder, decoder)


def test_perceiver_io_image_classifier_shapes():
    model = make_image_io()
    params = jit_once(model.init)(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 28, 28, 1))
    logits = model.apply(params, x, policy=FP32)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()


def test_encoder_returns_latent_and_pad_mask():
    model = make_image_io()
    params = jit_once(model.init)(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 28, 28, 1))
    latent, pad = jit_once(lambda p, x: model.encoder.apply(
        p, x, policy=FP32))(params["encoder"], x)
    assert latent.shape == (2, 32, 128)
    assert pad is None


def test_encoder_weight_shared_recurrence_changes_output():
    """num_layers=1 vs 3 must differ; layer_n params shared across
    iterations (reference model.py:162-166,185-187)."""
    m1, m3 = make_image_io(1), make_image_io(3)
    p3 = jit_once(m3.init)(jax.random.key(0))
    assert "layer_n" not in jit_once(m1.init)(jax.random.key(0))["encoder"]
    x = jax.random.normal(jax.random.key(1), (1, 28, 28, 1))
    l3, _ = jit_once(lambda p, x: m3.encoder.apply(p, x, policy=FP32))(
        p3["encoder"], x)
    # manually: one layer_1 pass only
    p1 = {k: v for k, v in p3["encoder"].items() if k != "layer_n"}
    l1, _ = jit_once(lambda p, x: m1.encoder.apply(p, x, policy=FP32))(
        p1, x)
    assert not np.allclose(np.asarray(l1), np.asarray(l3), atol=1e-4)


def test_latent_init_statistics():
    model = make_image_io()
    params = jit_once(model.init)(jax.random.key(0))
    lat = np.asarray(params["encoder"]["latent"])
    assert lat.shape == (32, 128)
    assert np.all(np.abs(lat) <= 2.0)
    assert 0.01 < lat.std() < 0.03  # N(0, 0.02)


def test_decoder_validates_latent_shape():
    model = make_image_io()
    params = jit_once(model.init)(jax.random.key(0))
    try:
        model.decoder.apply(params["decoder"], jnp.zeros((2, 16, 128)),
                            policy=FP32)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass


def test_decoder_query_chunking_is_exact():
    output_adapter = ClassificationOutputAdapter(
        num_classes=3, num_outputs=64, num_output_channels=16)
    dec_full = PerceiverDecoder(output_adapter=output_adapter,
                                latent_shape=(8, 32))
    dec_chunk = PerceiverDecoder(output_adapter=output_adapter,
                                 latent_shape=(8, 32), query_chunk_size=16)
    params = jit_once(dec_full.init)(jax.random.key(0))
    latent = jax.random.normal(jax.random.key(1), (2, 8, 32))
    y_full = dec_full.apply(params, latent, policy=FP32)
    y_chunk = dec_chunk.apply(params, latent, policy=FP32)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_chunk),
                               atol=1e-5)


def make_mlm(vocab_size=100, max_seq_len=32):
    input_adapter = TextInputAdapter(vocab_size=vocab_size,
                                     max_seq_len=max_seq_len,
                                     num_input_channels=64)
    output_adapter = TextOutputAdapter(vocab_size=vocab_size,
                                       max_seq_len=max_seq_len,
                                       num_output_channels=64)
    encoder = PerceiverEncoder(input_adapter=input_adapter,
                               latent_shape=(16, 64), num_layers=2,
                               num_self_attention_layers_per_block=2)
    decoder = PerceiverDecoder(output_adapter=output_adapter,
                               latent_shape=(16, 64))
    masking = TextMasking(vocab_size=vocab_size, unk_token_id=1,
                          mask_token_id=2, num_special_tokens=3)
    return PerceiverMLM(encoder, decoder, masking)


def test_mlm_forward_with_masking():
    model = make_mlm()
    params = jit_once(model.init)(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, 20), 3, 100)
    pad = jnp.zeros((2, 20), bool).at[:, 16:].set(True)
    logits, labels = jit_once(lambda p, x, pad, rng: model.apply(
        p, x, pad, rng=rng, policy=FP32))(params, x, pad, jax.random.key(2))
    # logits sliced to input length (reference model.py:316)
    assert logits.shape == (2, 20, 100)
    assert labels.shape == (2, 20)


def test_mlm_forward_without_masking():
    model = make_mlm()
    params = jit_once(model.init)(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, 20), 3, 100)
    logits, labels = jit_once(lambda p, x: model.apply(
        p, x, masking=False, policy=FP32))(params, x)
    assert logits.shape == (2, 20, 100)
    assert labels is None


def test_text_masking_statistics():
    """Net corruption stats: 15% selected; of those 80% MASK, 10%
    random, 10% unchanged (reference model.py:276-289)."""
    masking = TextMasking(vocab_size=1000, unk_token_id=1, mask_token_id=2,
                          num_special_tokens=3)
    x = jax.random.randint(jax.random.key(0), (400, 512), 3, 1000)
    xm, labels = masking.apply(jax.random.key(1), x)
    x, xm, labels = map(np.asarray, (x, xm, labels))

    selected = labels != IGNORE_INDEX
    sel_rate = selected.mean()
    assert 0.145 < sel_rate < 0.155

    n_sel = selected.sum()
    masked = (xm == 2) & selected
    changed_random = selected & (xm != 2) & (xm != x)
    unchanged = selected & (xm == x)
    assert abs(masked.sum() / n_sel - 0.8) < 0.01
    # "random" can coincide with the original id (~1/1000), fold into tol
    assert abs(changed_random.sum() / n_sel - 0.1) < 0.01
    assert abs(unchanged.sum() / n_sel - 0.1) < 0.01
    # labels hold original ids at selected positions
    np.testing.assert_array_equal(labels[selected], x[selected])
    # random replacements never produce special tokens
    assert (xm[changed_random] >= 3).all()


def test_text_masking_protects_pad_and_unk():
    masking = TextMasking(vocab_size=50, unk_token_id=1, mask_token_id=2,
                          num_special_tokens=3)
    x = jnp.full((8, 64), 1, dtype=jnp.int32)  # all UNK
    pad = jnp.zeros((8, 64), bool).at[:, 32:].set(True)
    xm, labels = masking.apply(jax.random.key(0), x, pad)
    np.testing.assert_array_equal(np.asarray(xm), np.asarray(x))
    assert (np.asarray(labels) == IGNORE_INDEX).all()


def test_dropout_only_active_in_training():
    model = make_image_io()
    object.__setattr__(model.encoder, "dropout", 0.5)
    params = jit_once(model.init)(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 28, 28, 1))
    y1 = model.apply(params, x, policy=FP32)
    y2 = model.apply(params, x, policy=FP32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    t1 = model.apply(params, x, rng=jax.random.key(2), deterministic=False,
                     policy=FP32)
    t2 = model.apply(params, x, rng=jax.random.key(3), deterministic=False,
                     policy=FP32)
    assert not np.allclose(np.asarray(t1), np.asarray(t2))


def test_model_under_jit():
    model = make_image_io()
    params = jit_once(model.init)(jax.random.key(0))
    fn = jit_once(lambda p, x: model.apply(p, x, policy=FP32))
    x = jax.random.normal(jax.random.key(1), (2, 28, 28, 1))
    np.testing.assert_allclose(np.asarray(fn(params, x)),
                               np.asarray(model.apply(params, x,
                                                      policy=FP32)),
                               atol=1e-5)


def test_attention_impl_parity_through_model():
    """Encoder/decoder with chunked or flash attention match einsum."""
    import dataclasses

    input_adapter = ImageInputAdapter(image_shape=(14, 14, 1),
                                      num_frequency_bands=8)
    output_adapter = ClassificationOutputAdapter(num_classes=10)
    enc = PerceiverEncoder(input_adapter=input_adapter,
                           latent_shape=(16, 32), num_layers=2,
                           num_self_attention_layers_per_block=2)
    dec = PerceiverDecoder(output_adapter=output_adapter,
                           latent_shape=(16, 32),
                           num_cross_attention_heads=1)
    model = PerceiverIO(enc, dec)
    params = jit_once(model.init)(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 14, 14, 1))
    ref = model.apply(params, x, policy=FP32)

    for impl in ("chunked", "flash"):
        m2 = PerceiverIO(
            dataclasses.replace(enc, attention_impl=impl, kv_chunk_size=64),
            dataclasses.replace(dec, attention_impl=impl, kv_chunk_size=64))
        out = m2.apply(params, x, policy=FP32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


def _output_and_gradients(model, params, x):
    """The model's output and the gradients of its mean square, as one
    jitted program."""
    def f(p):
        out = model.apply(p, x, policy=FP32)
        return (out ** 2).mean(), out

    (_, out), grads = jit_once(jax.value_and_grad(f, has_aux=True))(params)
    return out, grads


@pytest.fixture(scope="module", params=[None, "flash"],
                ids=["picked_core", "fused_core"])
def without_remat(request):
    """The model, its input, and its output and gradients as plain
    autodiff gives them."""
    import dataclasses

    model = make_image_io()
    model = PerceiverIO(
        dataclasses.replace(model.encoder, attention_impl=request.param),
        model.decoder)
    params = jit_once(model.init)(jax.random.key(0))
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 28, 28, 1)), jnp.float32)
    return (model, params, x, *_output_and_gradients(model, params, x))


@pytest.mark.parametrize("kept", range(4), ids=[
    "keeps_nothing", "keeps_attn_out", "keeps_qkv_too",
    "keeps_the_whole_list"])
def test_remat_is_numerically_transparent(kept, without_remat, monkeypatch):
    """remat=True must change memory behavior only: identical forward
    outputs and gradients (PerceiverEncoder.remat, the lever for the
    seq-2048 configs), whichever prefix of the dear values its layers
    keep (ops/remat.py) and whichever core makes them."""
    import dataclasses

    import perceiver_tpu.models.perceiver as perceiver
    from perceiver_tpu.ops.remat import REMAT_NAMES

    monkeypatch.setattr(perceiver, "choose_keeps",
                        lambda held, layer_in: REMAT_NAMES[:kept])
    model, params, x, out_a, ga = without_remat
    remat_model = PerceiverIO(
        dataclasses.replace(model.encoder, remat=True), model.decoder)

    out_b, gb = _output_and_gradients(remat_model, params, x)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-6, atol=1e-6)

    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        # transparent up to fp32 reassociation: recomputation under
        # remat re-fuses the same ops, so ~1-ulp drift on small grad
        # elements is expected, structural drift is not
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=5e-6)
