"""Which attention core a call site takes (``impl=None`` means "pick"),
and the trace-time tally that says so."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

import perceiver_tpu.ops.attention as attn
from perceiver_tpu.ops import mha_apply, mha_init
from perceiver_tpu.ops.policy import Policy

FUSED = ("fused", None)


def materialized(reason):
    return ("materialized", reason)


# (backend, Lq, Lk, dropout active, attn_mask, mesh devices)
CHOICES = {
    # the benchmark's call sites on the chip
    "lm_latent": (("tpu", 1024, 1024, False, False, 1), FUSED),
    "lm_enc_cross": (("tpu", 1024, 2048, False, False, 1), FUSED),
    "img_enc_cross": (("tpu", 512, 50176, False, False, 1), FUSED),
    "img_latent": (("tpu", 512, 512, False, False, 1), FUSED),
    "lm_decoder": (("tpu", 384, 1024, False, False, 1), FUSED),
    "at_the_floors": (("tpu", 128, 2048, False, False, 1), FUSED),
    "at_the_floors_square": (("tpu", 512, 512, False, False, 1), FUSED),
    # what the kernels do not cover
    "img_decoder_one_query": (("tpu", 1, 512, False, False, 1),
                              materialized("shape")),
    "text_clf_32_latents": (("tpu", 32, 512, False, False, 1),
                            materialized("shape")),
    "decoder_over_32_latents": (("tpu", 4096, 32, False, False, 1),
                                materialized("shape")),
    "few_keys": (("tpu", 4096, 256, False, False, 1),
                 materialized("shape")),
    "few_scores": (("tpu", 128, 1024, False, False, 1),
                   materialized("shape")),
    "dropout_active": (("tpu", 1024, 1024, True, False, 1),
                       materialized("dropout")),
    "attn_mask": (("tpu", 1024, 1024, False, True, 1),
                  materialized("attn_mask")),
    "dp2_tp2_mesh": (("tpu", 1024, 1024, False, False, 4),
                     materialized("mesh")),
    "cpu": (("cpu", 1024, 1024, False, False, 1),
            materialized("backend")),
    "gpu": (("gpu", 1024, 1024, False, False, 1),
            materialized("backend")),
    # the first reason in MATERIALIZED_REASONS' order wins
    "mask_before_backend": (("cpu", 8, 8, True, True, 4),
                            materialized("attn_mask")),
    "dropout_before_mesh": (("tpu", 8, 8, True, False, 4),
                            materialized("dropout")),
    "mesh_before_shape": (("tpu", 8, 8, False, False, 4),
                          materialized("mesh")),
}


@pytest.mark.parametrize("case", CHOICES)
def test_pick_attention_core(case):
    (backend, lq, lk, dropout, mask, mesh), want = CHOICES[case]
    got = attn.pick_attention_core(
        backend=backend, lq=lq, lk=lk, dropout_active=dropout,
        has_attn_mask=mask, mesh_devices=mesh)
    assert got == want
    assert got[1] is None or got[1] in attn.MATERIALIZED_REASONS


def _call(lq=512, lk=512, heads=2, dim=32, on_mesh=False, **kw):
    params = mha_init(jax.random.key(0), q_dim=dim, num_heads=heads)
    q = jax.random.normal(jax.random.key(1), (2, lq, dim))
    kv = q if lk == lq else jax.random.normal(jax.random.key(2),
                                              (2, lk, dim))
    if on_mesh:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        q = kv = jax.device_put(q, NamedSharding(mesh, P("data")))
    return mha_apply(params, q, kv, kv, num_heads=heads,
                     policy=Policy.fp32(), **kw)


# call -> the tally's one key, with the backend reading "tpu"
SITES = {
    "picked_fused": (dict(), FUSED),
    "forced_fused": (dict(impl="flash"), FUSED),
    "forced_einsum": (dict(impl="einsum"), materialized("impl")),
    "chunked": (dict(impl="chunked"), ("chunked", None)),
    "small": (dict(lq=8, lk=8), materialized("shape")),
    "few_keys": (dict(lq=1024, lk=256), materialized("shape")),
    "dropout": (dict(dropout_rate=0.1, deterministic=False,
                     rng=jax.random.key(3)), materialized("dropout")),
    "dropout_at_eval": (dict(dropout_rate=0.1, deterministic=True), FUSED),
    "attn_mask": (dict(attn_mask=jnp.zeros((512, 512), bool)),
                  materialized("attn_mask")),
    "mesh": (dict(on_mesh=True), materialized("mesh")),
}


@pytest.mark.parametrize("site", SITES)
def test_call_sites_are_tallied_by_path_and_reason(site, monkeypatch):
    """On a TPU; the kernels themselves still run interpreted here."""
    kwargs, key = SITES[site]
    monkeypatch.setattr(attn, "_backend", lambda: "tpu")
    with attn.attention_paths() as paths:
        out = _call(**kwargs)
    assert dict(paths) == {key: 1}
    if key[0] in ("fused", "chunked"):
        # the same attention, whichever core computed it
        ref = _call(**{**kwargs, "impl": "einsum"})
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_none_on_the_cpu_is_the_materialized_core():
    with attn.attention_paths() as outer, attn.attention_paths() as inner:
        _call()
        jit_once(_call)()          # traced once more, tallied once more
        jit_once(_call)()          # a cache hit traces nothing
    assert dict(inner) == dict(outer) == {materialized("backend"): 2}
    assert attn.format_attention_paths(inner) == "materialized[backend]=2"
    with attn.attention_paths() as later:
        pass
    assert not later and not attn._PATHS._open


def test_a_model_step_tallies_every_call_site(monkeypatch):
    """perceiver_lm at the benchmark's rehearsal sizes: one
    cross-attention and one body of the latent scan in the encoder's
    rematerialised layer (traced once, used by the first layer and the
    scanned ones), one cross-attention in the decoder."""
    task = _rehearsal_task()
    model = task.build()
    params = model.init(jax.random.key(0))
    batch = {"input_ids": jnp.ones((2, 64), jnp.int32),
             "pad_mask": jnp.zeros((2, 64), bool)}

    def loss(p):
        return task.loss_and_metrics(model, p, batch,
                                     rng=jax.random.key(1),
                                     deterministic=False)[0]

    for fn in (loss, jax.grad(loss)):
        # differentiation works on the traced bodies, it traces none
        with attn.attention_paths() as paths:
            jax.eval_shape(fn, params)
        assert dict(paths) == {materialized("backend"): 3}


def _rehearsal_task(**overrides):
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "perceiver_lm.json")) as f:
        config = json.load(f)
    return MaskedLanguageModelTask(
        **{**config["model"], **config["rehearsal"]["model"], **overrides})


# heads of 16 take the transposed forward layout, heads of 64 the
# standard one; both take the one backward kernel
@pytest.mark.parametrize("channels,policy,tol", [
    (32, "fp32", 2e-4), (128, "fp32", 2e-4), (128, "bf16", 5e-2)],
    ids=["d16_fp32", "d64_fp32", "d64_bf16"])
def test_model_loss_and_gradients_fused_against_einsum(channels, policy,
                                                       tol):
    """perceiver_lm at the benchmark's rehearsal sizes, every attention
    forced onto one core: the kernels inside the rematerialised layer,
    the layer ``lax.scan`` and the latent block's scan, against the
    materialised core."""
    policy = getattr(Policy, policy)()
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(rng.integers(3, 512, (2, 64)),
                                      jnp.int32),
             "pad_mask": jnp.arange(64)[None, :] >= jnp.array([[64], [40]])}

    def loss_and_grads(impl):
        task = _rehearsal_task(num_latent_channels=channels,
                               attention_impl=impl,
                               decoder_attention_impl=impl)
        assert task.remat
        model = task.build()
        params = model.init(jax.random.key(0))

        def loss(p):
            return task.loss_and_metrics(
                model, p, batch, rng=jax.random.key(1),
                deterministic=False, policy=policy)[0]

        with attn.attention_paths() as paths:
            out = jit_once(jax.value_and_grad(loss))(params)
        want = FUSED if impl == "flash" else materialized("impl")
        assert dict(paths) == {want: 3}
        return out

    (loss_f, grads_f), (loss_e, grads_e) = (loss_and_grads("flash"),
                                            loss_and_grads("einsum"))
    np.testing.assert_allclose(loss_f, loss_e, rtol=tol)
    flat_f = jax.tree_util.tree_leaves_with_path(grads_f)
    flat_e = jax.tree_util.tree_leaves(grads_e)
    assert len(flat_f) == len(flat_e) > 20
    # one scale for all: a key projection's bias has gradient zero
    # (softmax ignores a shift of the scores), so its leaf is rounding
    scale = max(float(jnp.abs(b).max()) for b in flat_e)
    for (path, a), b in zip(flat_f, flat_e):
        np.testing.assert_allclose(a, b, atol=tol * scale, rtol=tol,
                                   err_msg=jax.tree_util.keystr(path))


def test_a_mesh_keeps_the_trainers_step_on_the_materialized_core(
        tmp_path, monkeypatch):
    """The dp2 x tp2 step of ``chip_smoke.py --chips 4``: a Pallas call
    has no partitioning rule, so under a mesh every call site must
    report ``mesh`` (seen from the operands' type inside the trainer's
    own jit), not quietly take a replicated kernel."""
    from perceiver_tpu.parallel import make_mesh
    from perceiver_tpu.training import Trainer, TrainerConfig

    monkeypatch.setattr(attn, "_backend", lambda: "tpu")
    batch = {"input_ids": np.ones((4, 64), np.int32),
             "pad_mask": np.zeros((4, 64), bool),
             "valid": np.ones((4,), bool)}
    tallies = {}
    for name, mesh in (("one_chip", None),
                       ("dp2_tp2", make_mesh(4, model_parallel=2))):
        trainer = Trainer(
            _rehearsal_task(), None,
            TrainerConfig(default_root_dir=str(tmp_path / name),
                          enable_checkpointing=False),
            optimizer_init={"class_path": "AdamW",
                            "init_args": {"lr": 1e-3}}, mesh=mesh)
        state = trainer._build_state()
        trainer._make_steps()
        with attn.attention_paths() as paths:
            trainer._train_step.lower(state, trainer._shard_batch(batch))
        tallies[name] = dict(paths)
    assert tallies["dp2_tp2"] == {materialized("mesh"): 3}
    # the same step on one chip is held back by its toy shapes alone
    assert tallies["one_chip"] == {materialized("shape"): 3}
