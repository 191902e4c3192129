"""Optimizer-factory semantics: gradient accumulation, clipping,
freeze masks (reference trainer.yaml:16,33 and lightning.py:151-152)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from perceiver_tpu.training.optim import create_optimizer

SGD = {"class_path": "SGD", "init_args": {"lr": 0.1}}


def _params():
    return {"w": jnp.ones((3,)), "b": jnp.zeros((2,))}


def test_accumulation_defers_and_averages():
    """accumulate_grad_batches=K: params move only once per window,
    with the mean of the K micro-grads (Lightning semantics)."""
    tx, _ = create_optimizer(SGD, accumulate_grad_batches=2)
    params = _params()
    state = tx.init(params)
    g1 = {"w": jnp.full((3,), 2.0), "b": jnp.full((2,), 4.0)}
    g2 = {"w": jnp.full((3,), 4.0), "b": jnp.full((2,), 8.0)}

    up1, state = tx.update(g1, state, params)
    mid = optax.apply_updates(params, up1)
    # first micro-step of the window: no movement
    np.testing.assert_allclose(np.asarray(mid["w"]),
                               np.asarray(params["w"]))

    up2, state = tx.update(g2, state, mid)
    out = optax.apply_updates(mid, up2)
    # window closes: SGD step with the window-mean gradient (3.0, 6.0)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(params["w"]) - 0.1 * 3.0,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.asarray(params["b"]) - 0.1 * 6.0,
                               rtol=1e-6)


def test_gradient_clip_global_norm():
    """gradient_clip_val clips by global norm before the update."""
    tx, _ = create_optimizer(SGD, gradient_clip_val=1.0)
    params = _params()
    state = tx.init(params)
    g = {"w": jnp.full((3,), 100.0), "b": jnp.zeros((2,))}
    up, _ = tx.update(g, state, params)
    moved = jax.tree_util.tree_leaves(up)
    norm = float(optax.global_norm(moved))
    # |update| = lr * clipped-norm = 0.1 * 1.0
    assert abs(norm - 0.1) < 1e-5


def test_freeze_labels_zero_frozen_updates():
    labels = {"w": "frozen", "b": "trainable"}
    tx, _ = create_optimizer(SGD, param_labels=labels)
    params = _params()
    state = tx.init(params)
    g = {"w": jnp.ones((3,)), "b": jnp.ones((2,))}
    up, _ = tx.update(g, state, params)
    np.testing.assert_allclose(np.asarray(up["w"]), 0.0)
    assert float(jnp.abs(up["b"]).sum()) > 0


def test_stray_top_level_hparam_keys_rejected():
    """--optimizer.lr=x (outside init_args) must error, not silently
    train at the default LR."""
    import pytest

    with pytest.raises(ValueError, match="init_args"):
        create_optimizer({"class_path": "AdamW", "lr": 0.1})
    with pytest.raises(ValueError, match="init_args"):
        create_optimizer(
            SGD, scheduler_init={"class_path": "OneCycleLR",
                                 "max_lr": 0.1},
            max_steps=10)


def test_typod_init_args_keys_rejected():
    """Typos INSIDE init_args (weight_decy, total_step) must error too
    — every hparam is read with .get(default), so nothing else would
    notice."""
    import pytest

    with pytest.raises(ValueError, match="weight_decy"):
        create_optimizer({"class_path": "AdamW",
                          "init_args": {"lr": 0.1, "weight_decy": 0.0}})
    with pytest.raises(ValueError, match="total_step"):
        create_optimizer(
            SGD, scheduler_init={"class_path": "OneCycleLR",
                                 "init_args": {"total_step": 5000}},
            max_steps=10)


def test_defaulted_onecycle_falls_back_without_total_steps():
    """The MLM CLI injects OneCycleLR by default (reference mlm.py:14-16
    registers it unconditionally); with no max_steps the defaulted
    schedule degrades to constant lr with a warning instead of failing
    invocations that never asked for a scheduler."""
    import warnings

    import pytest

    from perceiver_tpu.training.optim import build_schedule

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        sched = build_schedule({"class_path": "OneCycleLR"},
                               base_lr=0.002, max_steps=None,
                               defaulted=True)
    assert sched == 0.002
    assert any("constant lr" in str(x.message) for x in w)

    # explicit (non-defaulted) OneCycle without steps still fails loudly
    with pytest.raises(ValueError, match="total_steps"):
        build_schedule({"class_path": "OneCycleLR"}, base_lr=0.002,
                       max_steps=None)

    # a user-smuggled in-dict marker is rejected as an unknown key
    with pytest.raises(ValueError, match="unknown lr_scheduler"):
        build_schedule({"class_path": "OneCycleLR", "defaulted": True},
                       base_lr=0.002, max_steps=1000)

    # with steps, the defaulted schedule is a real OneCycle
    sched = build_schedule({"class_path": "OneCycleLR"},
                           base_lr=0.002, max_steps=1000,
                           defaulted=True)
    assert callable(sched)
    assert float(sched(0)) < 0.0005 < 0.002  # warmup start << max_lr


def test_mlm_cli_defaults_onecycle():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import mlm as mlm_script

    cli = mlm_script.main(args=["fit"], run=False)
    sched = cli.config.get("lr_scheduler")
    assert sched and sched["class_path"] == "OneCycleLR"
    # the marker is internal: resolved by the CLI, never in the
    # user-visible config (it would otherwise leak into the run's
    # config.yaml snapshot and become a de-facto user flag)
    assert "defaulted" not in sched
    assert cli._sched_defaulted is True

    # an explicit user scheduler clears defaultedness (fail-loudly
    # semantics for explicitly requested OneCycle are preserved)
    cli2 = mlm_script.main(
        args=["fit", "--lr_scheduler.class_path=OneCycleLR"], run=False)
    assert cli2._sched_defaulted is False

    # switching scheduler class must not inherit OneCycle-only links
    cli3 = mlm_script.main(
        args=["fit", "--lr_scheduler.class_path=CosineAnnealingLR",
              "--lr_scheduler.init_args.T_max=100"], run=False)
    ia = cli3.config["lr_scheduler"].get("init_args", {})
    assert "total_steps" not in ia and "max_lr" not in ia


def test_config_snapshot_written_before_fit(tmp_path, monkeypatch):
    """The config.yaml snapshot must exist BEFORE training runs
    (reference SaveConfigCallback timing): a preempted/killed run's
    version dir still identifies its accelerator and hparams."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import img_clf as img_script

    from perceiver_tpu.training.trainer import Trainer

    seen = {}

    def boom(self):
        seen["snapshot_exists"] = os.path.exists(
            os.path.join(self.log_dir, "config.yaml"))
        raise RuntimeError("simulated mid-fit kill")

    monkeypatch.setattr(Trainer, "fit", boom)
    cli = img_script.main(
        args=["fit", "--data=SyntheticImageDataModule",
              "--data.train_size=8", "--data.val_size=8",
              "--data.test_size=8", "--data.batch_size=4",
              "--data.image_shape=[8,8,1]", "--data.num_classes=3",
              "--trainer.fast_dev_run=true", "--trainer.accelerator=cpu",
              f"--trainer.default_root_dir={tmp_path}"],
        run=False)
    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="simulated mid-fit kill"):
        cli.run()
    assert seen.get("snapshot_exists") is True
