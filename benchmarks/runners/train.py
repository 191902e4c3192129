"""Train runner: the program's ``Trainer.fit()`` on seeded batches, timed
over a window that the benchmark's data module opens and closes.

The path is the normal one (``scripts/mlm.py``, ``scripts/img_clf.py``):
a task from ``perceiver_tpu.tasks``, a data module, ``TrainerConfig``,
``Trainer(...).fit()``. ``fit()`` is bounded by epochs, so the data
module makes them:

* epoch 0   one batch: the step compiles and takes the first update;
* epoch 1   two batches: steps two and three;
* epoch 2   ``warmup_steps`` batches;
* epoch 3   the window: batches until ``--seconds`` have passed.

After each epoch the trainer runs its (empty) validation and calls the
task's ``on_validation_epoch_end(trainer, state)`` with the live state.
The benchmark's task is the program's with that hook and the
``restore_pretrained`` hook overridden: the first hands the trainer the
benchmark's seeded weights, the second reads what the comparison needs
from the one object that is then timed (the first gradient's norms
from AdamW's first moment after one step, the parameters' change after
three), waits for the device, and opens and closes the window. So the
window opens on a ready state after the warm-up epoch and closes when
the last step's state is ready: every step and all the time between is
counted, input pipeline, logging and the optimizer's update included.

The float32 reference then follows the first three steps from the same
weights and batches, after the trainer's state is freed.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks import comparisons, traffic, weights
from benchmarks.harness import (
    BenchmarkError,
    Context,
    Outcome,
    memory_peak_bytes,
    say,
)

CHECK_EPOCHS = (1, 2)   # batches in epochs 0 and 1: steps 1, 2-3
WARMUP_EPOCH, WINDOW_EPOCH = 2, 3


class Loader:
    """The train loader the trainer pulls from: what each epoch yields,
    and the clock at every pull of the window."""

    def __init__(self, pool: List[dict], warmup_steps: int, seconds: float):
        self.pool, self.warmup_steps, self.seconds = \
            pool, warmup_steps, seconds
        self.epoch = 0
        self.t_open = None
        self.window_pulls: List[float] = []

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        return len(self.pool)

    def _batch(self, i: int) -> dict:
        return self.pool[i % len(self.pool)]

    def __iter__(self):
        if self.epoch < len(CHECK_EPOCHS):
            first = sum(CHECK_EPOCHS[:self.epoch])
            for i in range(first, first + CHECK_EPOCHS[self.epoch]):
                yield self._batch(i)
        elif self.epoch == WARMUP_EPOCH:
            for i in range(self.warmup_steps):
                yield self._batch(sum(CHECK_EPOCHS) + i)
        elif self.epoch == WINDOW_EPOCH:
            if self.window_pulls:
                return  # a restarted producer: the window is not fed twice
            i = sum(CHECK_EPOCHS) + self.warmup_steps
            while time.perf_counter() - self.t_open < self.seconds:
                self.window_pulls.append(time.perf_counter())
                yield self._batch(i)
                i += 1


class DataModule:
    def __init__(self, loader: Loader):
        self.loader = loader

    def prepare_data(self) -> None:
        pass

    def setup(self, stage=None) -> None:
        pass

    def train_dataloader(self) -> Loader:
        return self.loader

    def val_dataloader(self) -> list:
        return []   # no validation pass: nothing to evaluate, no program

    test_dataloader = val_dataloader


class Probe:
    """What the benchmark's task carries: the seeded weights on the way
    in, and what the hooks read on the way."""

    def __init__(self, ctx: Context, shapes, loader: Loader, b1: float):
        self.ctx, self.shapes, self.loader, self.b1 = ctx, shapes, loader, b1
        self.grad_norms = self.update_norms = None
        self.t_open = self.t_close = None
        self.compiles_in_window: List[float] = []
        self._listener = None

    def weights(self):
        return weights.make_weights(self.shapes, self.ctx.seed)

    def after_epoch(self, trainer, state) -> None:
        import jax

        from perceiver_tpu import cache

        epoch = trainer.current_epoch
        jax.block_until_ready(state)
        if epoch <= WARMUP_EPOCH:
            self.ctx.mark(f"epoch {epoch} done (step {trainer.global_step})")
        if epoch == 0:
            moments = [x for x in jax.tree.leaves(
                state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(x, "mu")]
            if len(moments) != 1:
                raise BenchmarkError(
                    "expected one Adam moment pair in the optimizer state, "
                    f"found {len(moments)}")
            self.grad_norms = comparisons.leaf_norms(
                moments[0].mu) / (1.0 - self.b1)
        elif epoch == 1:
            self.update_norms = comparisons.leaf_norms_of_difference(
                state.params, self.weights)
        elif epoch == WARMUP_EPOCH:
            self._listener = cache.register_compile_listener(
                self.compiles_in_window.append)
            self.ctx.tracer.start()
            self.t_open = self.loader.t_open = time.perf_counter()
        elif epoch == WINDOW_EPOCH:
            self.t_close = time.perf_counter()
            cache.unregister_compile_listener(self._listener)


def bench_task(cls, kwargs: dict, probe: Probe):
    """The program's task with the two hooks the trainer offers."""

    def restore_pretrained(self, params):
        del params  # the program's own init: not used for values
        return probe.weights()

    def on_validation_epoch_end(self, trainer, state):
        probe.after_epoch(trainer, state)

    bench_cls = type(f"Bench{cls.__name__}", (cls,), {
        "restore_pretrained": restore_pretrained,
        "on_validation_epoch_end": on_validation_epoch_end})
    return bench_cls(**kwargs)


def read_telemetry(directory: str) -> Dict[int, float]:
    losses = {}
    with open(os.path.join(directory, "telemetry.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "loss" in rec and "step" in rec:
                losses[int(rec["step"])] = float(rec["loss"])
    return losses


def run_reference(ctx: Context, shapes, pool, trainer_seed: int,
                  prec: str = "f32") -> dict:
    from benchmarks.reference import perceiver_io as ref

    dep = ctx.deployment
    steps = sum(CHECK_EPOCHS)
    return ref.train_steps(
        weights.make_weights(shapes, ctx.seed),
        ctx.task.reference_batches(pool, ctx.cfg, trainer_seed, steps),
        ctx.cfg, loss_sum=ctx.task.loss_sum, lr=dep["optimizer"]["lr"],
        weight_decay=dep["optimizer"]["weight_decay"], prec=prec,
        block=ctx.mix["reference_block_rows"])


def leaf_names(tree) -> List[str]:
    import jax

    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def compare(ctx: Context, program: dict, reference: dict) -> list:
    checks = [ctx.check(f"loss_gap_step{i + 1}",
                        abs(p - r) / max(abs(r), 1e-12))
              for i, (p, r) in enumerate(zip(program["losses"],
                                             reference["losses"]))]
    checks.append(ctx.check("grad_norm_gap", comparisons.worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])))
    checks.append(ctx.check("grad_norm_gap_rms", comparisons.rms_leaf_gap(
        program["grad_norms"], reference["grad_norms"])))
    # Adam divides a gradient by its own size: where the gradient is
    # all but zero by the mathematics (a key projection's bias shifts
    # every score of a softmax alike), the update is rounding noise
    # scaled up to the learning rate, in the program and the reference
    # both. Those leaves are left out of the update's comparison.
    live = comparisons.live_leaves(reference["grad_norms"])
    say(f"update comparison over {int(live.sum())} of {len(live)} leaves")
    checks.append(ctx.check("update_norm_gap", comparisons.worst_leaf_gap(
        np.asarray(program["update_norms"])[live],
        np.asarray(reference["update_norms"])[live])))
    return checks


def run(ctx: Context) -> Outcome:
    import jax

    from perceiver_tpu.training import Trainer, TrainerConfig

    mix, dep, cfg = ctx.mix, ctx.deployment, ctx.cfg
    cls, kwargs = ctx.task.program_task(cfg)
    ctx.mark("imports done")
    with ctx.spans.span("make_batches"):
        pool = traffic.train_batches(mix, cfg, ctx.seed,
                                     ctx.task.make_batch)
    ctx.mark(f"{len(pool)} batches made")
    shapes = jax.eval_shape(cls(**kwargs).build().init, jax.random.key(0))
    loader = Loader(pool, mix["warmup_steps"], ctx.seconds)
    opt = dep["optimizer"]
    probe = Probe(ctx, shapes, loader, b1=opt.get("betas", (0.9, 0.999))[0])
    task = bench_task(cls, kwargs, probe)
    trainer_seed = weights.seed31(ctx.seed)
    tele = os.path.join(ctx.workdir, "telemetry")
    tcfg = TrainerConfig(
        max_epochs=WINDOW_EPOCH + 1, precision=dep["precision"],
        log_every_n_steps=1, num_sanity_val_steps=0,
        enable_checkpointing=False,
        default_root_dir=os.path.join(ctx.workdir, "logs"),
        experiment=ctx.cell.name, telemetry_dir=tele,
        # the AOT first dispatch, which the chip takes anyway
        exec_cache_dir=os.path.join(ctx.workdir, "exec_cache"),
        seed=trainer_seed)
    trainer = Trainer(task, DataModule(loader), tcfg, optimizer_init={
        "class_path": opt["class"],
        "init_args": {"lr": opt["lr"],
                      "weight_decay": opt["weight_decay"]}})
    ctx.mark("trainer built")
    with ctx.spans.span("fit"):
        state = trainer.fit()
    ctx.tracer.stop()
    if probe.t_close is None:
        raise BenchmarkError("fit() returned before the window closed")
    losses = read_telemetry(tele)
    peak = None if ctx.rehearse else memory_peak_bytes()
    del state, trainer

    check_steps = sum(CHECK_EPOCHS)
    first = check_steps + mix["warmup_steps"] + 1
    steps = len(loader.window_pulls)
    window_losses = [losses.get(s, float("nan"))
                     for s in range(first, first + steps)]
    nonfinite = int(np.sum(~np.isfinite(window_losses)))
    elapsed = probe.t_close - probe.t_open
    rows = mix["batch_rows"]
    tokens = steps * rows * ctx.task.tokens_per_row(cfg)
    say(f"window: {steps} steps of {rows} rows in {elapsed:.3f} s; "
        f"loss {window_losses[0]:.4f} -> {window_losses[-1]:.4f}; "
        f"{len(probe.compiles_in_window)} compiles inside")

    program = {"losses": [losses[s] for s in range(1, check_steps + 1)],
               "grad_norms": probe.grad_norms,
               "update_norms": probe.update_norms}
    t0 = time.perf_counter()
    with ctx.spans.span("reference"):
        reference = run_reference(ctx, shapes, pool, trainer_seed)
    say(f"reference: {check_steps} float32 steps in "
        f"{time.perf_counter() - t0:.1f} s; losses program "
        f"{program['losses']} reference {reference['losses']}")
    checks = compare(ctx, program, reference)
    control_checks = raw = None
    if ctx.control:   # benchmarks/control.py: the readings limits are set from
        raw = {"leaves": leaf_names(shapes), "program": program,
               "reference": reference}
        if ctx.control != "none":
            raw["control"] = run_reference(
                ctx, shapes, pool, trainer_seed, prec=ctx.control)
            control_checks = compare(ctx, raw["control"], reference)
    checks.append(ctx.check("nonfinite_losses", nonfinite))
    checks.append(ctx.check("window_compiles",
                            len(probe.compiles_in_window)))
    return Outcome(
        t_open=probe.t_open,
        metrics={"train_tokens_per_s": tokens / elapsed},
        attempted=steps,
        failed=nonfinite + len(probe.compiles_in_window),
        checks=checks,
        data={"pulls": loader.window_pulls, "steps": steps, "rows": rows,
              "elapsed_s": elapsed, "window_losses": window_losses,
              "telemetry_steps": sorted(losses),
              "control_checks": control_checks, "raw": raw},
        memory_peak_bytes=peak)
