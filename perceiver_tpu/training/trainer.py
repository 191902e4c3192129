"""Training orchestration: the Lightning-Trainer-equivalent loop.

Preserves the operative flag surface of ``scripts/trainer.yaml``
(SURVEY §2.3): max_epochs/max_steps, fast_dev_run, overfit_batches,
limit_{train,val,test}_batches, gradient_clip_val,
accumulate_grad_batches, log_every_n_steps, num_sanity_val_steps,
check_val_every_n_epoch, default_root_dir, enable_checkpointing,
resume_from_checkpoint, detect_anomaly, profiler, precision — each
implemented with the JAX-native mechanism (debug_nans, jax.profiler,
dtype policy) rather than Lightning plumbing.

The step path is one jitted, donated function over the whole
``TrainState`` pytree; when a ``jax.sharding.Mesh`` is supplied,
params/optimizer moments are laid out per ``parallel.sharding`` rules
(replicated on a data-only mesh, tensor-sharded when the mesh has a
``model`` axis) and batches are sharded over ``data`` — plus the
``seq`` axis for token fields the task nominates — so the same
trainer drives one chip or a dp×sp×tp pod slice (GSPMD inserts the
gradient all-reduce — the NCCL-DDP equivalent, SURVEY §2.5).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from perceiver_tpu.obs import events as events_mod
from perceiver_tpu.obs import trace as trace_mod
from perceiver_tpu.obs.process import GcSpans
from perceiver_tpu.obs.trace import device_scope, span
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.resilience import faults
from perceiver_tpu.resilience import guard as guard_mod
from perceiver_tpu.training.checkpoint import CheckpointHook
from perceiver_tpu.training.optim import create_optimizer
from perceiver_tpu.training.pace import StepPace
from perceiver_tpu.training.state import TrainState
from perceiver_tpu.utils.tb import SummaryWriter
from perceiver_tpu.utils.timing import fence

_UNLIMITED_EPOCHS = 1000  # Lightning's default cap for max_epochs=-1


@dataclasses.dataclass
class TrainerConfig:
    max_epochs: int = -1
    max_steps: int = -1
    precision: Any = "bf16"  # 32 | "bf16" (trainer.yaml:49 default 32)
    gradient_clip_val: float = 0.0
    accumulate_grad_batches: int = 1
    log_every_n_steps: int = 50
    num_sanity_val_steps: int = 2
    check_val_every_n_epoch: int = 1
    fast_dev_run: bool = False
    overfit_batches: int = 0
    limit_train_batches: Optional[int] = None
    limit_val_batches: Optional[int] = None
    limit_test_batches: Optional[int] = None
    default_root_dir: str = "logs"
    experiment: str = "default"
    enable_checkpointing: bool = True
    checkpoint_monitor: str = "val_loss"
    save_top_k: int = 1
    resume_from_checkpoint: Optional[str] = None
    detect_anomaly: bool = False
    # stop training when the loss goes non-finite (trainer.yaml:71).
    # Implemented as the resilience guard's "halt" policy: per-step
    # losses are threaded out of every dispatch, so a NaN inside a
    # steps_per_execution block is attributed to its exact step
    # instead of the block boundary (docs/RESILIENCE.md).
    terminate_on_nan: bool = False
    # non-finite step guard policy: "off" | "halt" | "skip".
    # "halt" = terminate_on_nan. "skip" withholds the parameter update
    # of isolated bad steps (guard_skipped_steps metric); on
    # nonfinite_streak consecutive bad steps the trainer restores the
    # last-good anchor checkpoint (<log_dir>/checkpoints-guard,
    # sha256-verified) and rewinds the data iterator deterministically,
    # at most nonfinite_max_rewinds times before halting. Any armed
    # policy syncs per-step losses each dispatch; "off" keeps the
    # pristine step functions and graphs byte-identical.
    nonfinite_policy: str = "off"
    nonfinite_streak: int = 3
    nonfinite_max_rewinds: int = 2
    # extra last-good anchor saves every N steps under the "skip"
    # policy (0 = anchors at fit start and epoch starts only)
    guard_anchor_every_n_steps: int = 0
    # where the guard's anchor checkpoints live (default
    # <log_dir>/checkpoints-guard). A multi-host group supervisor
    # points every generation of a re-formed group at ONE shared
    # directory so the respawned run finds the previous run's newest
    # verified anchor (distributed/worker.py)
    guard_anchor_dir: Optional[str] = None
    # position the data stream at the restored step after a resume:
    # the loader is epoch-seeded, so epoch = step // len(loader) and
    # replaying step % len(loader) batches reproduces the exact
    # position the checkpoint was taken at — the resumed loss curve is
    # bitwise-identical to an uninterrupted run (the crash-of-one-host
    # recovery contract, chaos scenario dist_kill_train_host). Off by
    # default: single-host resumes historically continue at the NEXT
    # epoch boundary
    resume_step_replay: bool = False
    # supervised input pipeline: transient loader failures restart the
    # prefetch producer with exponential backoff, bounded by this
    # poison-pill budget (0 = die on first error); persistent failures
    # re-raise once the budget is spent
    loader_restart_budget: int = 3
    loader_backoff_s: float = 0.05
    # deterministic fault-injection plan armed at fit() — the config
    # twin of the PERCEIVER_FAULTS env var (resilience/faults.py);
    # None/empty = unarmed (zero overhead)
    fault_plan: Optional[str] = None
    profiler: Optional[str] = None
    # on-demand profiling without a restart: arm SIGUSR1 to toggle a
    # jax.profiler capture into this directory (obs/telemetry.py;
    # docs/OBSERVABILITY.md). None = signal profiler not installed.
    profile_dir: Optional[str] = None
    # per-step JSONL telemetry + training_* metrics registry
    # (obs/telemetry.py). Rides the crossed_log host sync — zero extra
    # device syncs. None = telemetry off.
    telemetry_dir: Optional[str] = None
    # overlap host batch assembly with device compute: depth of the
    # background prefetch queue (the torch-DataLoader-workers analogue,
    # reference data/imdb.py:112-126; 0 disables)
    prefetch_batches: int = 2
    # optimizer steps per device dispatch: K batches are stacked on the
    # host and scanned on-device (lax.scan), amortizing host→device
    # dispatch latency over K steps — the dominant overhead for small
    # per-step compute on TPU. 1 = classic one-dispatch-per-step.
    # Logging/val/preemption/max_steps all operate at dispatch
    # boundaries; a trailing group smaller than K runs step-by-step.
    steps_per_execution: int = 1
    # save a full-state checkpoint and stop cleanly on SIGTERM — TPU
    # preemption notice. Beyond the reference's manual
    # restart-from-checkpoint story (SURVEY §5 failure detection): the
    # preempt save lands in <log_dir>/checkpoints-preempt and is picked
    # up by resume_from_checkpoint like any other.
    preempt_checkpoint: bool = True
    seed: int = 42
    # accelerator selects the JAX platform (see apply_accelerator;
    # raises at Trainer construction if the selection cannot take).
    # devices=N limits the CLI-built mesh to the first N devices
    # (README.md:43 semantics; "auto"/-1 = all). num_nodes is
    # informational — multi-host topology comes from jax.distributed.
    accelerator: str = "auto"
    devices: Any = "auto"
    num_nodes: int = 1
    # mesh shape knobs (CLI route to make_mesh): the data axis gets
    # all remaining devices. model_parallel opens the tensor-parallel
    # axis (v5p-16 config, BASELINE configs[4]); seq_parallel opens
    # the 'seq' axis for sequence-sharded tokens (pjit GSPMD form, or
    # the shard_map impls via --model.attention_impl)
    model_parallel: int = 1
    seq_parallel: int = 1
    # persistent compile cache directory (perceiver_tpu/cache): the
    # first dispatch deserializes the step executable instead of
    # paying the multi-second XLA compile when a prior run at the same
    # shapes populated it. None falls back to the PERCEIVER_EXEC_CACHE
    # env var; unset ⇒ caching off.
    exec_cache_dir: Optional[str] = None

    def policy(self) -> Policy:
        if str(self.precision) in ("32", "fp32", "32-true"):
            return Policy.fp32()
        return Policy.bf16()


def apply_accelerator(accelerator: str) -> None:
    """``--trainer.accelerator`` (reference README.md:42-43). "auto"
    keeps whatever platform JAX picked; any other value ("tpu", "cpu",
    "gpu") must be the platform the process ends up on. Must run before
    any device use in this process: the look for devices here is then
    the one that starts the accelerator's runtime, and has a span
    (``proc/backend_init``; a caller that looked first keeps those
    seconds under no span of the program)."""
    acc = str(accelerator).lower()
    if acc not in ("auto", "tpu"):
        jax.config.update("jax_platforms", acc)
    start = trace_mod._now()
    got = jax.devices()[0].platform
    end = trace_mod._now()
    if end - start > 1e-3:    # the runtime started here, not before
        trace_mod.timeline().record("proc/backend_init", start=start,
                                    end=end, platform=got)
    # A late update (after the backend initialized) silently no-ops, so
    # verify the selection actually took rather than trusting the call.
    if acc != "auto" and got != acc:
        raise RuntimeError(
            f"--trainer.accelerator={acc} had no effect (running on "
            f"{got!r}); select the accelerator before any other jax "
            "device use in this process")


def _version_dir(root: str, experiment: str) -> str:
    """logs/{experiment}/version_N — the reference's TB layout.

    Multi-host: every process must agree on N (the checkpoint hook's
    orbax saves are collectives into this directory), and concurrent
    listdir races would let hosts pick different numbers — process 0
    decides, everyone else adopts its choice."""
    base = os.path.join(root, experiment)
    os.makedirs(base, exist_ok=True)
    versions = [int(d.split("_")[1]) for d in os.listdir(base)
                if d.startswith("version_") and d.split("_")[1].isdigit()]
    n = max(versions, default=-1) + 1
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        n = int(multihost_utils.broadcast_one_to_all(np.int32(n)))
    return os.path.join(base, f"version_{n}")


class _NullWriter:
    """Rank-nonzero stand-in for SummaryWriter (one host writes TB
    events; duplicated writers would interleave corrupt event files)."""

    def add_scalar(self, *a, **k):
        pass

    def add_text(self, *a, **k):
        pass

    def flush(self):
        pass

    def close(self):
        pass


class Trainer:
    def __init__(self, task, datamodule, config: TrainerConfig = None,
                 optimizer_init: Optional[dict] = None,
                 scheduler_init: Optional[dict] = None,
                 scheduler_defaulted: bool = False,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.task = task
        self.datamodule = datamodule
        self.config = config or TrainerConfig()
        self.optimizer_init = optimizer_init
        self.scheduler_init = scheduler_init
        # True when the scheduler came from a script's defaults, not
        # the user (CLI-resolved): an unresolvable schedule then
        # degrades to constant lr instead of failing the run
        self.scheduler_defaulted = scheduler_defaulted
        self.mesh = mesh
        # schedule restart offset for the partial-resume fallback (the
        # fresh optimizer's schedule count restarts at 0 while
        # global_step resumes): logged lr must match the applied lr
        self._lr_step_offset = 0

        # effective non-finite guard policy: terminate_on_nan is the
        # legacy spelling of "halt" (one detection path for both)
        policy = str(self.config.nonfinite_policy or guard_mod.OFF).lower()
        if policy not in guard_mod.POLICIES:
            raise ValueError(
                f"trainer.nonfinite_policy={policy!r} not in "
                f"{guard_mod.POLICIES}")
        if policy == guard_mod.OFF and self.config.terminate_on_nan:
            policy = guard_mod.HALT
        self._guard_policy = policy
        self._guard: Optional[guard_mod.StepGuard] = None
        self._guard_ckpt: Optional[CheckpointHook] = None
        self._anchor_pos = (0, 0)   # (epoch, batches consumed) at anchor
        self._anchor_step = -1

        apply_accelerator(self.config.accelerator)

        with span("train/construct"):
            # the mesh reaches the model builder so tasks can wire the
            # shard_map sequence-parallel attention impls to its axes
            self.model = task.build(mesh=mesh)
            self.log_dir = _version_dir(self.config.default_root_dir,
                                        self.config.experiment)
        self.policy = self.config.policy()
        self.global_step = 0
        self.current_epoch = 0
        self.writer: Optional[SummaryWriter] = None
        self._ckpt: Optional[CheckpointHook] = None
        self._train_step = None
        self._train_step_multi = None
        # the state's shardings under a mesh (set by _build_state): the
        # train steps pin their state output to them
        self._state_shardings = None
        self._single_step_ran = False
        self._eval_step = None
        self._preempted = False
        # per-step telemetry sink (obs/telemetry.py), built in _fit()
        # when cfg.telemetry_dir is set
        self.telemetry = None
        # persistent compile cache for the AOT first-dispatch path
        # (config dir wins over the PERCEIVER_EXEC_CACHE env default)
        from perceiver_tpu.cache import default_cache
        self._exec_cache = default_cache(self.config.exec_cache_dir)
        # set by the first _load_step: that dispatch pays the compile
        self._step_loaded = False

    # --- setup ---------------------------------------------------------------

    def _hparams(self) -> dict:
        return {
            "task": dataclasses.asdict(self.task),
            "trainer": dataclasses.asdict(self.config),
            "optimizer_init": self.optimizer_init,
            "scheduler_init": self.scheduler_init,
        }

    def _build_state(self) -> TrainState:
        cfg = self.config
        rng = jax.random.key(cfg.seed)
        init_rng, state_rng = jax.random.split(rng)
        with span("train/model_init"):
            params = self.model.init(init_rng)
        if hasattr(self.task, "restore_pretrained"):
            with span("train/restore"):
                params = self.task.restore_pretrained(params)

        labels = None
        if hasattr(self.task, "frozen_param_labels"):
            labels = self.task.frozen_param_labels(params)
        self.tx, self.lr_fn = create_optimizer(
            self.optimizer_init, self.scheduler_init,
            max_steps=cfg.max_steps if cfg.max_steps > 0 else None,
            gradient_clip_val=cfg.gradient_clip_val,
            accumulate_grad_batches=cfg.accumulate_grad_batches,
            param_labels=labels,
            scheduler_defaulted=self.scheduler_defaulted)
        opt_state = self.tx.init(params)
        state = TrainState.create(params, opt_state, state_rng)

        if self.mesh is not None:
            # tensor-parallel meshes shard the weight/moment pytrees
            # per parallel.sharding rules; without a model axis this
            # reduces to full replication (P() everywhere)
            from perceiver_tpu.parallel.sharding import param_sharding
            shardings = param_sharding(state, self.mesh)
            self._state_shardings = shardings
            if jax.process_count() > 1:
                # device_put cannot create cross-process global arrays;
                # every host computed identical full values (same seed),
                # so each host contributes its addressable shards of
                # the full array it already holds
                def to_global(x, s):
                    if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
                        data = np.asarray(jax.random.key_data(x))
                        g = jax.make_array_from_process_local_data(
                            jax.sharding.NamedSharding(
                                self.mesh, jax.sharding.PartitionSpec()),
                            data, data.shape)
                        return jax.random.wrap_key_data(g)
                    arr = np.asarray(x)
                    return jax.make_array_from_process_local_data(
                        s, arr, arr.shape)

                state = jax.tree.map(to_global, state, shardings)
            else:
                state = jax.device_put(state, shardings)
        return state

    def _shard_batch(self, batch: Dict[str, np.ndarray], *,
                     stacked: bool = False):
        if self.mesh is None:
            return batch

        from perceiver_tpu.parallel.sharding import batch_sharding

        def sharding_for(name: str, arr) -> jax.sharding.NamedSharding:
            ndim = arr.ndim - (1 if stacked else 0)
            extra = tuple(self.task.batch_partition(
                name, ndim, self.mesh) or ())
            if stacked:
                spec = jax.sharding.PartitionSpec(None, "data", *extra)
                return jax.sharding.NamedSharding(self.mesh, spec)
            return batch_sharding(self.mesh, extra)

        if jax.process_count() > 1:
            # multi-host: each process contributes its per-host shard
            # (the loaders are process-sharded in _fit); JAX assembles
            # the global array without any cross-host data movement
            return {k: jax.make_array_from_process_local_data(
                        sharding_for(k, v), v)
                    for k, v in batch.items()}
        return {k: jax.device_put(v, sharding_for(k, v))
                for k, v in batch.items()}

    def _make_steps(self):
        # the steps close over these and not over the trainer: a
        # trainer its own jitted step refers back to is freed, with its
        # state on the device, only when the cycle collector next runs
        task, model, policy, tx = self.task, self.model, self.policy, self.tx

        def train_step(state: TrainState, batch):
            rng, step_rng = jax.random.split(state.rng)

            def loss_fn(params):
                return task.loss_and_metrics(
                    model, params, batch, rng=step_rng,
                    deterministic=False, policy=policy)

            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (_, metrics), grads = grad_fn(state.params)
            with device_scope("optimizer"):
                updates, opt_state = tx.update(
                    grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
            new_state = TrainState(params=params, opt_state=opt_state,
                                   rng=rng, step=state.step + 1)
            return new_state, metrics

        def eval_step(state: TrainState, batch, rng):
            # deterministic=True switches dropout off; the rng still
            # drives stochastic model inputs (MLM masking) and is folded
            # per batch index by _run_eval so every eval batch gets an
            # independent mask layout
            _, metrics = task.loss_and_metrics(
                model, state.params, batch, rng=rng, deterministic=True,
                policy=policy)
            # weighted by valid count so padded final batches are exact
            n = batch["valid"].sum() if "valid" in batch \
                else next(iter(batch.values())).shape[0]
            return metrics, n

        def train_step_multi(state: TrainState, stacked):
            """K steps in one dispatch: scan train_step over the leading
            axis of a stacked batch dict. Metrics are window means."""
            state, metrics = jax.lax.scan(train_step, state, stacked)
            return state, jax.tree.map(lambda m: m.mean(0), metrics)

        # Under a mesh the new state leaves the step sharded exactly
        # as the old one entered it. Left to the compiler, a leaf can
        # come back split differently (a bias over 'model'): the
        # donated buffer then cannot be reused in place, and the AOT
        # executable — the path the chip takes — refuses its own
        # output as the next step's input.
        def jit_step(fn, n_aux):
            if self._state_shardings is None:
                return jax.jit(fn, donate_argnums=0)
            return jax.jit(fn, donate_argnums=0, out_shardings=(
                self._state_shardings, *(None,) * n_aux))

        if self._guard_policy != guard_mod.OFF:
            # guarded step functions: bad steps apply no update and
            # every step's loss is threaded out so the host guard can
            # attribute/skip/rewind exactly (resilience/guard.py). Only
            # armed configs compile these — with the guard off the
            # pristine functions below lower to byte-identical graphs.
            self._train_step = jit_step(
                guard_mod.wrap_train_step(train_step), 2)
            self._train_step_multi = jit_step(
                guard_mod.wrap_train_step_multi(train_step), 2)
        else:
            self._train_step = jit_step(train_step, 1)
            self._train_step_multi = jit_step(train_step_multi, 1)
        self._eval_step = jax.jit(eval_step)

    def _load_step(self, step_fn, state, sharded, label):
        """Lower the step once, say which attention core each call site
        of the traced step took and what its ``remat`` layers keep, and
        return the function to call from now on: with an executable
        cache the compiled step (``cache.aot_compile``: a cache read, or
        the compile the first call would do anyway, stored), without
        one the jitted function itself, which pins no shapes."""
        from perceiver_tpu.cache import aot_compile
        from perceiver_tpu.ops.attention import (
            attention_paths,
            format_attention_paths,
            masked_attention_tiles,
        )
        from perceiver_tpu.models.hybrid_lm import prediction_modules
        from perceiver_tpu.ops.delta_rule import rule_paths
        from perceiver_tpu.ops.moe import moe_kinds, moe_paths
        from perceiver_tpu.ops.pallas_head_rotary import rotary_paths
        from perceiver_tpu.ops.pallas_short_conv import conv_paths
        from perceiver_tpu.ops.remat import format_remat_keeps, remat_keeps
        from perceiver_tpu.ops.ssm import scan_paths
        from perceiver_tpu.ops.tally import format_tally
        with span("train/step_load"), attention_paths() as paths, \
                masked_attention_tiles() as tiles, \
                remat_keeps() as keeps, scan_paths.counting() as scans, \
                moe_paths.counting() as experts, \
                moe_kinds.counting() as kinds, \
                rule_paths.counting() as rules, \
                conv_paths.counting() as convs, \
                rotary_paths.counting() as rotaries, \
                prediction_modules.counting() as modules:
            try:
                if self._exec_cache is None:
                    step_fn.lower(state, sharded)
                else:
                    step_fn, _ = aot_compile(
                        step_fn, (state, sharded), cache=self._exec_cache,
                        label=label)
            except Exception as e:
                # the first dispatch of the jitted step raises the real
                # error, with its traceback
                print(f"[step_load] not loaded ahead of time: {e!r}",
                      file=sys.stderr, flush=True)
        self._step_loaded = True
        lines = ["attention call sites: "
                 + format_attention_paths(paths, tiles),
                 f"remat keeps: {format_remat_keeps(keeps)}"]
        if rotaries:  # attention with q/k norms or rotary positions
            lines.insert(1, "head norms and rotations: "
                         + format_tally(rotaries))
        if scans:    # a stack with state-space layers (ops/ssm.py)
            lines.append(f"selective scans: {format_tally(scans)}")
        if rules:    # a stack with linear-attention layers (ops/delta_rule.py)
            lines.append(f"delta rules: {format_tally(rules)}")
        if convs:    # ... whose mixers have a short convolution
            lines.append(f"short convolutions: {format_tally(convs)}")
        if experts:  # a stack with expert layers (ops/moe.py)
            lines.append(f"expert layers: {format_tally(experts)}")
            lines.append(f"expert kinds: {format_tally(kinds)}")
        if modules:  # a multi-token prediction loss (tasks/hybrid_lm.py)
            lines.append(f"prediction modules: {format_tally(modules)}")
        print("\n".join(f"[step_load] {line}" for line in lines),
              file=sys.stderr, flush=True)
        return step_fn

    def _preemption_pending(self) -> bool:
        """Single-process: the SIGTERM flag. Multi-host: the orbax save
        below is a collective, so hosts must agree on the step — defer
        to JAX's coordinated sync point (driven by the coordination
        service's preemption notice) instead of per-host signals, which
        land at different loop positions on different hosts."""
        if faults.fire("train.preempt"):
            # injected preemption notice — the chaos twin of SIGTERM
            self._preempted = True
            return True
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            try:
                return bool(multihost_utils.reached_preemption_sync_point(
                    int(self.global_step)))
            except Exception:
                return False
        return self._preempted

    def _handle_preemption(self, state: TrainState) -> bool:
        """Save full state to checkpoints-preempt and signal a clean
        stop. Returns True when a preemption was handled."""
        if not self._preemption_pending():
            return False
        self._preempted = True  # skip the validation pass on stop
        with span("train/checkpoint"):
            hook = CheckpointHook(
                os.path.join(self.log_dir, "checkpoints-preempt"),
                max_to_keep=1, monitor="", hparams=self._hparams())
            hook.save(self.global_step, state, {})
            hook.wait()
        events_mod.emit("preempt_checkpoint", step=int(self.global_step))
        if self.telemetry is not None:
            self.telemetry.preempt_checkpoint(self.global_step)
        print(f"Preemption: saved step {self.global_step} to "
              f"{os.path.join(self.log_dir, 'checkpoints-preempt')}")
        return True

    # --- non-finite guard ----------------------------------------------------

    def _poison_batch(self, arrays: Dict[str, np.ndarray],
                      index: Optional[int] = None) -> None:
        """``train.nonfinite`` chaos seam: overwrite one step's float
        fields with NaN on the HOST, so a real non-finite loss flows
        through the unmodified jitted step (the lowered graph never
        changes; only the data does)."""
        for v in arrays.values():
            if np.issubdtype(v.dtype, np.floating):
                if index is None:
                    v[...] = np.nan
                else:
                    v[index] = np.nan

    def _save_anchor(self, state: TrainState, epoch: int,
                     batches_done: int) -> None:
        """Record a last-good rewind target: verified checkpoint plus
        the deterministic data-stream position it was taken at."""
        if self._guard_ckpt is None or self.global_step == self._anchor_step:
            return
        with span("train/anchor"):
            self._guard_ckpt.save(self.global_step, state, {})
        self._anchor_pos = (epoch, batches_done)
        self._anchor_step = self.global_step

    def _guard_rewind(self, template_state: TrainState) -> TrainState:
        """Restore the newest verified anchor checkpoint; the caller
        repositions the data iterator at ``self._anchor_pos``."""
        self._guard_ckpt.wait()
        restored = self._guard_ckpt.restore_latest(template_state)
        if restored is None:
            raise guard_mod.NonFiniteLossError(
                self.global_step, detail="no anchor checkpoint to "
                "rewind to")
        self.global_step = int(restored.step)
        if jax.process_index() == 0:
            print(f"[guard] non-finite streak: restored verified "
                  f"anchor at step {self.global_step}, replaying "
                  f"epoch {self._anchor_pos[0]} from batch "
                  f"{self._anchor_pos[1]}", file=sys.stderr, flush=True)
        return restored

    # --- loops ---------------------------------------------------------------

    def _process_shard(self, loader, pad_remainder: bool = False):
        """Apply per-host dataset sharding on multi-host runs. A loader
        that cannot shard would silently duplicate data P× (every host
        contributing identical rows to the global batch), so that is an
        error, not a fallback. Training drops the cross-host remainder
        (equal step counts); eval passes ``pad_remainder=True`` so short
        shards are padded with invalid rows instead and every example
        is evaluated exactly once."""
        if jax.process_count() <= 1:
            return loader
        if not hasattr(loader, "set_sharding"):
            raise ValueError(
                f"multi-host run ({jax.process_count()} processes) needs "
                "a process-shardable loader (set_sharding); got "
                f"{type(loader).__name__}")
        loader.set_sharding(jax.process_count(), jax.process_index(),
                            pad_remainder)
        return loader

    def _run_eval(self, loader, limit: Optional[int], state: TrainState,
                  prefix: str) -> Dict[str, float]:
        loader = self._process_shard(loader, pad_remainder=True)
        totals: Dict[str, float] = {}
        count = 0.0
        eval_key = jax.random.key(self.config.seed + 1)
        for i, batch in enumerate(loader):
            if limit is not None and i >= limit:
                break
            metrics, n = self._eval_step(state, self._shard_batch(batch),
                                         jax.random.fold_in(eval_key, i))
            n = float(n)
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + float(v) * n
            count += n
        if count == 0:
            return {}
        return {f"{prefix}_{k}": v / count for k, v in totals.items()}

    def _log_step(self, metrics, *, dt: float, throughput: float,
                  steps_since: int, pace: StepPace, step_span) -> None:
        """One logged step: console heartbeat, summary scalars and the
        telemetry line, three blocking writes and a leaf each. The
        caller fenced ``metrics``, so nothing here waits for the
        device's step."""
        with span("train/log_console"):
            if jax.process_index() == 0:
                # console heartbeat: progress visibility for interactive
                # runs and a liveness signal for watchdogs (a stalled
                # device shows up as this line going quiet)
                print(f"[step {self.global_step}] "
                      + " ".join(f"{k}={float(v):.4f}"
                                 for k, v in metrics.items())
                      + f" samples/s={throughput:.1f}",
                      file=sys.stderr, flush=True)
        with span("train/log_scalars"):
            for k, v in metrics.items():
                self.writer.add_scalar(f"train_{k}", float(v),
                                       self.global_step)
            # MultiSteps advances the schedule once per accumulation
            # window, not per micro-step
            opt_step = (max(self.global_step - self._lr_step_offset, 0)
                        // max(self.config.accumulate_grad_batches, 1))
            self.writer.add_scalar("lr", float(self.lr_fn(opt_step)),
                                   self.global_step)
            if steps_since > 0:
                self.writer.add_scalar("samples_per_sec", throughput,
                                       self.global_step)
            if self._guard is not None:
                self.writer.add_scalar("guard_skipped_steps",
                                       float(self._guard.skipped_total),
                                       self.global_step)
        if self.telemetry is None:
            return
        with span("train/log_telemetry"):
            # the caller's fence() already pulled metrics to host:
            # telemetry adds zero device syncs. The phases' seconds are
            # the leaves' since the last line, that line's own logging
            # among them; left out when tracing is switched off
            phases = pace.phases_since_line(step_span)
            # the task's other scalars (a packed loss's overflow, a
            # looped model's exit gate) ride the same line
            phases.update((k, float(v)) for k, v in metrics.items()
                          if k != "loss")
            self.telemetry.step(
                self.global_step, float(metrics.get("loss", float("nan"))),
                steps_delta=steps_since,
                steps_per_sec=steps_since / max(dt, 1e-9),
                samples_per_sec=throughput, **phases)

    def fit(self) -> TrainState:
        """Train with SIGTERM (preemption) handling around the loop."""
        self._preempted = False  # a prior preempted fit() must not leak
        if self.config.fault_plan:
            faults.arm(self.config.fault_plan)
        # the timeline's one installed piece: the collector's callback,
        # for as long as the steps run
        self._gc_spans = GcSpans().install()
        installed, old_term = False, None
        if self.config.preempt_checkpoint:
            try:
                old_term = signal.signal(
                    signal.SIGTERM,
                    lambda *_: setattr(self, "_preempted", True))
                installed = True
            except ValueError:
                pass  # not on the main thread
        uninstall_profiler = None
        if self.config.profile_dir:
            from perceiver_tpu.obs.telemetry import install_signal_profiler
            # SIGUSR1 toggles a jax.profiler capture into profile_dir;
            # returns None off the main thread (profiling stays manual)
            uninstall_profiler = install_signal_profiler(
                self.config.profile_dir,
                event_log=events_mod.default_log())
        try:
            return self._fit()
        finally:
            self._gc_spans.uninstall()
            if uninstall_profiler is not None:
                uninstall_profiler()
            if installed:
                # old_term is None when the prior handler was installed
                # at the C level — SIG_DFL is the closest restorable
                # disposition (None is not accepted by signal.signal)
                signal.signal(signal.SIGTERM,
                              old_term if old_term is not None
                              else signal.SIG_DFL)

    def _prepare_data(self):
        """Lightning ``prepare_data`` semantics on multi-host: only
        process 0 downloads/trains-tokenizer (concurrent writers on a
        shared data_dir would corrupt caches), everyone syncs after."""
        if jax.process_count() <= 1:
            self.datamodule.prepare_data()
            return
        try:
            if jax.process_index() == 0:
                self.datamodule.prepare_data()
        finally:
            # reach the barrier even when process 0 raised — otherwise
            # every other host hangs in the sync forever instead of the
            # fleet failing fast
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("prepare_data")

    def _fit(self) -> TrainState:
        cfg = self.config
        if cfg.detect_anomaly:
            jax.config.update("jax_debug_nans", True)

        with span("train/data_setup"):
            self._prepare_data()
            self.datamodule.setup()
        self._guard = None
        self._guard_ckpt = None
        self._anchor_pos, self._anchor_step = (0, 0), -1
        with span("train/io_setup"):
            self.writer = (SummaryWriter(self.log_dir)
                           if jax.process_index() == 0 else _NullWriter())
            if cfg.telemetry_dir and jax.process_index() == 0:
                from perceiver_tpu.obs.telemetry import Telemetry
                self.telemetry = Telemetry(cfg.telemetry_dir)
            if cfg.enable_checkpointing:
                self._ckpt = CheckpointHook(
                    os.path.join(self.log_dir, "checkpoints"),
                    max_to_keep=cfg.save_top_k,
                    monitor=cfg.checkpoint_monitor,
                    hparams=self._hparams())
            if self._guard_policy != guard_mod.OFF:
                self._guard = guard_mod.StepGuard(
                    self._guard_policy,
                    streak_to_rewind=cfg.nonfinite_streak,
                    max_rewinds=cfg.nonfinite_max_rewinds)
                if self._guard_policy == guard_mod.SKIP:
                    # synchronous: the anchor must snapshot the state AT
                    # this step — an async save of donated buffers can
                    # serialize a later step's contents under this label
                    self._guard_ckpt = CheckpointHook(
                        cfg.guard_anchor_dir
                        or os.path.join(self.log_dir, "checkpoints-guard"),
                        max_to_keep=1, monitor="", enable_async=False)
        pace = StepPace(self._gc_spans, self.telemetry)

        with span("train/build_state"):
            state = self._build_state()
        self._make_steps()

        if cfg.resume_from_checkpoint:
            hook = CheckpointHook(cfg.resume_from_checkpoint,
                                  monitor=cfg.checkpoint_monitor)
            try:
                with span("train/restore"):
                    restored = hook.restore_latest(state)
            except (ValueError, KeyError) as e:
                # orbax raises ValueError (or, on the 0.7 line's
                # flat-dict template matching, KeyError) on tree/shape
                # mismatch — typically the checkpoint's optimizer
                # state no longer matching the current optimizer/
                # scheduler config (e.g. the schedule changed between
                # runs); params + rng + step are still config-agnostic
                # and worth resuming from. Other failures (I/O,
                # corruption) propagate.
                import warnings

                warnings.warn(
                    f"full-state resume from "
                    f"{cfg.resume_from_checkpoint} failed "
                    f"({type(e).__name__}) — the checkpoint's "
                    f"optimizer state is incompatible with the current "
                    f"optimizer/scheduler config; restoring "
                    f"params/rng/step with a FRESH optimizer state "
                    f"instead (momentum and schedule restart)",
                    stacklevel=2)
                restored = hook.restore_params_and_step(state)
                if restored is not None:
                    # the fresh schedule counts from 0 while
                    # global_step resumes — keep the logged lr honest
                    self._lr_step_offset = int(restored.step)
            if restored is not None:
                state = restored
                self.global_step = int(state.step)

        max_epochs = (1 if cfg.fast_dev_run
                      else cfg.max_epochs if cfg.max_epochs > 0
                      else _UNLIMITED_EPOCHS)
        limit_train = (1 if cfg.fast_dev_run
                       else cfg.overfit_batches or cfg.limit_train_batches)
        limit_val = 1 if cfg.fast_dev_run else cfg.limit_val_batches

        with span("train/data_setup"):
            train_loader = self.datamodule.train_dataloader()
        if cfg.overfit_batches:
            # Lightning semantics: overfit repeats the SAME batches every
            # epoch, so shuffling must be disabled
            train_loader.shuffle = False
        # per-host data sharding (the DistributedSampler /
        # replace_sampler_ddp equivalent, reference trainer.yaml:61)
        train_loader = self._process_shard(train_loader)
        if cfg.prefetch_batches > 0:
            from perceiver_tpu.data.prefetch import PrefetchIterator
            train_loader = PrefetchIterator(
                train_loader, depth=cfg.prefetch_batches,
                max_restarts=cfg.loader_restart_budget,
                backoff_s=cfg.loader_backoff_s)

        # sanity validation (trainer.yaml:53)
        if cfg.num_sanity_val_steps and not cfg.fast_dev_run:
            with span("train/eval"):
                self._run_eval(self.datamodule.val_dataloader(),
                               cfg.num_sanity_val_steps, state, "sanity")

        if cfg.profiler:
            jax.profiler.start_trace(os.path.join(self.log_dir, "profile"))

        import itertools

        # optimizer steps per device dispatch (lax.scan over stacked
        # batches). fast_dev_run stays single-step for debuggability.
        spe = 1 if cfg.fast_dev_run else max(cfg.steps_per_execution, 1)

        stop = False
        t0, samples_since, steps_since = time.time(), 0, 0
        metrics = None
        epoch = 0
        replay_batches = 0  # rewind reposition within the next epoch
        if cfg.resume_step_replay and self.global_step > 0:
            # reposition the epoch-seeded stream at the restored step
            # (same mechanics as a guard rewind): global_step counts
            # one batch per step, so step // per_epoch names the epoch
            # and step % per_epoch the batches already consumed in it
            per_epoch = len(train_loader)
            if limit_train is not None:
                per_epoch = min(per_epoch, limit_train)
            if per_epoch > 0:
                epoch = self.global_step // per_epoch
                replay_batches = self.global_step % per_epoch
        while epoch < max_epochs:
            self.current_epoch = epoch
            train_loader.set_epoch(epoch)

            def epoch_batches():
                for i, b in enumerate(train_loader):
                    if limit_train is not None and i >= limit_train:
                        return
                    yield b

            batch_iter = epoch_batches()
            batches_done = 0
            if replay_batches:
                # deterministic rewind replay: the loader is
                # epoch-seeded, so discarding N batches reproduces the
                # exact stream position the anchor was taken at
                for _ in itertools.islice(batch_iter, replay_batches):
                    pass
                batches_done, replay_batches = replay_batches, 0
            self._save_anchor(state, epoch, batches_done)
            rewound = False
            pace.epoch_start()
            while True:
                remaining = (cfg.max_steps - self.global_step
                             if cfg.max_steps > 0 else spe)
                if remaining <= 0:
                    # already at/beyond max_steps (e.g. resumed from a
                    # finished run) — never pull or train another batch
                    stop = True
                    break
                with span("train/step",
                          step=self.global_step + 1) as step_span:
                    pace.step_open()
                    # queue_depth 0: the producer starved the loop
                    depth = ({"queue_depth": train_loader.queue_depth()}
                             if cfg.prefetch_batches > 0 else {})
                    with span("train/input_wait", **depth):
                        group = list(itertools.islice(batch_iter,
                                                      min(spe, remaining)))
                    if not group:
                        step_span.cancel()  # the epoch's end, not a step
                        break
                    # local rows × process count = global rows per dispatch
                    # (each host contributes an equal per-host shard to the
                    # global batch), so samples_per_sec reports global
                    # training throughput
                    # count only real rows — a non-drop_last loader pads the
                    # final batch with invalid rows that do no training work
                    batch_size = (sum(int(b["valid"].sum()) for b in group)
                                  * jax.process_count())
                    prev_step = self.global_step
                    first_step = not self._step_loaded
                    # the single-step fn compiles separately from the
                    # multi-step one; its first run must also stay out of
                    # the throughput measurement window
                    first_single = (spe > 1 and len(group) < spe
                                    and not self._single_step_ran)
                    poison = faults.armed("train.nonfinite")
                    losses = None
                    if len(group) == spe and spe > 1:
                        with span("train/shard"):
                            stacked = {
                                key: np.stack([b[key] for b in group])
                                for key in group[0]}
                            if poison:
                                for i in range(len(group)):
                                    if faults.fire("train.nonfinite"):
                                        self._poison_batch(stacked, index=i)
                            sharded = self._shard_batch(stacked,
                                                        stacked=True)
                        if first_step:
                            self._train_step_multi = self._load_step(
                                self._train_step_multi, state, sharded,
                                "trainer:train_step_multi")
                        with span("train/dispatch"):
                            if self._guard is not None:
                                state, metrics, losses = \
                                    self._train_step_multi(state, sharded)
                            else:
                                state, metrics = self._train_step_multi(
                                    state, sharded)
                    else:
                        # trailing (or single-step-mode) group, step by step
                        losses = [] if self._guard is not None else None
                        for b in group:
                            with span("train/shard"):
                                if poison and faults.fire("train.nonfinite"):
                                    self._poison_batch(b)
                                sharded = self._shard_batch(b)
                            if not self._step_loaded:
                                self._train_step = self._load_step(
                                    self._train_step, state, sharded,
                                    "trainer:train_step")
                            with span("train/dispatch"):
                                if self._guard is not None:
                                    state, metrics, loss_i = \
                                        self._train_step(state, sharded)
                                    losses.append(loss_i)
                                else:
                                    state, metrics = self._train_step(
                                        state, sharded)
                        self._single_step_ran = True
                    self.global_step += len(group)
                    batches_done += len(group)
                    samples_since += batch_size
                    steps_since += len(group)
                    # crash-of-one-host chaos window: a SIGKILL at the
                    # dispatch boundary — after steps are consumed, before
                    # the guard syncs or anchors — is the worst-case point
                    # the anchor/replay recovery must absorb
                    # (distributed/group.py re-forms; dist_kill_train_host)
                    faults.maybe_kill("train.kill")

                    if self._guard is not None:
                        # per-dispatch host sync of the per-step losses:
                        # the cost of an armed guard, and the one detection
                        # path halt/skip/rewind all share
                        with span("train/guard_sync"):
                            if isinstance(losses, list):
                                losses_host = np.concatenate(
                                    [np.asarray(x) for x in losses])
                            else:
                                losses_host = np.asarray(losses)
                        skips_before = self._guard.skipped_total
                        action = self._guard.observe(losses_host, prev_step)
                        if self.telemetry is not None:
                            for _ in range(self._guard.skipped_total
                                           - skips_before):
                                self.telemetry.guard_skip(self.global_step)
                        if action == guard_mod.REWIND:
                            if self.telemetry is not None:
                                self.telemetry.guard_rewind(self.global_step)
                            state = self._guard_rewind(state)
                            epoch, replay_batches = self._anchor_pos
                            metrics = None
                            t0, samples_since, steps_since = \
                                time.time(), 0, 0
                            rewound = True
                            break
                        if (cfg.guard_anchor_every_n_steps > 0
                                and bool(np.isfinite(losses_host).all())
                                and self.global_step - self._anchor_step
                                >= cfg.guard_anchor_every_n_steps):
                            self._save_anchor(state, epoch, batches_done)
                    if first_step or first_single:
                        # this dispatch paid a jit compilation; keep it
                        # out of the throughput measurement window
                        with span("train/fence"):
                            fence(metrics)
                        t0, samples_since, steps_since = time.time(), 0, 0

                    crossed_log = (self.global_step // cfg.log_every_n_steps
                                   > prev_step // cfg.log_every_n_steps)
                    if crossed_log or cfg.fast_dev_run:
                        # async dispatch: sync on the device before taking
                        # dt, else the window measures host dispatch time
                        # and over-reports throughput
                        with span("train/fence"):
                            fence(metrics)
                        dt = time.time() - t0
                        throughput = samples_since / max(dt, 1e-9)
                        with span("train/log"):
                            self._log_step(
                                metrics, dt=dt, throughput=throughput,
                                steps_since=steps_since, pace=pace,
                                step_span=step_span)
                        t0, samples_since, steps_since = time.time(), 0, 0
                    # the step's pace: its two closing attrs, and the
                    # slow-step rule over its leaves (training/pace.py)
                    pace.step_close(step_span, steps=len(group),
                                    queue_depth=depth.get("queue_depth"))

                if cfg.preempt_checkpoint and \
                        self._handle_preemption(state):
                    stop = True
                    break

                if cfg.max_steps > 0 and self.global_step >= cfg.max_steps:
                    stop = True
                    break

            if rewound:
                # restart the loop at the anchor's epoch/batch without
                # counting an epoch or running validation on the
                # just-restored state
                continue

            if (epoch % cfg.check_val_every_n_epoch == 0 or stop) \
                    and not self._preempted:  # grace window is short
                with span("train/eval"):
                    val_metrics = self._run_eval(
                        self.datamodule.val_dataloader(), limit_val, state,
                        "val")
                if val_metrics and jax.process_index() == 0:
                    print(f"[step {self.global_step}] "
                          + " ".join(f"{k}={float(v):.4f}"
                                     for k, v in val_metrics.items()),
                          file=sys.stderr, flush=True)
                for k, v in val_metrics.items():
                    self.writer.add_scalar(k, v, self.global_step)
                if hasattr(self.task, "on_validation_epoch_end"):
                    with span("train/epoch_end"):
                        self.task.on_validation_epoch_end(self, state)
                if self._ckpt is not None and val_metrics:
                    with span("train/checkpoint"):
                        self._ckpt.save(self.global_step, state,
                                        val_metrics)
                # eval/checkpoint wall time must not depress the next
                # window's samples_per_sec scalar
                t0, samples_since, steps_since = time.time(), 0, 0
            if stop:
                break
            epoch += 1

        if cfg.profiler:
            jax.profiler.stop_trace()
        if self._ckpt is not None:
            self._ckpt.wait()
        if self._guard_ckpt is not None:
            self._guard_ckpt.wait()
        self.final_state = state
        return state

    def validate(self, state: TrainState) -> Dict[str, float]:
        self.datamodule.setup()
        if self._eval_step is None:
            self._make_steps()
        m = self._run_eval(self.datamodule.val_dataloader(),
                           self.config.limit_val_batches, state, "val")
        return m

    def test(self, state: TrainState) -> Dict[str, float]:
        self.datamodule.setup()
        if self._eval_step is None:
            self._make_steps()
        return self._run_eval(self.datamodule.test_dataloader(),
                              self.config.limit_test_batches, state, "test")
