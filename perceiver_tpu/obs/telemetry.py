"""Training telemetry: per-step JSONL + the serving registry types.

The trainer already syncs its metrics to host at the logging boundary
(``crossed_log`` in ``training/trainer.py``) — the :class:`Telemetry`
sink rides that boundary, so telemetry adds ZERO extra device syncs:
it receives already-host floats and writes one JSONL line per logged
step plus ``training_*`` series in a
:class:`~perceiver_tpu.serving.metrics.MetricsRegistry` (same types as
serving, so the exposition conformance tests and the lint conventions
cover both planes with one rule set).

Profiling: :func:`install_signal_profiler` arms SIGUSR1 so a running
trainer can be told to capture ``jax.profiler`` traces without a
restart (first signal starts, second stops — or the bounded-duration
watchdog stops it); the serving side gets the same capability over
HTTP (``/profile?seconds=N`` in :mod:`perceiver_tpu.obs.server`).
"""

from __future__ import annotations

import os
import signal
import threading
from typing import Optional

from perceiver_tpu.obs.events import EventLog
from perceiver_tpu.serving.metrics import MetricsRegistry

__all__ = ["Telemetry", "install_signal_profiler"]


class Telemetry:
    """Per-step training telemetry sink (JSONL + metrics registry)."""

    def __init__(self, out_dir: str, *,
                 registry: Optional[MetricsRegistry] = None,
                 max_bytes: int = 4 << 20, max_backups: int = 3):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.path = os.path.join(out_dir, "telemetry.jsonl")
        self._log = EventLog(self.path, max_bytes=max_bytes,
                             max_backups=max_backups)
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        m = self.registry
        self._m_steps = m.counter(
            "training_steps_total", "optimizer steps completed")
        self._m_loss = m.gauge(
            "training_loss", "last logged training loss")
        self._m_steps_per_sec = m.gauge(
            "training_steps_per_second", "optimizer steps per second")
        self._m_samples_per_sec = m.gauge(
            "training_samples_per_second", "training throughput")
        self._m_input_wait = m.counter(
            "training_input_wait_seconds_total",
            "seconds the loop waited for its next batch (train/input_wait)")
        self._m_fence_wait = m.counter(
            "training_fence_wait_seconds_total",
            "seconds the host waited for the device (train/fence)")
        self._m_host_busy = m.counter(
            "training_host_busy_seconds_total",
            "seconds of the loop's own host work: shard, dispatch, "
            "guard sync, logging")
        self._m_slow_steps = m.counter(
            "training_slow_steps_total",
            "steps over their neighbours' median pace by more than 5% "
            "and 20 ms, those slow by design left out (training/pace.py)")
        self._m_stall = m.counter(
            "training_stall_seconds_total",
            "seconds the slow steps took over the median pace")
        self._m_guard_skips = m.counter(
            "training_guard_skips_total", "non-finite steps skipped")
        self._m_rewinds = m.counter(
            "training_guard_rewinds_total",
            "rewinds to a verified anchor")
        self._m_seals = m.counter(
            "training_checkpoint_seals_total",
            "sha256-sealed checkpoints written")
        self._m_preempts = m.counter(
            "training_preempt_checkpoints_total",
            "preemption checkpoints written")

    def step(self, step: int, loss: float, *, steps_delta: int = 1,
             steps_per_sec: Optional[float] = None,
             samples_per_sec: Optional[float] = None,
             input_wait_s: Optional[float] = None,
             host_s: Optional[float] = None,
             fence_s: Optional[float] = None, **extra) -> dict:
        """Record one logged step (values must already be host floats —
        never pass device arrays; the trainer syncs first).
        ``input_wait_s`` / ``host_s`` / ``fence_s`` are the seconds of
        the trainer's phases (``obs.trace.TRAIN_PHASES``) since the last
        logged step."""
        self._m_steps.inc(steps_delta)
        self._m_loss.set(loss)
        fields = {"step": int(step), "loss": float(loss)}
        if steps_per_sec is not None:
            self._m_steps_per_sec.set(steps_per_sec)
            fields["steps_per_sec"] = round(float(steps_per_sec), 4)
        if samples_per_sec is not None:
            self._m_samples_per_sec.set(samples_per_sec)
            fields["samples_per_sec"] = round(float(samples_per_sec), 4)
        for key, counter, seconds in (
                ("input_wait_s", self._m_input_wait, input_wait_s),
                ("host_s", self._m_host_busy, host_s),
                ("fence_s", self._m_fence_wait, fence_s)):
            if seconds is not None:
                counter.inc(seconds)
                fields[key] = round(float(seconds), 6)
        for k, v in extra.items():
            try:
                fields[k] = float(v)
            except (TypeError, ValueError):
                fields[k] = v
        return self._log.emit("train_step", **fields)

    def slow_step(self, step: int, *, stalled_s: Optional[float],
                  **fields) -> None:
        """One step the slow-step rule named; ``stalled_s`` is its
        excess over the median, None for a step slow by design, which
        the counters leave out."""
        if stalled_s is not None:
            self._m_slow_steps.inc()
            self._m_stall.inc(stalled_s)
        self._log.emit("slow_step", step=int(step), **fields)

    def guard_skip(self, step: int, **fields) -> None:
        self._m_guard_skips.inc()
        self._log.emit("guard_skip", step=int(step), **fields)

    def guard_rewind(self, step: int, **fields) -> None:
        self._m_rewinds.inc()
        self._log.emit("guard_rewind", step=int(step), **fields)

    def checkpoint_seal(self, path: str) -> None:
        self._m_seals.inc()
        self._log.emit("checkpoint_seal", path=str(path))

    def preempt_checkpoint(self, step: int) -> None:
        self._m_preempts.inc()
        self._log.emit("preempt_checkpoint", step=int(step))

    def events(self, etype: Optional[str] = None):
        return self._log.events(etype)


def install_signal_profiler(profile_dir: str, *,
                            signum: int = signal.SIGUSR1,
                            max_seconds: float = 60.0,
                            event_log: Optional[EventLog] = None):
    """Arm ``signum`` to toggle a ``jax.profiler`` capture into
    ``profile_dir``.  Returns an ``uninstall()`` callable, or ``None``
    when handlers can't be installed (non-main thread).

    First signal starts the capture; a second signal — or a
    ``max_seconds`` watchdog — stops it, so a forgotten capture cannot
    fill the disk.
    """
    os.makedirs(profile_dir, exist_ok=True)
    state = {"active": False}
    lock = threading.Lock()

    def _stop(reason: str) -> None:
        with lock:
            if not state["active"]:
                return
            state["active"] = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # profiler drift — the toggle must survive
            return
        if event_log is not None:
            event_log.emit("profile_capture", dir=profile_dir,
                           reason=reason)

    def _handler(signo, frame):
        with lock:
            starting = not state["active"]
            state["active"] = starting
        if starting:
            try:
                import jax

                jax.profiler.start_trace(profile_dir)
            except Exception:  # profiler drift — the toggle must survive
                with lock:
                    state["active"] = False
                return
            threading.Timer(max_seconds,
                            lambda: _stop("watchdog")).start()
        else:
            with lock:  # _stop re-checks; restore for its guard
                state["active"] = True
            _stop("signal")

    try:
        prev = signal.signal(signum, _handler)
    except ValueError:  # not the main thread — profiling stays manual
        return None

    def uninstall() -> None:
        _stop("uninstall")
        signal.signal(signum, prev)

    return uninstall
