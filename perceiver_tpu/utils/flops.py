"""Step-FLOPs estimation and MFU computation.

The reference never measures throughput or efficiency (SURVEY §6); the
rebuild's north-star metric is MFU (BASELINE.md: ≥40% on v5e-8 for MLM
pretraining), so the trainer and bench report it directly.

FLOPs come from XLA's own HLO cost analysis of the lowered step
(``Lowered.cost_analysis()`` — tracing+lowering only, no extra compile,
and matmul FLOPs are invariant under XLA's later optimization passes).
Peak chip FLOP/s is looked up by the device kind the chip reports. A
non-TPU device (the CPU test backend) has no peak and callers skip the
MFU scalar; a TPU whose kind is not in the table is an error, not a
default.
"""

from __future__ import annotations

from typing import Optional

import jax

# bf16 (MXU) peak FLOP/s per chip, by device-kind substring (spaces
# and dashes dropped, lower-cased). Sources: public TPU spec sheets
# (cloud.google.com/tpu/docs/system-architecture-tpu-vm). The chips
# publish no fp32 peak, so utilization is always against bf16.
_PEAK_BF16 = {
    "v6": 918e12,   # Trillium
    "v5p": 459e12,
    "v5e": 197e12,
    "v5lite": 197e12,  # a v5e reports "TPU v5 lite"
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def device_peak_flops(device: Optional[jax.Device] = None,
                      ) -> Optional[float]:
    """bf16 peak FLOP/s of one chip; None off-TPU; an unknown TPU
    ``device_kind`` raises."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower().replace(" ", "").replace("-", "")
    for tag, peak in _PEAK_BF16.items():
        if tag in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for TPU device_kind "
        f"{device.device_kind!r}; add it to utils/flops.py:_PEAK_BF16 "
        "with its source")


def _flops_of(cost) -> Optional[float]:
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    flops = float(cost.get("flops", 0.0))
    return flops if flops > 0 else None


def lowered_step_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """Total FLOPs of one call of ``jitted_fn`` at these arg shapes,
    from lowering alone (no compile). Returns None on backends that
    only expose post-compile analysis."""
    try:
        return _flops_of(jitted_fn.lower(*args, **kwargs).cost_analysis())
    except Exception:
        return None


def step_flops_and_fn(jitted_fn, *args, num_devices: int = 1,
                      on_lowered=None, cache=None,
                      cache_label: str = "train_step", **kwargs):
    """Returns ``(global_flops, fn)`` where ``fn`` is what the caller
    should invoke from now on.

    Prefers lowering-only cost analysis (keeps the original jit fn);
    the lowered HLO is the pre-partitioning module, so its count is
    already global. Where that is unavailable, AOT-compiles — the same
    compile the first jit call would have done, so no double
    compilation — and takes the analysis from the compiled module.
    That module is the SPMD-*partitioned* per-device program, so its
    count is scaled by ``num_devices`` (the devices the computation
    spans) to stay global. AOT executables require argument shapes and
    shardings to stay fixed, which the static-shape input pipeline
    guarantees.

    ``cache`` (a ``perceiver_tpu.cache.ExecutableCache``) switches the
    step to the persistent-compile-cache AOT path: a key hit
    deserializes the stored executable — the first dispatch performs
    ZERO XLA compiles — with flops read from the entry's sidecar; a
    miss compiles once (the compile the first jit call would have done
    anyway) and stores executable + sidecar for the next process.

    ``on_lowered``, when given, receives the ``Lowered`` object
    best-effort (the bench's graphcheck provenance hook — dtype audit
    from the very lowering being timed, without a second trace)."""
    from perceiver_tpu.cache import compile_lowered, has_host_callbacks

    try:
        lowered = jitted_fn.lower(*args, **kwargs)
    except Exception:
        return None, jitted_fn
    if on_lowered is not None:
        try:
            on_lowered(lowered)
        except Exception:
            pass  # provenance must never fail the measurement
    if cache is not None:
        try:
            text = lowered.as_text()
            # callback-bearing steps (e.g. the packed-CE overflow
            # warning on CPU) embed host pointers — never cacheable
            key = None if has_host_callbacks(text) \
                else cache.executable_key(text)
        except Exception:
            key = None
        if key is not None:
            exe = cache.load_executable(key)
            if exe is not None:
                flops = (cache.sidecar(key) or {}).get("flops")
                if flops is None:
                    try:
                        flops = _flops_of(lowered.cost_analysis())
                    except Exception:
                        flops = None
                return flops, exe
            try:
                flops = _flops_of(lowered.cost_analysis())
            except Exception:
                flops = None
            try:
                compiled = compile_lowered(lowered)
            except Exception:
                return flops, jitted_fn
            if flops is None:
                try:
                    flops = _flops_of(compiled.cost_analysis())
                    if flops is not None:
                        flops *= max(num_devices, 1)
                except Exception:
                    flops = None
            # sidecar carries the already-global flops so warm starts
            # skip cost analysis entirely
            cache.store_executable(key, compiled,
                                   sidecar={"label": cache_label,
                                            "flops": flops})
            return flops, compiled
    try:
        flops = _flops_of(lowered.cost_analysis())
    except Exception:
        flops = None
    if flops is not None:
        return flops, jitted_fn
    try:
        compiled = compile_lowered(lowered)
        flops = _flops_of(compiled.cost_analysis())
        if flops is not None:
            flops *= max(num_devices, 1)
        return flops, compiled
    except Exception:
        return None, jitted_fn


def mfu(flops_per_step: Optional[float], steps: int, seconds: float,
        num_devices: int = 1,
        peak_flops_per_device: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization in [0, 1] over a measured interval."""
    if not flops_per_step or not peak_flops_per_device or seconds <= 0 \
            or steps <= 0:
        return None
    achieved = flops_per_step * steps / seconds
    return achieved / (peak_flops_per_device * num_devices)
