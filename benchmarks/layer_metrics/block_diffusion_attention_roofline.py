"""Roofline share of the block-diffusion attention kernels
(``block_diffusion_attention_fwd``, ``block_diffusion_attention_bwd`` of
``ops/pallas_attention.py``) in the traced window: the least time the
chip could take for every call (``block_diffusion_costs.attention_cost``
of the call's own shapes: the ``L^2 + L B`` pairs a head's queries may
see, and q, k, v, o once each), summed, over the same events' summed
device time. Device time over device time: no host clock.

As ``causal_attention_roofline`` for its two kernels: a kernel's events
are the custom calls whose instruction name holds the kernel's; shapes
come from the event's HLO text, the first two rank-3 operands ``q (B,
2 L, E)`` and ``k (B, 2 L, E)`` (the mask's two prefetched tables are
rank 1); the block length is the configuration's; a forward that
``remat`` runs again is a second call. The tiles the mask cuts do hidden
work that the count leaves out, so the share stays under what the same
kernels reach on full scores. Nothing to read (no trace, no such kernel,
a configuration without a block length, a name without shapes) gives
None."""

from benchmarks import flops, trace_reduce
from benchmarks.layer_metrics import block_diffusion_costs as costs

KERNELS = {"block_diffusion_attention_fwd": False,
           "block_diffusion_attention_bwd": True}
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def call_of(event_name: str):
    """``(kernel, b, positions, width, itemsize)`` of a kernel's event;
    None for any other event; a ``ValueError`` for a kernel's event
    whose shapes cannot be read."""
    if "block_diffusion_attention_" not in event_name:
        return None
    name, opcode, _, operands = trace_reduce.instruction(event_name)
    kernel = next((k for k in KERNELS if k in name), None)
    if kernel is None or opcode != "custom-call":
        return None
    rank3 = [(dtype, dims) for dtype, dims in operands if len(dims) == 3]
    if len(rank3) < 2:
        raise ValueError(f"no (B, S, E) operands in {event_name[:160]!r}")
    (dtype, (b, s, e)), (_, k_dims) = rank3[:2]
    if tuple(k_dims) != (b, s, e) or s % 2 or dtype not in ITEMSIZE:
        raise ValueError(f"q and k do not agree in {event_name[:160]!r}")
    return kernel, b, s, e, ITEMSIZE[dtype]


def by_call(reduction) -> dict:
    """{(kernel, b, positions, width, itemsize): [calls, seconds]} over
    every device of the trace."""
    parsed, out = {}, {}
    for events in reduction.events.values():
        for e in events:
            if e.name not in parsed:
                parsed[e.name] = call_of(e.name)
            key = parsed[e.name]
            if key is not None:
                row = out.setdefault(key, [0, 0.0])
                row[0] += 1
                row[1] += e.duration_ns / 1e9
    return out


def read(run):
    block = run.cfg.get("block_length")
    if run.trace is None or not run.peak or not block:
        return None
    try:
        rows = by_call(run.trace)
    except ValueError as e:
        print(f"[bench] block_diffusion_attention_roofline: not reported, "
              f"{e}", flush=True)
        return None
    if not rows:
        return None
    least_all = seconds_all = 0.0
    for (kernel, b, s, width, itemsize), (calls, seconds) in sorted(
            rows.items(), key=lambda kv: -kv[1][1]):
        ops, moved = costs.attention_cost(
            b, s // 2, int(block), width, backward=KERNELS[kernel],
            bytes_per_value=itemsize)
        t, bound = flops.roofline_seconds(ops, moved, run.peak)
        least_all += calls * t
        seconds_all += seconds
        print(f"[bench] {kernel} {b} x 2 x {s // 2} (blocks of {block}) x "
              f"{width}: {calls} calls, {seconds:.4f} s on the device, "
              f"least {calls * t:.4f} s ({bound}): "
              f"{100.0 * calls * t / seconds:.1f}%", flush=True)
    print(f"[bench] block-diffusion attention kernels: {seconds_all:.4f} s "
          f"on the device, least {least_all:.4f} s", flush=True)
    return 100.0 * least_all / seconds_all
