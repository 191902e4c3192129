"""Roofline share of ``ragged_paged_attention`` in the decode step: the
least time the chip could take for the calls inside the traced window
(``benchmarks/flops.py``: operations and bytes from each step's cached
lengths, the larger of operations over the bf16 peak and bytes over the
HBM bandwidth), over the kernel's device time in the trace.

The kernel has no name of its own in the trace yet (its
``kernel_metadata`` is empty): it is the step's only Pallas call, so its
events are those whose instruction text holds ``tpu_custom_call``. A
step calls it once per encoder layer. The cached length of every row of
every step comes from the program's spans (``prefill_chunk`` with
``fed``, ``decode_step``), matched to the traced window on the
monotonic clock both use."""

from benchmarks import flops, trace_reduce

MATCH = ("tpu_custom_call",)


def step_lengths(program_spans, prompt_lens, mono0, mono1):
    """{step's start time: [cached length of each live row]} for the
    engine steps that began inside [mono0, mono1]."""
    steps = {}
    for index, spans in program_spans.items():
        decoded = 0
        for span in sorted(spans, key=lambda s: s["start"]):
            if span["phase"] == "prefill_chunk":
                kv = span["attrs"]["fed"]
            elif span["phase"] == "decode_step":
                decoded += 1
                kv = prompt_lens[index] + decoded
            else:
                continue
            if mono0 <= span["start"] <= mono1:
                steps.setdefault(span["start"], []).append(kv)
    return steps


def read(run):
    data = run.outcome.data
    if run.trace is None or not data.get("program_spans") or not run.peak:
        return None
    seconds, calls = trace_reduce.kernel_seconds(run.trace, MATCH)
    if not calls:
        return None
    prompt_lens = {s.request.index: len(s.request.prompt)
                   for s in data["sent"]}
    steps = step_lengths(data["program_spans"], prompt_lens,
                         run.tracer.mono0, run.tracer.mono1)
    cfg = run.cfg
    heads = cfg["num_encoder_cross_attention_heads"]
    least = 0.0
    bound = {"compute": 0, "memory": 0}
    for lengths in steps.values():
        ops, moved = flops.paged_attention_cost(
            lengths, queries=cfg["num_latents"], heads=heads,
            head_dim=cfg["num_latent_channels"] // heads)
        t, which = flops.roofline_seconds(ops, moved, run.peak)
        least += cfg["num_encoder_layers"] * t
        bound[which] += 1
    print(f"[bench] paged attention: {calls} calls, {seconds:.4f} s on the "
          f"device over {len(steps)} steps; least {least:.4f} s; bound by "
          f"{bound}", flush=True)
    return 100.0 * least / seconds
