"""Prompt tokens the engine consumed by chunked prefill, as a share of
all tokens its steps handled (prefill plus generated): counts, from the
program's own counters."""


def read(run):
    registry = run.outcome.data.get("registry")
    if registry is None:
        return None
    prefill = registry.get("serving_decode_prefill_tokens_total")
    generated = registry.get("serving_decode_tokens_total")
    if prefill is None or generated is None:
        return None
    total = prefill.value + generated.value
    return 100.0 * prefill.value / total if total else None
