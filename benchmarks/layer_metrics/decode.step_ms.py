"""Median of the program's ``serving_decode_step_latency_seconds``: the
host clock around one step of the engine, which ends in the read-back of
the next tokens. The histogram keeps its last 8192 steps, which covers
the warm-up's few and the window's."""


def read(run):
    registry = run.outcome.data.get("registry")
    if registry is None:
        return None
    hist = registry.get("serving_decode_step_latency_seconds")
    if hist is None or hist.count == 0:
        return None
    return hist.quantile(0.5) * 1e3
