"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick for ``train.mfu_pct`` and the kernel roofline
shares: what the mathematics of the configuration requires, not what a
compiled program happens to execute. Recomputation (``remat``) is not
counted, a value that several layers share is counted once (the key and
value projections of the weight-shared encoder layers read the same
input with the same weights), and only matrix products count: 2 m n k
each. XLA's cost analysis is not used: it counts the body of a
``lax.scan`` once, which at the Perceiver-LM widths reads 3.65 TFLOP for
a step that needs about 17 (PERF.md, Findings).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]


def forward_parts(cfg: dict, shape: dict) -> Dict[str, float]:
    """Forward matrix-product operations for one row, by part.
    ``shape`` is the task's ``flop_shape(cfg)``: input ``positions`` of
    ``channels`` each, and the ``queries`` of ``classes`` each that the
    loss reads per row."""
    m, c_in = shape["positions"], shape["channels"]
    n, c = int(cfg["num_latents"]), int(cfg["num_latent_channels"])
    layers = int(cfg["num_encoder_layers"])
    selfs = layers * int(cfg["num_encoder_self_attention_layers_per_block"])
    k_out, classes = shape["queries"], shape["classes"]
    # the first layer's weights and the shared layers' weights: two
    # distinct key/value projections however many layers share
    kv_sets = min(layers, 2)
    return {
        "encoder_kv_projection": kv_sets * 4.0 * m * c_in * c,
        "encoder_cross_attention": layers * 4.0 * n * m * c,
        "encoder_cross_dense": layers * 8.0 * n * c * c,
        "self_attention_dense": selfs * 12.0 * n * c * c,
        "self_attention_scores": selfs * 4.0 * n * n * c,
        "decoder": 8.0 * k_out * c * c + 4.0 * n * c * c
        + 4.0 * k_out * n * c,
        "output_projection": 2.0 * k_out * c * classes,
    }


def train_step_flops(cfg: dict, rows: int, shape: dict) -> float:
    """Forward plus backward for ``rows`` rows: a product costs twice
    itself again in the backward pass (one product for each operand's
    gradient); where the input takes no gradient (``input_grad``
    false: pixels), its key/value projection costs once again."""
    total = 0.0
    for part, fwd in forward_parts(cfg, shape).items():
        no_input_grad = (part == "encoder_kv_projection"
                         and not shape["input_grad"])
        total += fwd * (2.0 if no_input_grad else 3.0)
    return rows * total


def decode_token_flops(cfg: dict, kv_len: int) -> float:
    """One generated token of one stream, served from cached keys and
    values: the latents are rebuilt over ``kv_len`` cached positions,
    one query is decoded and projected to the vocabulary."""
    n, c = int(cfg["num_latents"]), int(cfg["num_latent_channels"])
    layers = int(cfg["num_encoder_layers"])
    selfs = layers * int(cfg["num_encoder_self_attention_layers_per_block"])
    return (layers * (4.0 * n * kv_len * c + 8.0 * n * c * c)
            + selfs * (12.0 * n * c * c + 4.0 * n * n * c)
            + 8.0 * c * c + 4.0 * n * c * c + 4.0 * n * c
            + 2.0 * c * cfg["vocab_size"])


def paged_attention_cost(kv_lens: Iterable[int], *, queries: int,
                         heads: int, head_dim: int,
                         bytes_per_value: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one ``ragged_paged_attention`` call in
    which every live row brings ``queries`` query vectors per head and
    attends its own ``kv_len`` cached tokens: QK^T and PV products; the
    queries read and the output written once, each row's keys and values
    read once. Rows with nothing cached do no work."""
    flops = bytes_ = 0.0
    for kv in kv_lens:
        if kv <= 0:
            continue
        flops += 4.0 * queries * kv * head_dim * heads
        bytes_ += bytes_per_value * heads * head_dim * (
            2.0 * queries + 2.0 * kv)
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peak: dict
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which bound binds."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = bytes_ / peak["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
