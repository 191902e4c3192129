"""Operations and bytes of the two mechanisms a block-diffusion
mixture-of-experts stack adds (``perceiver_tpu/ops/pallas_attention.py``'s
block-diffusion mode, ``perceiver_tpu/ops/moe.py``'s gated experts),
from shapes. No reader file itself (no ``read``):
``block_diffusion_attention_roofline``, ``moe_gated_expert_roofline`` and
``benchmarks/tasks/block_diffusion_lm.py`` import it.

By the rules at the head of ``benchmarks/flops.py``: a product 2 m n k,
a backward pass twice its forward's products (the attention backward
computes the scores again: 10 a pair and channel for the forward's 4),
recomputation not counted.
"""


def visible_pairs(half: int, block: int) -> float:
    """(query, key) pairs a head computes for one row of ``half``
    noised positions beside their clean ones in blocks of ``block``:
    a noised position sees its own block (``L B``) and the clean blocks
    before it (``(L^2 - L B) / 2``), a clean position the clean blocks up
    to its own (``(L^2 + L B) / 2``): ``L^2 + L B``."""
    return float(half) * half + float(half) * block


def attention_cost(b: int, half: int, block: int, width: int, *,
                   backward: bool, bytes_per_value: int = 2):
    """(operations, bytes) of one block-diffusion attention call on
    ``b`` rows of ``2 x half`` positions, ``width`` = heads x head dim
    channels, as ``flops.flash_attention_cost`` counts the other masks:
    forward 4 and backward 10 a visible pair and channel; q, k, v and
    the output once each, and in the backward do, dq, dk and dv once
    each as well. The masked tiles' hidden pairs are time, not work."""
    ops = (10.0 if backward else 4.0) * b * visible_pairs(half, block) \
        * width
    moved = bytes_per_value * b * width * 4.0 * (2 * half) \
        * (2.0 if backward else 1.0)
    return ops, moved


def gated_grouped_cost(cfg: dict, assignments: float, *, backward: bool):
    """(operations, bytes) of one expert layer's **three** grouped
    products (gate, up, down: ``hybrid_costs.grouped_cost`` counts a
    relu-squared layer's two) over ``assignments`` (token, held expert)
    rows, one pass. Bytes: the held experts' three matrices read once,
    each row read and written once a product, all in the compute dtype;
    the backward reads the matrices and twice the rows and writes the
    matrices' gradient."""
    c, hidden = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = int(cfg.get("held_experts") or cfg["num_experts"])
    ops = assignments * 2.0 * 3 * c * hidden
    matrices = 2.0 * held * 3 * c * hidden
    rows = 2.0 * assignments * 3 * (c + hidden)
    if backward:
        return 2.0 * ops, 2.0 * matrices + 2.0 * rows
    return ops, matrices + rows


def expected_assignments(cfg: dict, positions: int) -> float:
    """What an even router sends the held experts of one layer, of
    ``positions`` positions (the noised and the clean ones alike)."""
    held = int(cfg.get("held_experts") or cfg["num_experts"])
    return positions * float(cfg["num_experts_per_tok"]) * held \
        / int(cfg["num_experts"])
