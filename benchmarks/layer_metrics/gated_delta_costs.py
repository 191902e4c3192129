"""Operations and bytes of what a linear-attention mixture-of-experts
stack adds (``perceiver_tpu/ops/delta_rule.py``'s chunked gated delta
rule; ``perceiver_tpu/ops/moe.py``'s gated experts under this family's
key names), from the configuration's shapes. No reader file itself (no
``read``): ``delta_rule_roofline``, ``moe_gated_expert_roofline.gdn`` and
``benchmarks/tasks/gated_delta_lm.py`` import it.

By the rules at the head of ``benchmarks/flops.py``: a product 2 m n k,
a backward pass twice its forward's products, recomputation not
counted.
"""

from benchmarks.layer_metrics import block_diffusion_costs


def rule_cost(cfg: dict, rows: int, positions: int, *, backward: bool):
    """(operations, bytes) of one linear layer's chunked gated delta
    rule over ``rows`` rows of ``positions`` positions, one pass, at the
    chunk ``delta_chunk_size`` Q, ``Hk`` key heads of ``Dk``, ``Hv``
    value heads of ``Dv``. The products the chunked form needs, each
    ``Q x Q`` whole (a chunk is the unit the mask cannot cut): a
    position and key head ``k k^T`` and ``q k^T`` (2 Q Dk each); a
    position and value head the two triangular solves for ``U`` and
    ``W`` (2 Q (Dv + Dk): a substitution costs what a product with the
    inverse costs, and **the inverse itself is not counted**: how it is
    made, by doubling or by substitution, is the implementation's),
    ``W S`` and ``q S`` (2 Dk Dv each), the masked scores times ``v'``
    (2 Q Dv) and ``k^T v'`` (2 Dk Dv). Bytes: q, k, v in the compute
    dtype and g, beta in float32 read, o written, once; the backward
    reads them and ``do`` and writes five gradients: twice as many."""
    key_heads = int(cfg["linear_num_key_heads"])
    heads = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    q = min(int(cfg["delta_chunk_size"]), positions)
    ops = rows * positions * 2.0 * (
        key_heads * 2 * q * dk
        + heads * (q * (dv + dk) + 3 * dk * dv + q * dv))
    moved = rows * positions * (
        2.0 * (2 * key_heads * dk + 2 * heads * dv) + 4.0 * 2 * heads)
    factor = 2.0 if backward else 1.0
    return factor * ops, factor * moved


def _as_bd(cfg: dict) -> dict:
    """``block_diffusion_costs`` reads the router's width under
    ``num_experts``; this family's task calls it ``n_routed_experts``."""
    return {**cfg, "num_experts": cfg["n_routed_experts"]}


def gated_grouped_cost(cfg: dict, assignments: float, *, backward: bool):
    """``block_diffusion_costs.gated_grouped_cost`` (the three grouped
    products of gated experts) under this family's key names."""
    return block_diffusion_costs.gated_grouped_cost(
        _as_bd(cfg), assignments, backward=backward)


def expected_assignments(cfg: dict, positions: int) -> float:
    """What an even router sends the held experts of one layer."""
    return block_diffusion_costs.expected_assignments(_as_bd(cfg), positions)
