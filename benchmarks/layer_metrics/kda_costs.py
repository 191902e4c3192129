"""Operations and bytes of what a Kimi Linear stack adds
(``perceiver_tpu/ops/delta_rule.py``'s chunked delta rule with a decay
that is a vector a key channel, Kimi Delta Attention;
``perceiver_tpu/models/hybrid_lm.py``'s latent attention, MLA; the
gated experts of ``perceiver_tpu/ops/moe.py`` under this family's key
names), from the configuration's shapes. No reader file itself (no
``read``): ``kda_rule_roofline``, ``mla_attention_roofline`` and
``benchmarks/tasks/kimi_linear_lm.py`` import it.

By the rules at the head of ``benchmarks/flops.py``: a product 2 m n k,
a backward pass twice its forward's products, recomputation not
counted.
"""

# the three grouped products of gated experts and the even share, under
# the hybrid task's key names (the router's width ``n_routed_experts``)
from benchmarks.layer_metrics.gated_delta_costs import (  # noqa: F401
    expected_assignments,
    gated_grouped_cost,
)


def rule_cost(cfg: dict, rows: int, positions: int, *, backward: bool):
    """(operations, bytes) of one KDA layer's chunked rule over ``rows``
    rows of ``positions`` positions, one pass, at the chunk
    ``delta_chunk_size`` Q and ``H`` heads of ``D`` channels for q, k
    and v alike. The products the chunked form needs, each ``Q x Q``
    whole (a chunk is the unit the mask cannot cut), a position and
    head: the decayed ``k k^T`` and ``q k^T`` (2 Q D each: a decay that
    is a vector does not change what a product costs, only what has to
    be multiplied in before it, which is not counted), the two
    triangular solves for ``U`` and ``W`` (2 Q (D + D): a substitution
    costs what a product with the inverse costs, and **the inverse
    itself is not counted**: how it is made is the implementation's),
    ``W S`` and ``q S`` (2 D D each), the masked scores times ``v'``
    (2 Q D) and ``k^T v'`` (2 D D). Bytes: q, k, v in the compute dtype,
    the (B, S, H, D) float32 ``g`` and the float32 ``beta`` read, o
    written, once; the backward reads them and ``do`` and writes five
    gradients: twice as many."""
    heads, d = int(cfg["kda_num_heads"]), int(cfg["kda_head_dim"])
    q = min(int(cfg["delta_chunk_size"]), positions)
    ops = rows * positions * 2.0 * heads * (
        2 * q * d + q * (d + d) + 3 * d * d + q * d)
    moved = rows * positions * heads * (2.0 * 4 * d + 4.0 * (d + 1))
    factor = 2.0 if backward else 1.0
    return factor * ops, factor * moved


def latent_core_cost(cfg: dict, rows: int, positions: int, *,
                     backward: bool):
    """(operations, bytes) of one latent-attention layer's causal core
    over ``rows`` rows of ``positions`` positions, one pass, **at the
    published widths**: ``H`` heads, score heads of ``n + r``
    (``qk_nope_head_dim + qk_rope_head_dim``) beside value heads of
    ``e`` (``v_head_dim``), ``S (S + 1) / 2`` pairs a head. Forward a
    pair: the score 2 (n + r) and its value 2 e. Backward: the score
    again, dK and dQ at the score heads' width, dP and dV at the value
    heads' (3 x 2 (n + r) + 2 x 2 e: as ``flops.flash_attention_cost``
    counts 10 for 4 at one width). Bytes: q, k (score width) and v, o
    (value width) once each in the compute dtype, and in the backward
    do, dq, dk and dv once each as well. Lanes a head is padded to, in
    a kernel that takes one width, are not work."""
    heads = int(cfg["num_attention_heads"])
    score = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    value = int(cfg["v_head_dim"])
    pairs = positions * (positions + 1) / 2.0
    ops = rows * heads * pairs * (
        3 * 2.0 * score + 2 * 2.0 * value if backward
        else 2.0 * score + 2.0 * value)
    moved = 2.0 * rows * positions * heads * (2 * score + 2 * value) \
        * (2.0 if backward else 1.0)
    return ops, moved
