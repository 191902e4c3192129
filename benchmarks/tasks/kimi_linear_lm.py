"""Causal language model on a Kimi Linear stack (``HybridLMTask`` with
the pattern ``KDKEKEAEKE``: Kimi Delta Attention mixers, a latent
attention without positions, a leading dense MLP, sigmoid-routed gated
experts beside a shared expert with no gate column): full rows of
Zipf-distributed ids over the vocabulary the configuration holds (a
slice of the published one), each position labelled with the next id;
nothing is masked and nothing is drawn inside the step.

**Every seed gets the same work.** The chip holds a share of each
expert layer's experts (8 of 256: one of 32 shares), and how many of a
step's assignments a share gets is the seed's router's (the balancing
buffer ``e_score_correction_bias`` is 0), so a batch names the share the
chip plays in each expert layer (``first_experts``), chosen once a run
by ``even_shares`` from the router's loads alone, as
``benchmarks/tasks/gated_delta_lm.py`` chooses: this file's own walk of
its own reference over the pool's ``WALK_BATCHES`` batches, holding in
each expert layer the share that keeps every batch's held assignments
nearest the even load, in that layer and over the layers so far (the
least distance on the batch that lies farthest off). The router, its
scores and its choices are untouched; only which of the equal shares is
held.
"""

import copy
import functools
import json

import numpy as np

from benchmarks import weights
from benchmarks.harness import say
from benchmarks.layer_metrics import kda_costs as costs
from benchmarks.reference import kimi_linear_lm as ref
from benchmarks.tasks import causal_lm, hybrid_lm, program_kwargs

loss_sum = ref.loss_sum
tokens_per_row = causal_lm.tokens_per_row
# ``causal_lm``'s, each with its batch's ``first_experts``
reference_batches = hybrid_lm.reference_batches

# the last run's shares: a pool's batches are made one by one from one
# generator, and all of them carry what the first one chose
_shares = {}
# the batches ``even_shares`` walks: the pool of the cell's traffic
# (``pool_batches`` 8; steps 1 to 8 take them in order)
WALK_BATCHES = 8


def program_task(cfg: dict):
    from perceiver_tpu.tasks import HybridLMTask as cls

    return cls, program_kwargs(cls, cfg)


def make_batch(rng, rows: int, cfg: dict) -> dict:
    """ids from the (sliced) vocabulary (the next ids are the labels)
    and, where the chip holds a share of the experts, ``first_experts``
    (rows, expert layers): the share of each expert layer, the same in
    every row and every batch of a run."""
    ahead = copy.deepcopy(rng)   # the draws this and the next calls make
    batch = causal_lm.make_batch(rng, rows, cfg)
    held = cfg.get("held_experts")
    if held and held < cfg["n_routed_experts"]:
        # the run's seed: ``traffic.train_batches`` seeds the generator
        # with it, and the weights are ``weights.make_weights``'s of it
        seed = int(np.ravel(rng.bit_generator.seed_seq.entropy)[0])
        key = (seed, json.dumps(cfg, sort_keys=True))
        if key not in _shares:
            import jax

            cls, kwargs = program_task(cfg)
            shapes = jax.eval_shape(cls(**kwargs).build().init,
                                    jax.random.key(0))
            _shares.clear()
            _shares[key] = even_shares(
                weights.make_weights(shapes, seed),
                [causal_lm.make_batch(ahead, rows, cfg)["input_ids"]
                 for _ in range(WALK_BATCHES)], cfg)
        batch["first_experts"] = np.tile(_shares[key], (rows, 1))
    return batch


@functools.lru_cache(maxsize=2)
def _walk(frozen: str):
    """The two jitted steps of ``even_shares`` for a configuration (its
    JSON): a layer of the reference, and an expert layer's choices
    counted by share."""
    import jax

    cfg = json.loads(frozen)
    held = cfg["held_experts"]

    def share_loads(p, h):
        a = ref.rms_norm(p["norm"]["scale"], h, cfg["norm_eps"])
        chosen = ref.router_weights(
            p["mixer"], a.reshape(-1, a.shape[-1]), cfg, "f32") > 0
        return chosen.sum(0).reshape(-1, held).sum(-1)

    return jax.jit(
        lambda p, h, kind, first: ref.layer(p, h, first, kind=kind, cfg=cfg),
        static_argnums=2), jax.jit(share_loads)


def even_shares(params, pool_ids, cfg: dict) -> np.ndarray:
    """(expert layers,) int32: for each expert layer the first expert
    of the share that this run holds. The seed's weights ``params`` are
    taken through the reference's layers over each batch of
    ``pool_ids``; at each expert layer the router's choices are counted
    by share (``n_routed_experts / held_experts`` shares of neighbouring
    experts, a chip of the deployment each) on every batch. The share is
    held that keeps the batches' held assignments nearest to what an
    even router would have sent (``tokens x top_k x held / experts`` a
    layer), in this layer and over the layers so far: the least
    distance on the batch that lies farthest off in either. What a
    layer gives depends on the share it holds, so the layers are walked
    in order."""
    import jax.numpy as jnp

    held = cfg["held_experts"]
    layer, share_loads = _walk(json.dumps(cfg, sort_keys=True))
    pattern = cfg["hybrid_override_pattern"]
    states = [params["embed"]["embed"][jnp.asarray(ids)] for ids in pool_ids]
    even = np.size(pool_ids[0]) * cfg["num_experts_per_tok"] * held \
        / cfg["n_routed_experts"]
    firsts, totals = [], np.zeros(len(states))
    for name, kind in zip(ref.layer_names(cfg), pattern):
        p, first = params["layers"][name], None
        if kind == "E":
            loads = np.asarray([share_loads(p, h) for h in states],
                               np.float64)           # (batches, shares)
            off = np.maximum(
                np.abs(loads - even),
                np.abs(totals[:, None] + loads - even * (len(firsts) + 1)))
            share = int(np.argmin(off.max(0)))
            totals += loads[:, share]
            first = share * held
            firsts.append(first)
            say(f"expert layer {name}: of {loads.shape[1]} shares' "
                f"{loads.min():.0f} to {loads.max():.0f} assignments "
                f"(even {even:.0f}) held experts {first} to "
                f"{first + held - 1} with {loads[:, share].min():.0f} to "
                f"{loads[:, share].max():.0f} over {len(states)} batches; "
                f"{totals.min():.0f} to {totals.max():.0f} so far")
            if len(firsts) == pattern.count("E"):
                break   # nothing reads what the last expert layer gives
        states = [layer(p, h, kind, first) for h in states]
    return np.asarray(firsts, np.int32)


def forward_parts(cfg: dict) -> dict:
    """Forward matrix-product operations for one row, by part, by the
    rules at the head of ``benchmarks/flops.py``: a product 2 m n k; the
    causal scores S (S + 1) / 2 pairs a head, **at the published widths**
    (score heads of ``nope + rope``, value heads of ``v_head_dim``: lanes
    a kernel pads a head to are not work). The rule's products, the
    latent core's and the routed experts' are
    ``layer_metrics/kda_costs.py``'s, the same count their roofline
    readers take; the routed experts are counted at the **expected**
    share (top-k spread evenly over the router's experts, those held
    here their part): the real number moves with the router from step
    to step."""
    s, c = int(cfg["max_seq_len"]), int(cfg["hidden_size"])
    pattern = cfg["hybrid_override_pattern"]
    heads, d = int(cfg["kda_num_heads"]), int(cfg["kda_head_dim"])
    width = heads * d
    attn_heads = int(cfg["num_attention_heads"])
    rank, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    nope, value = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    shared = int(cfg["moe_shared_expert_intermediate_size"])
    return {
        # q, k, v, out; the decay's and the gate's two low-rank products;
        # beta
        "kda_projections": pattern.count("K") * s * 2.0 * (
            4 * c * width + 2 * (c * d + d * width) + c * heads),
        "kda_rule": pattern.count("K") * costs.rule_cost(
            cfg, 1, s, backward=False)[0],
        # q, the latent beside the shared key, keys and values from the
        # latent, out
        "latent_projections": pattern.count("A") * s * 2.0 * (
            c * attn_heads * (nope + rope) + c * (rank + rope)
            + rank * attn_heads * (nope + value) + attn_heads * value * c),
        "latent_attention": pattern.count("A") * costs.latent_core_cost(
            cfg, 1, s, backward=False)[0],
        "dense_mlp": pattern.count("D") * s * 2.0 * 3 * c * int(
            cfg["intermediate_size"]),
        # the router and the shared expert's three matrices
        "router_and_shared": pattern.count("E") * s * 2.0 * (
            c * int(cfg["n_routed_experts"]) + 3 * c * shared),
        "routed_experts": pattern.count("E") * costs.gated_grouped_cost(
            cfg, costs.expected_assignments(cfg, s), backward=False)[0],
        "head": s * 2.0 * c * int(cfg["vocab_size"]),
    }


def train_step_flops(cfg: dict, rows: int) -> float:
    """Forward plus backward of one step of ``rows`` rows: a product
    costs twice itself again in the backward pass; the embedding takes
    its gradient, so the first layer's input does too. Recomputation is
    not counted."""
    return rows * 3.0 * sum(forward_parts(cfg).values())
