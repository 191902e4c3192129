"""Causal language model on a hybrid state-space / mixture-of-experts
stack (``HybridLMTask``): full rows of Zipf-distributed ids over the
vocabulary the configuration holds (a slice of the published one), each
position labelled with the next id; nothing is masked and nothing is
drawn inside the step.

**Every seed gets the same work.** The chip holds a share of each
expert layer's experts, and how many of a step's assignments a share
gets is the seed's router's (``e_score_correction_bias`` is 0: the
shares of an untrained router get 0.6 to 1.8 times the even load), so
a cell that always played the first share would do another amount of
work on every seed. A batch therefore names the share the chip plays in
each expert layer (``first_experts``), chosen once a run by
``even_shares``: of the deployment's chips, the one whose experts get
the even load on the run's first batch. The router, its scores and its
choices are untouched; only which of the equal shares is held.
"""

import functools
import json

import numpy as np

from benchmarks import flops, weights
from benchmarks.harness import say
from benchmarks.layer_metrics import hybrid_costs
from benchmarks.reference import hybrid_lm as ref
from benchmarks.tasks import causal_lm, program_kwargs

loss_sum = ref.loss_sum
tokens_per_row = causal_lm.tokens_per_row

# the last run's shares: a pool's batches are made one by one from one
# generator, and all of them carry what the first one chose
_shares = {}


def program_task(cfg: dict):
    from perceiver_tpu.tasks import HybridLMTask as cls

    return cls, program_kwargs(cls, cfg)


def make_batch(rng, rows: int, cfg: dict) -> dict:
    """ids from the (sliced) vocabulary (the next ids are the labels)
    and, where the chip holds a share of the experts, ``first_experts``
    (rows, expert layers): the share of each expert layer, the same in
    every row and every batch of a run."""
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
            _shares[key] = even_shares(weights.make_weights(shapes, seed),
                                       batch["input_ids"], cfg)
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


def even_shares(params, ids, cfg: dict) -> np.ndarray:
    """(expert layers,) int32: for each expert layer the first expert
    of the share that this run holds. The seed's weights ``params`` are
    taken through the reference's layers over ``ids``; at each expert
    layer the router's choices are counted by share (``n_routed_experts /
    held_experts`` shares of neighbouring experts, a chip of the
    deployment each), and the share is held that brings the held
    assignments so far nearest to what an even router would have sent
    (``tokens x top_k x held / experts`` a layer). What a layer gives
    depends on the share it holds, so the layers are walked in order."""
    import jax.numpy as jnp

    held = cfg["held_experts"]
    even = np.size(ids) * cfg["num_experts_per_tok"] * held \
        / cfg["n_routed_experts"]
    layer, share_loads = _walk(json.dumps(cfg, sort_keys=True))
    pattern = cfg["hybrid_override_pattern"]
    h = params["embed"]["embed"][jnp.asarray(ids)]
    firsts, total = [], 0.0
    for name, kind in zip(ref.layer_names(cfg), pattern):
        p, first = params["layers"][name], None
        if kind == "E":
            loads = np.asarray(share_loads(p, h), np.float64)
            share = int(np.argmin(
                np.abs(total + loads - even * (len(firsts) + 1))))
            total += loads[share]
            first = share * held
            firsts.append(first)
            say(f"expert layer {name}: of {len(loads)} shares' "
                f"{loads.min():.0f} to {loads.max():.0f} assignments "
                f"(even {even:.0f}) held experts {first} to "
                f"{first + held - 1} with {loads[share]:.0f}")
            if len(firsts) == pattern.count("E"):
                break   # nothing reads what the last expert layer gives
        h = layer(p, h, kind, first)
    return np.asarray(firsts, np.int32)


def reference_batches(pool, cfg: dict, trainer_seed: int, steps: int):
    """``causal_lm``'s, each with its batch's ``first_experts``."""
    import jax.numpy as jnp

    out = causal_lm.reference_batches(pool, cfg, trainer_seed, steps)
    for batch, made in zip(out, pool):
        if "first_experts" in made:
            batch["first_experts"] = jnp.asarray(made["first_experts"])
    return out


def forward_parts(cfg: dict) -> dict:
    """Forward matrix-product operations for one row, by part, by the
    rules at the head of ``benchmarks/flops.py``: a product 2 m n k; the
    causal scores S (S + 1) / 2 pairs a head. The scan's products and
    the routed experts' are ``layer_metrics/hybrid_costs.py``'s, the
    same count its roofline readers take; the routed experts are
    counted at the **expected** share (top-k spread evenly over the
    router's experts, those held here their part): the real number
    moves with the router from step to step."""
    s, c = int(cfg["max_seq_len"]), int(cfg["hidden_size"])
    pattern = cfg["hybrid_override_pattern"]
    heads, width = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, state = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    inner = heads * width
    in_width = 2 * inner + 2 * groups * state + heads
    attn_width = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    kv_width = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    return {
        "ssm_projections": pattern.count("M") * s * 2.0 * (
            c * in_width + inner * c),
        "ssm_scan": pattern.count("M") * hybrid_costs.scan_cost(
            cfg, 1, s, backward=False)[0],
        "router_and_shared": pattern.count("E") * s * 2.0 * (
            c * int(cfg["n_routed_experts"])
            + 2 * c * int(cfg["moe_shared_expert_intermediate_size"])),
        "routed_experts": pattern.count("E") * hybrid_costs.grouped_cost(
            cfg, hybrid_costs.expected_assignments(cfg, s),
            backward=False)[0],
        "attention_projections": pattern.count("*") * s * 2.0 * (
            2 * c * attn_width + 2 * c * kv_width),
        "causal_attention": pattern.count("*") * flops.flash_attention_cost(
            1, s, s, attn_width, backward=False, causal=True)[0],
        "head": s * 2.0 * c * int(cfg["vocab_size"]),
    }


def train_step_flops(cfg: dict, rows: int) -> float:
    """Forward plus backward of one step of ``rows`` rows: a product
    costs twice itself again in the backward pass; the embedding takes
    its gradient, so the first layer's input does too. Recomputation is
    not counted."""
    return rows * 3.0 * sum(forward_parts(cfg).values())
