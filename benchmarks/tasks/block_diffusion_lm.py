"""Block-diffusion language model on a stack of Qwen3-MoE layers
(``BlockDiffusionLMTask``): full rows of Zipf-distributed ids over the
vocabulary the configuration holds (a slice of the published one) less
the mask id; the program noises each row inside its step from the
step's key and runs the noised copy beside the clean one, so a row of
``max_seq_len`` data tokens is ``2 x max_seq_len`` positions of work.
``tokens_per_row`` counts the data tokens: the noised copy is the
method's cost and is not counted twice.

**Every seed gets the same work.** The chip holds a share of each
expert layer's experts, and how many of a step's assignments a share
gets is the seed's router's. An untrained softmax router is far from
balanced: the states share a direction, so nearly every token of a
layer chooses the same eight experts, and a share's load is about a
whole step's positions for each of those it holds (176 to 41,952
assignments a share in one layer of one seed, 16,384 if even; 8 k to
27 k for one share over a pool's batches; my chip runs, PR 37). A cell
that always played the first share would do another amount of work on
every seed, so a batch names the share the chip plays in each expert
layer (``first_experts``), chosen once a run by ``even_shares`` from
the router's loads alone: this file's own walk of its own reference
over the pool's ``WALK_BATCHES`` batches, each noised as the trainer's
step that first takes it will noise it, holding in each expert layer
the share that keeps every batch's held assignments nearest the even
load, in that layer and over the layers so far (the least distance on
the batch that lies farthest off).
The router, its scores and its choices are untouched; only which of
the equal shares is held. **The expert traffic is the untrained
router's**: one or two favoured experts of a held share take a step's
positions each (``moe_load_max_over_mean`` 12 to 16 of a possible 16),
the other held experts next to nothing, so the grouped products see a
long group or two and not sixteen of 1,024 rows.
"""

import copy
import functools
import json

import numpy as np

from benchmarks import traffic, weights
from benchmarks.harness import say
from benchmarks.layer_metrics import block_diffusion_costs as costs
from benchmarks.reference import block_diffusion_lm as ref
from benchmarks.reference.perceiver_io import trainer_step_keys
from benchmarks.tasks import program_kwargs

loss_sum = ref.loss_sum

# the last run's shares: a pool's batches are made one by one from one
# generator, and all of them carry what the first one chose
_shares = {}
# the batches ``even_shares`` walks: the pool of the cell's traffic
# (``pool_batches`` 8; steps 1 to 8 take them in order)
WALK_BATCHES = 8


def program_task(cfg: dict):
    from perceiver_tpu.tasks import BlockDiffusionLMTask as cls

    return cls, program_kwargs(cls, cfg)


def tokens_per_row(cfg: dict) -> int:
    """Data tokens, as ``train_tokens_per_s`` counts them: what the
    user pays for. The stack sees twice as many positions."""
    return int(cfg["max_seq_len"])


def _ids(rng, rows: int, cfg: dict) -> np.ndarray:
    return traffic.zipf_ids(rng, cfg["vocab_size"],
                            cfg["num_special_tokens"],
                            (rows, cfg["max_seq_len"]))


def make_batch(rng, rows: int, cfg: dict) -> dict:
    """ids from the (sliced) vocabulary above the mask id and, where
    the chip holds a share of the experts, ``first_experts`` (rows,
    layers): the share of each expert layer, the same in every row and
    every batch of a run."""
    ahead = copy.deepcopy(rng)   # the draws the next calls will make
    batch = {"input_ids": _ids(rng, rows, cfg),
             "valid": np.ones(rows, bool)}
    held = cfg.get("held_experts")
    if held and held < cfg["num_experts"]:
        # the run's seed: ``traffic.train_batches`` seeds the generator
        # with it, the weights are ``weights.make_weights``'s of it and
        # the trainer's seed ``weights.seed31``'s
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
                [_ids(ahead, rows, cfg) for _ in range(WALK_BATCHES)], cfg,
                trainer_step_keys(weights.seed31(seed), WALK_BATCHES))
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
        a = ref.rms_norm(p["norm"]["scale"], h, cfg["rms_norm_eps"])
        chosen = ref.router_weights(
            p["mixer"], a.reshape(-1, a.shape[-1]), cfg, "f32") > 0
        return chosen.sum(0).reshape(-1, held).sum(-1)

    return jax.jit(
        lambda p, h, kind, first: ref.layer(p, h, first, kind=kind, cfg=cfg),
        static_argnums=2), jax.jit(share_loads)


def even_shares(params, pool_ids, cfg: dict, step_keys) -> np.ndarray:
    """(layers,) int32: for each expert layer the first expert of the
    share that this run holds. The seed's weights ``params`` are taken
    through the reference's layers over each batch of ``pool_ids``
    noised with its own key of ``step_keys`` (the trainer's first
    steps'), the noised copy beside the clean one; at each expert layer
    the router's choices are counted by share (``num_experts /
    held_experts`` shares of neighbouring experts, a chip of the
    deployment each) on every batch. The share is held that keeps the
    batches' held assignments nearest to what an even router would
    have sent (``positions x top_k x held / experts`` a layer), in
    this layer and over the layers so far: the least distance on the
    batch that lies farthest off in either, so that no layer and no
    step of the pool does much more or less than another seed's. What
    a layer gives depends on the share it holds, so the layers are
    walked in order."""
    import jax.numpy as jnp

    held = cfg["held_experts"]
    layer, share_loads = _walk(json.dumps(cfg, sort_keys=True))
    states = []
    for ids, key in zip(pool_ids, step_keys):
        ids = jnp.asarray(ids)
        both = jnp.concatenate([ref.block_noise(key, ids, cfg)[0], ids], 1)
        states.append(params["embed"]["embed"][both])
    even = both.size * cfg["num_experts_per_tok"] * held / cfg["num_experts"]
    firsts, totals = [], np.zeros(len(states))
    for name in ref.layer_names(cfg):
        p, first, kind = params["layers"][name], None, "*"
        if name.endswith("moe"):
            kind = "E"
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
            if len(firsts) == cfg["num_hidden_layers"]:
                break   # nothing reads what the last expert layer gives
        states = [layer(p, h, kind, first) for h in states]
    return np.asarray(firsts, np.int32)


def reference_batches(pool, cfg: dict, trainer_seed: int, steps: int):
    """The first ``steps`` batches with each step's noise applied,
    re-derived from the trainer's seed: the clean rows, the noised
    rows, the ``1 / t`` weights of the masked positions and the batch's
    ``first_experts``."""
    import jax.numpy as jnp

    out = []
    for key, b in zip(trainer_step_keys(trainer_seed, steps), pool):
        ids = jnp.asarray(b["input_ids"])
        noised, w = ref.block_noise(key, ids, cfg)
        batch = {"input_ids": ids, "noised_ids": noised, "weights": w}
        if "first_experts" in b:
            batch["first_experts"] = jnp.asarray(b["first_experts"])
        out.append(batch)
    return out


def forward_parts(cfg: dict) -> dict:
    """Forward matrix-product operations for one row, by part, by the
    rules at the head of ``benchmarks/flops.py``: a product 2 m n k over
    the ``2 L`` positions the stack sees; the masked core at the pairs a
    query may see (``block_diffusion_costs.visible_pairs``); the routed
    experts' three products at the **expected** share (top-k spread
    evenly over the router's experts, those held here their part); the
    head at the **expected masked positions**, ``L (1 + t_min) / 2`` a
    row, whatever the program computes: a program that packs the head
    then reads as faster, not as doing less."""
    s, c = int(cfg["max_seq_len"]), int(cfg["hidden_size"])
    layers, positions = int(cfg["num_hidden_layers"]), 2 * s
    attn_width = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    kv_width = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    return {
        "attention_projections": layers * positions * 2.0 * (
            2 * c * attn_width + 2 * c * kv_width),
        "block_diffusion_attention": layers * costs.attention_cost(
            1, s, int(cfg["block_length"]), attn_width, backward=False)[0],
        "router": layers * positions * 2.0 * c * int(cfg["num_experts"]),
        "routed_experts": layers * costs.gated_grouped_cost(
            cfg, costs.expected_assignments(cfg, positions),
            backward=False)[0],
        "head": s * (1.0 + float(cfg["t_min"])) / 2.0 * 2.0 * c
        * int(cfg["vocab_size"]),
    }


def train_step_flops(cfg: dict, rows: int) -> float:
    """Forward plus backward of one step of ``rows`` rows: a product
    costs twice itself again in the backward pass; the embedding takes
    its gradient, so the first layer's input does too. Recomputation is
    not counted."""
    return rows * 3.0 * sum(forward_parts(cfg).values())
