"""Causal language model on a GLM-4.7-Flash stack (``HybridLMTask`` with
the pattern ``AEAEAEAE`` and a multi-token prediction module:
latent attention with a query latent and rotary positions on its shared
channels, sigmoid-routed gated experts beside a shared expert with no
gate column, and after the stack one more such layer pair that reads
the stack's state beside the next id's embedding and is read by the
stack's own head): full rows of Zipf-distributed ids over the
vocabulary the configuration holds (a slice of the published one), each
position labelled with the next id and, for the module, the id after
it; nothing is masked and nothing is drawn inside the step.

**The program has to know every key.** ``tasks.program_kwargs`` drops
the keys a task class does not take, and the pattern ``AEAEAEAE`` is
one that a program without the query latent, the rotary channels and
the module accepts too: it would train a stack that is another model
for minutes before its comparison fails. ``program_task`` raises at
once where the class lacks a key of the configuration.

**Every seed gets the same work.** The chip holds a share of each
expert layer's experts (8 of 64: one of 8 shares), and how many of a
step's assignments a share gets is the seed's router's (the balancing
buffer ``e_score_correction_bias`` is 0), so a batch names the share the
chip plays in each of the **five** expert layers (``first_experts``:
the stack's four, then the module's), chosen once a run by
``even_shares`` from the router's loads alone, as
``benchmarks/tasks/kimi_linear_lm.py`` chooses: this file's own walk of
its own reference over the pool's ``WALK_BATCHES`` batches, holding in
each expert layer the share that keeps every batch's held assignments
nearest the even load, in that layer and over the layers so far (the
least distance on the batch that lies farthest off). The router, its
scores and its choices are untouched; only which of the equal shares is
held.
"""

import copy
import dataclasses
import functools
import json

import numpy as np

from benchmarks import weights
from benchmarks.harness import BenchmarkError, say
from benchmarks.layer_metrics import kda_costs as costs
from benchmarks.reference import glm_moe_lite_lm as ref
from benchmarks.tasks import causal_lm, hybrid_lm, program_kwargs

loss_sum = ref.loss_sum
# input positions: the module's are the same positions read again
tokens_per_row = causal_lm.tokens_per_row
# ``causal_lm``'s, each with its batch's ``first_experts``
reference_batches = hybrid_lm.reference_batches

# the keys of the flat configuration that are the benchmark's own (its
# task file, the token ids the traffic draws from), not the program's
BENCHMARK_KEYS = ("task", "num_special_tokens")
# the last run's shares: a pool's batches are made one by one from one
# generator, and all of them carry what the first one chose
_shares = {}
# the batches ``even_shares`` walks: the pool of the cell's traffic
# (``pool_batches`` 8; steps 1 to 8 take them in order)
WALK_BATCHES = 8


def program_task(cfg: dict):
    from perceiver_tpu.tasks import HybridLMTask as cls

    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(cfg) - fields - set(BENCHMARK_KEYS))
    if unknown:
        raise BenchmarkError(
            f"the program's {cls.__name__} has no field for "
            f"{', '.join(unknown)} of the configuration: it would train "
            "another model under this one's name")
    return cls, program_kwargs(cls, cfg)


def make_batch(rng, rows: int, cfg: dict) -> dict:
    """ids from the (sliced) vocabulary (the next ids are the labels,
    the ids after those the module's) and, where the chip holds a share
    of the experts, ``first_experts`` (rows, expert layers): the share
    of each expert layer, the module's last, the same in every row and
    every batch of a run."""
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
    """The jitted steps of ``even_shares`` for a configuration (its
    JSON): a layer of the reference, an expert layer's choices counted
    by share, and the way from the stack's last layer into the
    module."""
    import jax
    import jax.numpy as jnp

    cfg = json.loads(frozen)
    held = cfg["held_experts"]

    def share_loads(p, h):
        a = ref.rms_norm(p["norm"]["scale"], h, cfg["norm_eps"])
        chosen = ref.router_weights(
            p["mixer"], a.reshape(-1, a.shape[-1]), cfg, "f32") > 0
        return chosen.sum(0).reshape(-1, held).sum(-1)

    def into_module(params, h, ids):
        state = ref.rms_norm(params["norm"]["scale"], h, cfg["norm_eps"])
        next_ids = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
        return ref.prediction_input(params, state, next_ids, cfg)

    return jax.jit(
        lambda p, h, kind, first: ref.layer(p, h, first, kind=kind, cfg=cfg),
        static_argnums=2), jax.jit(share_loads), jax.jit(into_module)


def even_shares(params, pool_ids, cfg: dict) -> np.ndarray:
    """(expert layers,) int32: for each expert layer, the module's
    last, the first expert of the share that this run holds. The seed's
    weights ``params`` are taken through the reference's layers over
    each batch of ``pool_ids``; at each expert layer the router's
    choices are counted by share (``n_routed_experts / held_experts``
    shares of neighbouring experts, a chip of the deployment each) on
    every batch. The share is held that keeps the batches' held
    assignments nearest to what an even router would have sent
    (``tokens x top_k x held / experts`` a layer), in this layer and
    over the layers so far: the least distance on the batch that lies
    farthest off in either. What a layer gives depends on the share it
    holds, so the layers are walked in order, on through the module."""
    import jax.numpy as jnp

    held = cfg["held_experts"]
    layer, share_loads, into_module = _walk(json.dumps(cfg, sort_keys=True))
    pool_ids = [jnp.asarray(ids) for ids in pool_ids]
    states = [params["embed"]["embed"][ids] for ids in pool_ids]
    even = np.size(pool_ids[0]) * cfg["num_experts_per_tok"] * held \
        / cfg["n_routed_experts"]
    walk = [(name, kind, params["layers"][name]) for name, kind in zip(
        ref.layer_names(cfg), cfg["hybrid_override_pattern"])]
    if cfg.get("num_nextn_predict_layers"):
        walk += [("mtp/mla", "A", params["mtp"]["mla"]),
                 ("mtp/moe", "E", params["mtp"]["moe"])]
    experts = sum(kind == "E" for _, kind, _ in walk)
    firsts, totals = [], np.zeros(len(states))
    for name, kind, p in walk:
        if name == "mtp/mla":
            states = [into_module(params, h, ids)
                      for h, ids in zip(states, pool_ids)]
        first = None
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
            if len(firsts) == experts:
                break   # nothing reads what the last expert layer gives
        states = [layer(p, h, kind, first) for h in states]
    return np.asarray(firsts, np.int32)


def forward_parts(cfg: dict) -> dict:
    """Forward matrix-product operations for one row, by part, by the
    rules at the head of ``benchmarks/flops.py``: a product 2 m n k; the
    causal scores S (S + 1) / 2 pairs a head at the published widths
    (score heads of ``nope + rope`` 256, value heads of 256: no lane is
    padding here). The stack's layer pairs and the prediction module's
    one are counted apart (``mtp_*``); the module reads the head a
    second time (``mtp_head``). The latent core's and the routed
    experts' are ``layer_metrics/kda_costs.py``'s; the routed experts
    are counted at the **expected** share (top-k spread evenly over the
    router's experts, those held here their part): the real number
    moves with the router from step to step."""
    s, c = int(cfg["max_seq_len"]), int(cfg["hidden_size"])
    pattern = cfg["hybrid_override_pattern"]
    modules = int(cfg.get("num_nextn_predict_layers", 0))
    heads = int(cfg["num_attention_heads"])
    rank, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    nope, value = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    q_rank = int(cfg["q_lora_rank"])
    shared = int(cfg["moe_shared_expert_intermediate_size"])
    # a layer's: the query latent and the queries from it, the latent
    # beside the shared key, keys and values from the latent, out
    projections = s * 2.0 * (
        c * q_rank + q_rank * heads * (nope + rope) + c * (rank + rope)
        + rank * heads * (nope + value) + heads * value * c)
    core = costs.latent_core_cost(cfg, 1, s, backward=False)[0]
    # the router and the shared expert's three matrices
    outside = s * 2.0 * (c * int(cfg["n_routed_experts"]) + 3 * c * shared)
    routed = costs.gated_grouped_cost(
        cfg, costs.expected_assignments(cfg, s), backward=False)[0]
    head = s * 2.0 * c * int(cfg["vocab_size"])
    return {
        "latent_projections": pattern.count("A") * projections,
        "latent_attention": pattern.count("A") * core,
        "router_and_shared": pattern.count("E") * outside,
        "routed_experts": pattern.count("E") * routed,
        "head": head,
        "mtp_eh_proj": modules * s * 2.0 * 2 * c * c,
        "mtp_latent_projections": modules * projections,
        "mtp_latent_attention": modules * core,
        "mtp_router_and_shared": modules * outside,
        "mtp_routed_experts": modules * routed,
        "mtp_head": modules * head,
    }


def train_step_flops(cfg: dict, rows: int) -> float:
    """Forward plus backward of one step of ``rows`` rows: a product
    costs twice itself again in the backward pass; the embedding takes
    its gradient, so the first layer's input does too. Recomputation is
    not counted."""
    return rows * 3.0 * sum(forward_parts(cfg).values())
