"""Plain reference for a block-diffusion mixture-of-experts language
model (SDAR-30B-A3B-Chat's ``config.json``, ``model_type: sdar_moe``: a
Qwen3-MoE layer; trained by the block-diffusion objective of BD3-LM,
arXiv:2503.09573, as SDAR, arXiv:2510.06303, adapts an autoregressive
model to it): forward pass, the weighted masked-token loss and, through
``reference.perceiver_io.train_steps``, gradients and three AdamW steps,
in float32 at ``highest`` matmul precision. It imports nothing of the
program and no kernel; weights come from ``benchmarks/weights.py`` in
the program's tree layout.

One layer, on ``h`` of ``P`` positions (RMSNorm eps ``rms_norm_eps``,
no bias anywhere, ``D`` the head size, query head ``i`` reads key/value
head ``i // (heads / kv_heads)``)::

    a = rms1(h);  q, k, v = a Wq, a Wk, a Wv
    q, k = rope(rms_q(q)), rope(rms_k(k))     rms over each head's D channels, one scale of D each
    o = softmax(q k^T / sqrt(D) + M) v;  h = h + o Wo
    m = rms2(h);  p = softmax(m Wr)           all num_experts experts
    chosen = top_k(p);  w_e = p_e / sum_chosen p
    h = h + sum over the chosen experts HELD HERE of w_e (silu(m Wg_e) * (m Wu_e)) Wd_e

    h0 = E[ids];  logits = rms_f(h_last) Wh   head untied

In the program's tree a published layer is two layers of the hybrid
stack, ``*`` (``rms1`` is its ``norm``) and ``E`` (``rms2`` is its
``norm``). The ``held_experts`` experts from ``first_expert`` on are
this chip's share (a batch may name each expert layer's,
``first_experts``): what the absent experts would add is left out, here
as in the program.

Training a row ``x`` of ``L`` tokens, block length ``B``::

    t_b ~ U(t_min, 1) a block;  m_i ~ Bernoulli(t_block(i));  xt_i = MASK if m_i else x_i
    the model runs [xt ; x], 2 L positions; index j has rotary position j mod L
    query j sees key l  iff  j <  L, l <  L, block(j) == block(l)
                         or  j <  L, l >= L, block(l - L) <  block(j)
                         or  j >= L, l >= L, block(l - L) <= block(j - L)
    loss = (1 / (rows L)) sum_{i < L, m_i} (1 / t_block(i)) (-log softmax(logits_i)[x_i])

``block_noise`` re-derives a step's ``t`` and ``m`` from the step's key
by the program's own draws (a uniform a block, a uniform a position):
the one thing taken from the program is that order of draws, as
``mlm_mask`` takes the masked LM's.

Departures from ``perceiver_tpu/models/hybrid_lm.py`` and
``tasks/block_diffusion_lm.py``, and why:

* float32 everywhere, every matrix product through ``matmul`` at
  ``Precision.HIGHEST``; ``prec`` lowers the operands there and nowhere
  else, so the control shares every other line;
* the mask **materialised** from the three rules, a ``(2 L, 2 L)``
  boolean, and attention as a full masked softmax, one query head and
  ``QUERY_BLOCK`` queries at a time (a row's scores are ``8192 x
  8192`` float32 a head, 268 MB: the program runs fused kernels that
  skip the tiles no query sees, on keys and values repeated to the
  query heads);
* the experts as a **masked sum over the held experts**: every held
  expert multiplies every token and a weight that is 0 where the token
  did not choose it scales the result (the program sorts the
  assignments and multiplies each expert by its own rows);
* the head over every position of the noised half, ``LOGIT_CHUNK`` at
  a time, the weights 0 where a position is not masked (the program
  reads the same half);
* each layer is a ``jax.checkpoint`` so that it fits beside five
  parameter trees. Same mathematics, smaller live set.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.hybrid_lm import nll, rms_norm
from benchmarks.reference.perceiver_io import matmul

QUERY_BLOCK = 2048
KINDS = {"*": "attn", "E": "moe"}


def layer_names(cfg):
    """The program's tree: ``00_attn``, ``01_moe``, ... two a published
    layer."""
    return [f"{i:02d}_{KINDS[kind]}"
            for i, kind in enumerate("*E" * cfg["num_hidden_layers"])]


def visible(half: int, block: int):
    """(2 L, 2 L) bool: query ``j`` (a row) sees key ``l`` (a column),
    by the three rules."""
    j = jnp.arange(2 * half)[:, None]
    l = jnp.arange(2 * half)[None, :]
    own = (j < half) & (l < half) & (j // block == l // block)
    before = (j < half) & (l >= half) & ((l - half) // block < j // block)
    clean = (j >= half) & (l >= half) \
        & ((l - half) // block <= (j - half) // block)
    return own | before | clean


def rope(x, positions, theta: float):
    """Rotary embedding of ``x`` (B, S, H, D) at ``positions`` (S,):
    channel ``i`` and ``i + D/2`` turn by ``positions * theta^(-2i/D)``."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# --- * -----------------------------------------------------------------------


def attention_layer(p, a, cfg, prec):
    rows, seq, _ = a.shape
    half = seq // 2
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    positions = jnp.arange(seq) % half
    q = matmul("bsi,io->bso", a, p["q"]["w"], prec).reshape(
        rows, seq, heads, d)
    k, v = (matmul("bsi,io->bso", a, p[n]["w"], prec).reshape(
        rows, seq, kv_heads, d) for n in ("k", "v"))
    q = rope(rms_norm(p["q_norm"]["scale"], q, eps), positions,
             cfg["rope_theta"])
    k = rope(rms_norm(p["k_norm"]["scale"], k, eps), positions,
             cfg["rope_theta"])
    sees = visible(half, cfg["block_length"])
    block = math.gcd(seq, QUERY_BLOCK)
    blocks = seq // block

    @jax.checkpoint
    def one_block(args):
        qb, index = args                                   # (B, block, D)
        head, first = index // blocks, (index % blocks) * block
        kh, vh = (jnp.take(x, head // (heads // kv_heads), axis=2)
                  for x in (k, v))
        scores = matmul("bqd,bkd->bqk", qb / math.sqrt(d), kh, prec)
        rows_seen = jax.lax.dynamic_slice_in_dim(sees, first, block, axis=0)
        w = jax.nn.softmax(jnp.where(rows_seen, scores, -1e30), axis=-1)
        return matmul("bqk,bkd->bqd", w, vh, prec)

    # (heads x blocks, B, block, D), a head's blocks side by side
    qs = jnp.moveaxis(q.reshape(rows, blocks, block, heads, d), (3, 1),
                      (0, 1)).reshape(heads * blocks, rows, block, d)
    o = jax.lax.map(one_block, (qs, jnp.arange(heads * blocks)))
    o = jnp.moveaxis(o.reshape(heads, blocks, rows, block, d), (0, 1),
                     (3, 1)).reshape(rows, seq, heads * d)
    return matmul("bsi,io->bso", o, p["out"]["w"], prec)


# --- E -----------------------------------------------------------------------


def gated_mlp(gate, up, down, a, prec):
    hidden = jax.nn.silu(matmul("ti,io->to", a, gate, prec)) \
        * matmul("ti,io->to", a, up, prec)
    return matmul("ti,io->to", hidden, down, prec)


def router_weights(p, a, cfg, prec):
    """(T, num_experts): the weight of every expert for every token, 0
    where the token did not choose it: a softmax over all the experts,
    the top ``num_experts_per_tok`` over their sum."""
    scores = jax.nn.softmax(
        matmul("tc,ce->te", a, p["router"]["w"], prec), axis=-1)
    k = cfg["num_experts_per_tok"]
    kth = jnp.sort(scores, axis=-1)[:, -k][:, None]
    picked = jnp.where(scores >= kth, scores, 0.0)
    if cfg.get("norm_topk_prob", True):
        picked = picked / picked.sum(-1, keepdims=True)
    return picked


def expert_layer(p, a, cfg, prec, first=None):
    """``first``: the first expert held (an int or a traced scalar);
    None: the configuration's."""
    shape = a.shape
    a = a.reshape(-1, shape[-1])
    if first is None:
        first = cfg.get("first_expert", 0)
    experts = p["experts"]
    held = experts["up"]["w"].shape[0]
    weights = jax.lax.dynamic_slice_in_dim(
        router_weights(p, a, cfg, prec), first, held, axis=1)

    @jax.checkpoint
    def one_expert(total, expert):
        gate, up, down, w = expert
        return total + w[:, None] * gated_mlp(gate, up, down, a, prec), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(a),
        (experts["gate"]["w"], experts["up"]["w"], experts["down"]["w"],
         weights.T))
    return routed.reshape(shape)


# --- the stack and the loss --------------------------------------------------


def layer(p, h, first, *, kind, cfg, prec="f32"):
    """``h + mixer(rms(h))`` of one of the program's layers: ``*`` the
    attention half of a published layer, ``E`` its expert half
    (``first``: its first held expert; None: the configuration's)."""
    a = rms_norm(p["norm"]["scale"], h, cfg["rms_norm_eps"])
    if kind == "E":
        return h + expert_layer(p["mixer"], a, cfg, prec, first)
    return h + attention_layer(p["mixer"], a, cfg, prec)


def final_state(params, ids, cfg, prec="f32", first_experts=None):
    """The normed state the head reads, (B, 2 L, C), of rows ``ids``
    (B, 2 L): the noised copy beside the clean one. ``first_experts``
    (layers,) int32: each expert layer's first held expert, in the
    configuration's place."""
    h = params["embed"]["embed"][ids]
    firsts = iter(() if first_experts is None else first_experts)
    for name in layer_names(cfg):
        kind = "E" if name.endswith("moe") else "*"
        h = jax.checkpoint(functools.partial(
            layer, kind=kind, cfg=cfg, prec=prec))(
                params["layers"][name], h,
                next(firsts, None) if kind == "E" else None)
    return rms_norm(params["norm"]["scale"], h, cfg["rms_norm_eps"])


def logits(params, ids, cfg, prec="f32", first_experts=None):
    """Dense (B, 2 L, V): for the tests at a toy size."""
    return matmul("bsc,cv->bsv",
                  final_state(params, ids, cfg, prec, first_experts),
                  params["head"]["w"], prec)


def block_noise(key, ids, cfg):
    """``(noised ids, weights)`` of one step, re-derived from the
    step's key (``reference.perceiver_io.trainer_step_keys``): the key
    split in two, a uniform in ``[t_min, 1)`` a row and block from the
    first half, a uniform a position from the second, masked where it
    lies under its block's ``t``; the weights ``1 / t`` at the masked
    positions and 0 elsewhere."""
    rows, seq = ids.shape
    block = cfg["block_length"]
    k_t, k_m = jax.random.split(key)
    t = jnp.repeat(jax.random.uniform(
        k_t, (rows, seq // block), jnp.float32, cfg["t_min"], 1.0),
        block, axis=1)
    masked = jax.random.uniform(k_m, (rows, seq), jnp.float32) < t
    return (jnp.where(masked, jnp.asarray(cfg["mask_token_id"], ids.dtype),
                      ids),
            jnp.where(masked, 1.0 / t, 0.0))


def loss_sum(params, batch, cfg, prec):
    """(sum over the masked positions of ``1 / t`` times the NLL of the
    token at its own position, rows x L); ``batch`` holds ``input_ids``
    (the clean rows), ``noised_ids``, ``weights`` (``block_noise``'s)
    and may hold ``first_experts``."""
    ids = batch["input_ids"]
    seq = ids.shape[1]
    firsts = batch.get("first_experts")
    state = final_state(
        params, jnp.concatenate([batch["noised_ids"], ids], axis=1), cfg,
        prec, None if firsts is None else firsts[0])
    return (nll(params, state[:, :seq], ids, prec)
            * batch["weights"]).sum(), jnp.float32(ids.size)
