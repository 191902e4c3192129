"""Plain reference for a GLM-4.7-Flash language model (``config.json``
of zai-org/GLM-4.7-Flash, ``model_type: glm4_moe_lite``; the layer is
DeepSeek-V3's, arXiv:2412.19437, sections 2.1 and 2.2; the GLM-4.5
report, arXiv:2508.06471, describes the family's multi-token prediction
layer): forward pass, the next-token loss, the multi-token prediction
loss, their weighted sum and, through
``reference.perceiver_io.train_steps``, gradients and three AdamW
steps, in float32 at ``highest`` matmul precision. It imports nothing
of the program and no kernel; weights come from ``benchmarks/weights.py``
in the program's tree layout.

A published layer (``C`` the hidden size, eps ``norm_eps``, no bias in
any linear layer, every RMSNorm in the scale form)::

    h = h + A(rms(h; w1));  h = h + E(rms(h; w2))
    rms(x; w) = x / sqrt(mean(x^2) + eps) * w
    h0 = E[ids];  x = rms(h_last; w_f);  logits = x Wh      (head untied)

In the program's tree a published layer is two layers of the hybrid
stack, ``A`` (``w1`` is its ``norm``) and ``E`` (``w2``); the cell's
stack is ``AEAEAEAE``, published layers 44 to 47 (the leading dense
layer lies on the first pipeline stage and is not here).

``A``, latent attention with a query latent and decoupled rotary
positions (``H`` heads; ``n`` = ``qk_nope_head_dim``, ``r`` =
``qk_rope_head_dim``, ``e`` = ``v_head_dim``, ``L`` = ``kv_lora_rank``,
``Q`` = ``q_lora_rank``)::

    c_q = rms(a W_qa; w_q)            (Q);  q = c_q W_qb
                                      (a head's n + r channels: [q_n | q_r])
    [c | k_s] = a W_kva               (L | r);  c' = rms(c; w_c)
    [k_n | v] = c' W_kvb              (a head's n | e)
    q_r <- R_t q_r a head;  k_s <- R_t k_s     (once: every head reads it)
    R_t: the pair (j, j + r/2) of the r channels turned by the angle
         t theta^(-2j/r), t the position, theta = rope_theta
    scores_h = (q_n,h k_n,h^T + q_r,h k_s^T) / sqrt(n + r)
    out = causal_softmax(scores) v  W_o

computed here **from the latent**: ``q_n,h k_n,h^T = (q_n,h Wk_h^T) c'^T``
and ``softmax v_h = (softmax c') Wv_h``, with ``Wk_h`` (L x n) and
``Wv_h`` (L x e) the head's columns of ``W_kvb``, so no head's keys or
values are formed. With ``q_lora_rank`` 0 the query is ``a W_q``; with
``rope_theta`` None nothing turns.

``E``, the experts (router over all ``n_routed_experts``, top
``num_experts_per_tok``; ``n_group`` 1 and ``topk_group`` 1 make the
grouped top-k the plain one)::

    s = sigmoid(a W_r);  chosen = top_k(s)     (e_score_correction_bias at 0)
    w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
    out = sum over the chosen experts HELD HERE of w_e (silu(a Wg_e) * (a Wu_e)) Wd_e
          + (silu(a Wg_s) * (a Wu_s)) Wd_s      (the shared expert: no gate column)

(``reference.kimi_linear_lm.expert_layer``: the same layer.) The
``held_experts`` experts from ``first_expert`` on are this chip's share
(a batch names each expert layer's, ``first_experts``, the prediction
module's last): what the absent experts would add is left out, here as
in the program; the shared expert is whole.

The multi-token prediction module, depth 1 (``t`` a row's ids, ``x`` the
stack's final-normed state, position ``i``)::

    u_i = [rms(E[t_(i+1)]; w_e) | rms(x_i; w_h)] W_eh      (W_eh: 2C x C)
    y = u + A(rms(u));  y = y + E_xp(rms(y));  z = rms(y; w_o)
    logits2_i = z_i Wh                     (the same E, the same head Wh)
    L1 = mean over the labelled i of -log softmax(logits_i)[t_(i+1)]
    L2 = mean over the i with t_(i+2) of -log softmax(logits2_i)[t_(i+2)]
    loss = L1 + mtp_loss_weight L2

``A`` and ``E_xp`` are one layer pair of the kinds above with the
module's own weights, position ``i``'s rotation and the module's own
share of the experts; no gradient is stopped anywhere.

Departures from the published description, and why:

* the rotation couples the channels ``(j, j + r/2)`` of the rope part,
  where the published code de-interleaves ``(2j, 2j + 1)`` into those
  halves first: a permutation of the columns of ``W_qb`` and ``W_kva``,
  which are random here;
* no auxiliary balancing loss, and the balancing buffer
  ``e_score_correction_bias`` at 0 and not read: ``config.json`` gives
  neither a value;
* the module, where ``config.json`` gives its depth alone: the stack's
  state is taken after the final norm and the module's result gets a
  norm of its own before the head (the published checkpoints' extra
  layer holds ``enorm``, ``hnorm``, ``eh_proj``, a whole decoder layer
  and ``shared_head.norm``); the embedding comes first in the
  concatenation (DeepSeek-V3's equation 21 writes it second: a
  permutation of the rows of ``W_eh``); the weight 0.3 is both reports'
  for most of pretraining; ``L2`` is a mean over the positions that
  have a target (the paper divides by the row's length).

Departures from ``perceiver_tpu/models/hybrid_lm.py`` and
``tasks/hybrid_lm.py``, and why:

* float32 everywhere, every matrix product through ``matmul`` at
  ``Precision.HIGHEST``; ``prec`` lowers the operands there and nowhere
  else, so the control shares every other line;
* latent attention from the latent, a full masked softmax one head at
  a time (the program expands keys and values to the heads and runs
  fused causal kernels on heads of 256 lanes); the rotation written out
  from the angles' ``cos`` and ``sin`` (the program multiplies by two
  tables and a half-swapped copy);
* the experts as a masked sum over the held experts, one at a time (the
  program sorts the assignments and multiplies each expert by its own
  rows);
* each layer is a ``jax.checkpoint`` so that it fits beside five
  parameter trees; both readings of the head go over the positions in
  chunks of dense logits (``reference.hybrid_lm.nll``; the program
  never forms them);
* ``loss_sum`` keeps ``train_steps``' contract of one ``(sum, count)`` a
  block of rows: it gives ``(s1 + weight s2 n1 / n2, n1)`` with ``s``
  and ``n`` each loss's sum and count in the block. With full rows
  ``n1 / n2`` is ``(S - 1) / (S - 2)`` in every block, so the blocks'
  sums over the blocks' counts are ``L1 + weight L2`` exactly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.hybrid_lm import (  # noqa: F401
    nll,
    rms_norm,
    router_weights,
)
from benchmarks.reference.kimi_linear_lm import expert_layer
from benchmarks.reference.perceiver_io import IGNORE, matmul

KINDS = {"A": "mla", "E": "moe"}


def layer_names(cfg):
    return [f"{i:02d}_{KINDS[kind]}"
            for i, kind in enumerate(cfg["hybrid_override_pattern"])]


# --- A -----------------------------------------------------------------------


def rotate(x, theta: float):
    """``R_t x_t``: x (..., S, r), position ``t`` the last axis but one;
    the pair ``(j, j + r/2)`` turned by ``t theta^(-2j/r)``. The angles
    are made in float64 and rounded once."""
    seq, width = x.shape[-2:]
    angles = np.arange(seq, dtype=np.float64)[:, None] * float(theta) ** (
        -2.0 * np.arange(width // 2, dtype=np.float64) / width)
    cos, sin = (jnp.asarray(f(angles), jnp.float32)
                for f in (np.cos, np.sin))
    x1, x2 = x[..., :width // 2], x[..., width // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_attention(p, a, cfg, prec):
    rows, seq, _ = a.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg.get("rope_theta")
    if cfg.get("q_lora_rank"):
        q = matmul("bsq,qo->bso", rms_norm(
            p["q_a_norm"]["scale"],
            matmul("bsi,iq->bsq", a, p["q_a"]["w"], prec), cfg["norm_eps"]),
            p["q_b"]["w"], prec)
    else:
        q = matmul("bsi,io->bso", a, p["q"]["w"], prec)
    q = jnp.moveaxis(q.reshape(rows, seq, heads, nope + rope), 2, 0)
    kva = matmul("bsi,io->bso", a, p["kv_a"]["w"], prec)
    latent = rms_norm(p["kv_norm"]["scale"], kva[..., :rank],
                      cfg["norm_eps"])
    shared = kva[..., rank:]                               # (B, S, r)
    q_n, q_r = q[..., :nope], q[..., nope:]                # (H, B, S, .)
    if theta is not None:
        q_r, shared = rotate(q_r, theta), rotate(shared, theta)
    kv_b = p["kv_b"]["w"].reshape(rank, heads, -1)         # (L, H, n + e)
    visible = jnp.tril(jnp.ones((seq, seq), bool))
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def one_head(args):
        qn, qr, w = args                # (B, S, n), (B, S, r), (L, n + e)
        in_latent = matmul("bqn,ln->bql", qn, w[:, :nope], prec)
        scores = (matmul("bql,bkl->bqk", in_latent, latent, prec)
                  + matmul("bqr,bkr->bqk", qr, shared, prec)) * scale
        weights = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return matmul("bql,le->bqe",
                      matmul("bqk,bkl->bql", weights, latent, prec),
                      w[:, nope:], prec)

    o = jax.lax.map(one_head, (q_n, q_r, jnp.moveaxis(kv_b, 1, 0)))
    return matmul("bsi,io->bso",
                  jnp.moveaxis(o, 0, 2).reshape(rows, seq, -1),
                  p["out"]["w"], prec)


# --- the stack, the module and the losses ------------------------------------


def layer(p, h, first, *, kind, cfg, prec="f32"):
    """``h + mixer(rms(h))`` of one of the program's layers; ``first``
    is an expert layer's first held expert (None: the configuration's,
    and in ``A``)."""
    a = rms_norm(p["norm"]["scale"], h, cfg["norm_eps"])
    if kind == "E":
        return h + expert_layer(p["mixer"], a, cfg, prec, first)
    return h + latent_attention(p["mixer"], a, cfg, prec)


def _checkpointed(kind, cfg, prec):
    return jax.checkpoint(functools.partial(
        layer, kind=kind, cfg=cfg, prec=prec))


def final_state(params, ids, cfg, prec="f32", first_experts=None):
    """The normed state the head reads, (B, S, C). ``first_experts``
    (expert layers,) int32: each expert layer's first held expert, in
    the configuration's place (the stack takes its own from the
    front)."""
    h = params["embed"]["embed"][ids]
    firsts = iter(() if first_experts is None else first_experts)
    for name, kind in zip(layer_names(cfg), cfg["hybrid_override_pattern"]):
        h = _checkpointed(kind, cfg, prec)(
            params["layers"][name], h,
            next(firsts, None) if kind == "E" else None)
    return rms_norm(params["norm"]["scale"], h, cfg["norm_eps"])


def prediction_input(params, state, next_ids, cfg, prec="f32"):
    """``u`` (B, S, C): the stack's ``state`` and the embedding of the
    ids one position on, each normed, side by side through ``W_eh``."""
    p, eps = params["mtp"], cfg["norm_eps"]
    return matmul("bsi,io->bso", jnp.concatenate([
        rms_norm(p["enorm"]["scale"], params["embed"]["embed"][next_ids],
                 eps),
        rms_norm(p["hnorm"]["scale"], state, eps)], -1),
        p["eh_proj"]["w"], prec)


def prediction_state(params, state, next_ids, cfg, prec="f32", first=None):
    """``z`` (B, S, C): the module's normed state; ``first`` its expert
    layer's first held expert."""
    p = params["mtp"]
    u = prediction_input(params, state, next_ids, cfg, prec)
    u = _checkpointed("A", cfg, prec)(p["mla"], u, None)
    u = _checkpointed("E", cfg, prec)(p["moe"], u, first)
    return rms_norm(p["norm"]["scale"], u, cfg["norm_eps"])


def states(params, batch, cfg, prec="f32"):
    """``(x, z, labels one position on)``: the stack's state, the
    module's (None without a module) and the module's targets, from a
    batch of ``input_ids``, ``labels`` (the next ids, ``IGNORE`` where
    there is none) and maybe ``first_experts``."""
    labels = batch["labels"]
    firsts = batch.get("first_experts")
    firsts = None if firsts is None else firsts[0]
    experts = cfg["hybrid_override_pattern"].count("E")
    x = final_state(params, batch["input_ids"], cfg, prec,
                    None if firsts is None else firsts[:experts])
    if not cfg.get("num_nextn_predict_layers"):
        return x, None, None
    ahead = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], IGNORE)], axis=1)
    ahead = jnp.where(labels == IGNORE, IGNORE, ahead)
    z = prediction_state(params, x, jnp.clip(labels, 0), cfg, prec,
                         None if firsts is None else firsts[experts])
    return x, z, ahead


def logits(params, batch, cfg, prec="f32"):
    """Dense ``(logits, logits2)``, (B, S, V) each: for the tests at a
    toy size."""
    x, z, _ = states(params, batch, cfg, prec)
    return tuple(None if s is None else matmul(
        "bsc,cv->bsv", s, params["head"]["w"], prec) for s in (x, z))


def loss_sums(params, batch, cfg, prec):
    """``((s1, n1), (s2, n2))``: each loss's sum over its labelled
    positions and their number; the second pair is zeros without a
    module."""
    x, z, ahead = states(params, batch, cfg, prec)

    def summed(state, labels):
        w = (labels != IGNORE).astype(jnp.float32)
        return (nll(params, state, labels, prec) * w).sum(), w.sum()

    first = summed(x, batch["labels"])
    return first, (summed(z, ahead) if z is not None
                   else (jnp.float32(0.0), jnp.float32(0.0)))


def loss_sum(params, batch, cfg, prec):
    """``(s1 + weight s2 n1 / n2, n1)``: over blocks of full rows the
    sums over the counts give ``L1 + weight L2`` (the head of this
    file)."""
    (s1, n1), (s2, n2) = loss_sums(params, batch, cfg, prec)
    if not cfg.get("num_nextn_predict_layers"):
        return s1, n1
    return s1 + cfg["mtp_loss_weight"] * s2 * n1 / jnp.maximum(n2, 1.0), n1
