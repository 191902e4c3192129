"""Plain reference for a Kimi Linear language model
(Kimi-Linear-48B-A3B-Instruct's ``config.json``, ``model_type:
kimi_linear``; the mixer is Kimi Delta Attention, KDA, of the Kimi
Linear report, arXiv:2510.26692): forward pass, mean next-token
cross-entropy and, through ``reference.perceiver_io.train_steps``,
gradients and three AdamW steps, in float32 at ``highest`` matmul
precision. It imports nothing of the program and no kernel; weights come
from ``benchmarks/weights.py`` in the program's tree layout.

A published layer (``C`` the hidden size, eps ``norm_eps``, no bias in
any linear layer, every RMSNorm in the scale form)::

    h = h + mixer(rms(h; w1));  h = h + mlp(rms(h; w2))
    rms(x; w) = x / sqrt(mean(x^2) + eps) * w
    h0 = E[ids];  logits = rms(h_last; w_f) Wh            (head untied)

In the program's tree a published layer is two layers of the hybrid
stack, ``K`` or ``A`` (``w1`` is its ``norm``) and ``D`` or ``E``
(``w2``); published layers 1 to 5 are ``KDKEKEAEKE``: ``kda_layers``
1, 2, 3, 5, ``full_attn_layers`` 4, ``first_k_dense_replace`` 1.

``K``, Kimi Delta Attention (``H`` heads of ``D`` channels for q, k and
v alike)::

    q, k, v = silu(conv(a W_q)), silu(conv(a W_k)), silu(conv(a W_v))
                                     (causal, depthwise, 4 taps, no bias)
    q = l2norm(q) / sqrt(D);  k = l2norm(k)       (a head's channels, eps 1e-6)
    g = -exp(A_log[h]) softplus((a W_fa) W_fb + dt_bias)   (a number a head
                                                            and key channel)
    beta = sigmoid(a W_b)                                  (a head)
    S' = Diag(exp(g_t)) S_(t-1);  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t                                        (S: D x D a head)
    out = ((o / sqrt(mean(o^2) + eps) * w_n) * sigmoid((a W_ga) W_gb)) W_o

``A``, latent attention (``H`` heads; ``n`` = ``qk_nope_head_dim``, ``r``
= ``qk_rope_head_dim``, ``e`` = ``v_head_dim``, ``L`` = ``kv_lora_rank``;
**no rotary embedding on any channel**, ``mla_use_nope``)::

    q = a W_q                         (a head's n + r channels: [q_n | q_s])
    [c | k_s] = a W_kva               (L | r);  c' = rms(c; w_c)
    [k_n | v] = c' W_kvb              (a head's n | e)
    scores_h = (q_n,h k_n,h^T + q_s,h k_s^T) / sqrt(n + r)
    out = causal_softmax(scores) v  W_o

computed here **from the latent**: ``q_n,h k_n,h^T = (q_n,h Wk_h^T) c'^T``
and ``softmax v_h = (softmax c') Wv_h``, with ``Wk_h`` (L x n) and
``Wv_h`` (L x e) the head's columns of ``W_kvb``, so no head's keys or
values are formed.

``D``, the dense MLP: ``(silu(a W_g) * (a W_u)) W_d``.

``E``, the experts (router over all ``n_routed_experts``, top
``num_experts_per_tok``; ``num_expert_group`` 1 and ``topk_group`` 1
make the grouped top-k the plain one)::

    s = sigmoid(a W_r);  chosen = top_k(s)     (e_score_correction_bias at 0)
    w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
    out = sum over the chosen experts HELD HERE of w_e (silu(a Wg_e) * (a Wu_e)) Wd_e
          + (silu(a Wg_s) * (a Wu_s)) Wd_s      (the shared expert: no gate column)

The ``held_experts`` experts from ``first_expert`` on are this chip's
share (a batch may name each expert layer's, ``first_experts``): what
the absent experts would add is left out, here as in the program; the
shared expert is whole.

Departures from the published description, and why:

* q, k and v keep a projection and a convolution each, as published;
  the program runs them as one product and one convolution over the
  matrices side by side, the same function;
* no multi-token prediction head (``num_nextn_predict_layers`` 0), no
  auxiliary balancing loss, and the balancing buffer
  ``e_score_correction_bias`` at 0 and not read: ``config.json`` gives
  none of them a value.

Departures from ``perceiver_tpu/models/hybrid_lm.py`` and
``ops/delta_rule.py``, and why:

* float32 everywhere, every matrix product through ``matmul`` at
  ``Precision.HIGHEST``; ``prec`` lowers the operands there and nowhere
  else, so the control shares every other line. The recurrence's own
  arithmetic (the decay, ``S^T k``, the outer product, ``S^T q``) is
  elementwise float32 and is never lowered;
* the recurrence **position by position**, as written above (no
  chunks): a ``lax.scan`` over stretches of ``SCAN_CHUNK`` positions,
  each a ``jax.checkpoint`` with a scan over its positions inside, so
  that a stretch's ``D x D`` states live only while it is
  differentiated. The program computes whole chunks of 64 as products
  (the WY form, a triangular inverse, sub-blocks of 16 for the decays);
* latent attention from the latent, a full masked softmax one head at
  a time (the program expands keys and values to the heads and runs
  fused kernels on heads padded to 256 lanes);
* the experts as a **masked sum over the held experts**, one at a time
  (the program sorts the assignments and multiplies each expert by its
  own rows);
* each layer is a ``jax.checkpoint`` so that it fits beside five
  parameter trees, and the head and its CE go over the positions in
  chunks (``reference.hybrid_lm.nll``). Same mathematics, smaller live
  set.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.block_diffusion_lm import gated_mlp
from benchmarks.reference.hybrid_lm import (  # noqa: F401
    causal_conv,
    nll,
    rms_norm,
    router_weights,
)
from benchmarks.reference.perceiver_io import IGNORE, matmul

SCAN_CHUNK = 128
KINDS = {"K": "kda", "A": "mla", "D": "mlp", "E": "moe"}


def layer_names(cfg):
    return [f"{i:02d}_{KINDS[kind]}"
            for i, kind in enumerate(cfg["hybrid_override_pattern"])]


# --- K -----------------------------------------------------------------------


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + eps)


def recurrence(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` with ``S' = Diag(exp(g_t)) S_(t-1)`` and
    ``S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T``, position by
    position. q, k, g (B, S, H, Dk); v (B, S, H, Dv); beta (B, S, H).
    Returns (B, S, H, Dv)."""
    rows, seq, heads, width = v.shape
    pad = -seq % SCAN_CHUNK
    if pad:   # g = 0, beta = 0: no decay, nothing written
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))[:x.ndim])
            for x in (q, k, v, g, beta))

    def position(state, at):                      # state (B, H, Dk, Dv)
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None]
        found = (state * k_t[..., None]).sum(-2)              # S^T k
        write = beta_t[..., None] * (v_t - found)
        state = state + k_t[..., None] * write[..., None, :]
        return state, (state * q_t[..., None]).sum(-2)        # S^T q

    @jax.checkpoint
    def stretch(state, positions):
        return jax.lax.scan(position, state, positions)

    def stretched(x):   # (B, S, ...) -> (stretches, SCAN_CHUNK, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(-1, SCAN_CHUNK, *x.shape[1:])

    state = jnp.zeros((rows, heads, q.shape[-1], width), jnp.float32)
    _, o = jax.lax.scan(stretch, state,
                        tuple(map(stretched, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(-1, rows, heads, width), 0, 1)[:, :seq]


def kda_mixer(p, a, cfg, prec):
    rows, seq, _ = a.shape
    heads, d = cfg["kda_num_heads"], cfg["kda_head_dim"]

    def project(*names):
        x = a
        for name in names:
            x = matmul("bsi,io->bso", x, p[name]["w"], prec)
        return x

    q, k, v = (jax.nn.silu(causal_conv(
        p[f"{n}_conv"]["w"], 0.0, project(n))).reshape(rows, seq, heads, d)
        for n in ("q", "k", "v"))
    g = -jnp.exp(p["A_log"]["bias"])[:, None] * jax.nn.softplus(
        project("f_a", "f_b") + p["dt"]["bias"]).reshape(rows, seq, heads, d)
    beta = jax.nn.sigmoid(project("beta"))
    o = recurrence(l2_norm(q) / math.sqrt(d), l2_norm(k), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                          + cfg["norm_eps"]) * p["norm"]["scale"]
    y = o * jax.nn.sigmoid(project("g_a", "g_b").reshape(rows, seq, heads, d))
    return matmul("bsi,io->bso", y.reshape(rows, seq, heads * d),
                  p["out"]["w"], prec)


# --- A -----------------------------------------------------------------------


def latent_attention(p, a, cfg, prec):
    rows, seq, _ = a.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q = matmul("bsi,io->bso", a, p["q"]["w"], prec).reshape(
        rows, seq, heads, nope + rope) / math.sqrt(nope + rope)
    kva = matmul("bsi,io->bso", a, p["kv_a"]["w"], prec)
    latent = rms_norm(p["kv_norm"]["scale"], kva[..., :rank],
                      cfg["norm_eps"])
    shared = kva[..., rank:]                               # (B, S, r)
    kv_b = p["kv_b"]["w"].reshape(rank, heads, -1)         # (L, H, n + e)
    visible = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(args):
        qh, w = args                            # (B, S, n + r), (L, n + e)
        in_latent = matmul("bqn,ln->bql", qh[..., :nope], w[:, :nope], prec)
        scores = matmul("bql,bkl->bqk", in_latent, latent, prec) \
            + matmul("bqr,bkr->bqk", qh[..., nope:], shared, prec)
        weights = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return matmul("bql,le->bqe",
                      matmul("bqk,bkl->bql", weights, latent, prec),
                      w[:, nope:], prec)

    o = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                               jnp.moveaxis(kv_b, 1, 0)))
    return matmul("bsi,io->bso",
                  jnp.moveaxis(o, 0, 2).reshape(rows, seq, -1),
                  p["out"]["w"], prec)


# --- D and E -----------------------------------------------------------------


def dense_mlp(p, a, cfg, prec):
    return gated_mlp(p["gate"]["w"], p["up"]["w"], p["down"]["w"],
                     a.reshape(-1, a.shape[-1]), prec).reshape(a.shape)


def expert_layer(p, a, cfg, prec, first=None):
    """The held experts' part, a masked sum one held expert at a time
    under the sigmoid router's scaled weights
    (``reference.hybrid_lm.router_weights``; ``first`` the first held
    expert, None: the configuration's), and the shared expert, gated
    with three matrices and no gate column."""
    shape = a.shape
    a = a.reshape(-1, shape[-1])
    if first is None:
        first = cfg.get("first_expert", 0)
    experts = p["experts"]
    weights = jax.lax.dynamic_slice_in_dim(
        router_weights(p, a, cfg, prec), first,
        experts["up"]["w"].shape[0], axis=1)

    @jax.checkpoint
    def one_expert(total, expert):
        gate, up, down, w = expert
        return total + w[:, None] * gated_mlp(gate, up, down, a, prec), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(a),
        (experts["gate"]["w"], experts["up"]["w"], experts["down"]["w"],
         weights.T))
    shared = p["shared"]
    return (routed + gated_mlp(shared["gate"]["w"], shared["up"]["w"],
                               shared["down"]["w"], a, prec)).reshape(shape)


# --- the stack and the loss --------------------------------------------------

MIXERS = {"K": kda_mixer, "A": latent_attention, "D": dense_mlp}


def layer(p, h, first, *, kind, cfg, prec="f32"):
    """``h + mixer(rms(h))`` of one of the program's layers; ``first``
    is an expert layer's first held expert (None: the configuration's,
    and in the other kinds)."""
    a = rms_norm(p["norm"]["scale"], h, cfg["norm_eps"])
    if kind == "E":
        return h + expert_layer(p["mixer"], a, cfg, prec, first)
    return h + MIXERS[kind](p["mixer"], a, cfg, prec)


def final_state(params, ids, cfg, prec="f32", first_experts=None):
    """The normed state the head reads, (B, S, C). ``first_experts``
    (expert layers,) int32: each expert layer's first held expert, in
    the configuration's place."""
    h = params["embed"]["embed"][ids]
    firsts = iter(() if first_experts is None else first_experts)
    for name, kind in zip(layer_names(cfg), cfg["hybrid_override_pattern"]):
        h = jax.checkpoint(functools.partial(
            layer, kind=kind, cfg=cfg, prec=prec))(
                params["layers"][name], h,
                next(firsts, None) if kind == "E" else None)
    return rms_norm(params["norm"]["scale"], h, cfg["norm_eps"])


def logits(params, ids, cfg, prec="f32", first_experts=None):
    """Dense (B, S, V): for the tests at a toy size."""
    return matmul("bsc,cv->bsv",
                  final_state(params, ids, cfg, prec, first_experts),
                  params["head"]["w"], prec)


def loss_sum(params, batch, cfg, prec):
    """(sum of the labelled positions' next-token NLL, their number);
    ``batch`` holds ``input_ids`` and ``labels`` (the next ids,
    ``IGNORE`` where there is none) and may hold ``first_experts``."""
    labels = batch["labels"]
    firsts = batch.get("first_experts")
    state = final_state(params, batch["input_ids"], cfg, prec,
                        None if firsts is None else firsts[0])
    w = (labels != IGNORE).astype(jnp.float32)
    return (nll(params, state, labels, prec) * w).sum(), w.sum()
