"""Plain reference for a linear-attention mixture-of-experts language
model (Qwen3-Next-80B-A3B-Instruct's ``config.json``, ``model_type:
qwen3_next``; the mixer is Gated DeltaNet, arXiv:2412.06464): forward
pass, mean next-token cross-entropy and, through
``reference.perceiver_io.train_steps``, gradients and three AdamW steps,
in float32 at ``highest`` matmul precision. It imports nothing of the
program and no kernel; weights come from ``benchmarks/weights.py`` in
the program's tree layout.

A published layer (``C`` the hidden size, eps ``norm_eps``, no bias in
any linear layer; every RMSNorm but the mixer's own zero-centred)::

    h = h + mixer(rms(h; w1));  h = h + moe(rms(h; w2))
    rms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)
    h0 = E[ids];  logits = rms(h_last; w_f) Wh            (head untied)

In the program's tree a published layer is two layers of the hybrid
stack, ``L`` or ``*`` (``w1`` is its ``norm``) and ``E`` (``w2`` is its
``norm``); ``hybrid_override_pattern`` is ``LELELE*E`` a period: layers
0, 1, 2 of four are linear, the fourth is full
(``full_attention_interval`` 4).

``L``, the gated delta net (``Hk`` key heads of ``Dk``, ``Hv`` value
heads of ``Dv``, value head ``j`` reads key head ``j // (Hv / Hk)``)::

    [q k v z] = a W_qkvz;  [b alpha] = a W_ba
    [q k v] = silu(causal_depthwise_conv([q k v]))        (K taps, no bias)
    q = l2norm(q) / sqrt(Dk);  k = l2norm(k)              (a head's channels, eps 1e-6)
    beta = sigmoid(b);  g = -exp(A_log) softplus(alpha + dt_bias)
    S_t = exp(g_t) S_(t-1);  S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t                                       (S: Dk x Dv a head)
    out = ((o / sqrt(mean(o^2) + eps) * w_n) * silu(z)) W_out   (a head's Dv channels; scale form)

``*``, the gated attention (``D`` the head size, query head ``i`` reads
key/value head ``i // (heads / kv_heads)``)::

    [q gate] = a W_q  (a head's query beside its gate);  k = a W_k;  v = a W_v
    q = rope(rms(q; w_q)), k = rope(rms(k; w_k))   rms over a head's D channels; the first
                                                   partial_rotary_factor D channels turn, theta rope_theta
    out = (causal_softmax(q k^T / sqrt(D)) v * sigmoid(gate)) W_o

``E``, the experts (router over all ``n_routed_experts``, top
``num_experts_per_tok``)::

    p = softmax(a W_r);  chosen = top_k(p);  w_e = p_e / sum_chosen p
    out = sum over the chosen experts HELD HERE of w_e (silu(a Wg_e) * (a Wu_e)) Wd_e
          + sigmoid(a w_sg) * (silu(a Wg_s) * (a Wu_s)) Wd_s

The ``held_experts`` experts from ``first_expert`` on are this chip's
share (a batch may name each expert layer's, ``first_experts``): what
the absent experts would add is left out, here as in the program; the
shared expert and its gate are whole.

Departures from the published description, and why:

* the in-projections' columns lie ``[q | k | v | z]`` and
  ``[b | alpha]`` side by side, where the published matrices interleave
  them a key head: a permutation of columns of seeded matrices, the
  same function (the program's layout, which the weights follow);
* no multi-token prediction head and no auxiliary balancing loss: the
  catalog row's ``config`` has no key for either.

Departures from ``perceiver_tpu/models/hybrid_lm.py`` and
``ops/delta_rule.py``, and why:

* float32 everywhere, every matrix product through ``matmul`` at
  ``Precision.HIGHEST``; ``prec`` lowers the operands there and nowhere
  else, so the control shares every other line. The recurrence's own
  arithmetic (the decay, ``S^T k``, the outer product, ``S^T q``) is
  elementwise float32 and is never lowered;
* the recurrence **position by position**, as written above: a
  ``lax.scan`` over chunks of ``SCAN_CHUNK`` positions, each a
  ``jax.checkpoint`` with a scan over its positions inside, so that a
  chunk's ``Dk x Dv`` states live only while that chunk is
  differentiated. The program computes whole chunks of 64 as products
  (the WY form and a triangular inverse);
* attention as a full masked softmax, one query head at a time (the
  program repeats the keys and values and runs fused kernels);
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

from benchmarks.reference.block_diffusion_lm import (  # noqa: F401
    expert_layer as routed_experts,
    gated_mlp,
    router_weights,
)
from benchmarks.reference.hybrid_lm import causal_conv, nll
from benchmarks.reference.perceiver_io import IGNORE, matmul

SCAN_CHUNK = 128
KINDS = {"L": "delta", "E": "moe", "*": "attn"}


def rms_norm(w, x, eps):
    """Zero-centred: ``w`` is what the multiplier departs from 1 by."""
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * (1.0 + w)


def layer_names(cfg):
    return [f"{i:02d}_{KINDS[kind]}"
            for i, kind in enumerate(cfg["hybrid_override_pattern"])]


# --- L -----------------------------------------------------------------------


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + eps)


def recurrence(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` with ``S_t = exp(g_t) S_(t-1)`` and then
    ``S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T``, position by
    position. q, k (B, S, H, Dk) (each value head's key head's);
    v (B, S, H, Dv); g, beta (B, S, H). Returns (B, S, H, Dv)."""
    rows, seq, heads, width = v.shape
    pad = -seq % SCAN_CHUNK
    if pad:   # g = 0, beta = 0: no decay, nothing written
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))[:x.ndim])
            for x in (q, k, v, g, beta))

    def position(state, at):                      # state (B, H, Dk, Dv)
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        found = (state * k_t[..., None]).sum(-2)              # S^T k
        write = beta_t[..., None] * (v_t - found)
        state = state + k_t[..., None] * write[..., None, :]
        return state, (state * q_t[..., None]).sum(-2)        # S^T q

    @jax.checkpoint
    def chunk(state, positions):
        return jax.lax.scan(position, state, positions)

    def chunked(x):   # (B, S, ...) -> (chunks, SCAN_CHUNK, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(-1, SCAN_CHUNK, *x.shape[1:])

    state = jnp.zeros((rows, heads, q.shape[-1], width), jnp.float32)
    _, o = jax.lax.scan(chunk, state,
                        tuple(map(chunked, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(-1, rows, heads, width), 0, 1)[:, :seq]


def delta_mixer(p, a, cfg, prec):
    rows, seq, _ = a.shape
    key_heads, heads = (cfg["linear_num_key_heads"],
                        cfg["linear_num_value_heads"])
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = key_heads * dk, heads * dv
    qkvz = matmul("bsi,io->bso", a, p["in_proj_qkvz"]["w"], prec)
    ba = matmul("bsi,io->bso", a, p["in_proj_ba"]["w"], prec)
    qkv, z = qkvz[..., :2 * key_dim + value_dim], \
        qkvz[..., 2 * key_dim + value_dim:]
    qkv = jax.nn.silu(causal_conv(p["conv"]["w"], 0.0, qkv))
    # every value head reads its key head's q and k
    q, k = (jnp.repeat(x.reshape(rows, seq, key_heads, dk),
                       heads // key_heads, axis=2)
            for x in (qkv[..., :key_dim], qkv[..., key_dim:2 * key_dim]))
    v = qkv[..., 2 * key_dim:].reshape(rows, seq, heads, dv)
    beta = jax.nn.sigmoid(ba[..., :heads])
    g = -jnp.exp(p["A_log"]["bias"]) * jax.nn.softplus(
        ba[..., heads:] + p["dt"]["bias"])
    o = recurrence(l2_norm(q) / math.sqrt(dk), l2_norm(k), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                          + cfg["norm_eps"]) * p["norm"]["scale"]
    y = o * jax.nn.silu(z.reshape(rows, seq, heads, dv))
    return matmul("bsi,io->bso", y.reshape(rows, seq, value_dim),
                  p["out_proj"]["w"], prec)


# --- * -----------------------------------------------------------------------


def rope(x, theta: float, turned: int):
    """Rotary embedding of the first ``turned`` channels of each head of
    ``x`` (B, S, H, D), position = index: channel ``i`` and ``i +
    turned/2`` turn by ``position * theta^(-2i/turned)``; the rest pass."""
    inv_freq = theta ** (-jnp.arange(0, turned, 2, dtype=jnp.float32)
                         / turned)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate(
        [x[..., :turned] * cos + jnp.concatenate([-x2, x1], -1) * sin,
         x[..., turned:]], axis=-1)


def attention_layer(p, a, cfg, prec):
    rows, seq, _ = a.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["norm_eps"]
    turned = int(d * cfg["partial_rotary_factor"])
    q, gate = jnp.split(
        matmul("bsi,io->bso", a, p["q"]["w"], prec).reshape(
            rows, seq, heads, 2 * d), 2, axis=-1)
    k, v = (matmul("bsi,io->bso", a, p[n]["w"], prec).reshape(
        rows, seq, kv_heads, d) for n in ("k", "v"))
    q = rope(rms_norm(p["q_norm"]["bias"], q, eps), cfg["rope_theta"],
             turned)
    k = rope(rms_norm(p["k_norm"]["bias"], k, eps), cfg["rope_theta"],
             turned)
    visible = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(args):
        qh, index = args                                   # (B, S, D)
        kh, vh = (jnp.take(x, index // (heads // kv_heads), axis=2)
                  for x in (k, v))
        scores = matmul("bqd,bkd->bqk", qh / math.sqrt(d), kh, prec)
        w = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return matmul("bqk,bkd->bqd", w, vh, prec)

    o = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(heads)))
    o = jnp.moveaxis(o, 0, 2) * jax.nn.sigmoid(gate)
    return matmul("bsi,io->bso", o.reshape(rows, seq, heads * d),
                  p["out"]["w"], prec)


# --- E -----------------------------------------------------------------------


def shared_expert(p, a, prec):
    """(T, C): ``sigmoid(a w_sg) * (silu(a Wg) * (a Wu)) Wd``."""
    s = p["shared"]
    gate = jax.nn.sigmoid(matmul("ti,io->to", a, p["shared_gate"]["w"],
                                 prec))
    return gate * gated_mlp(s["gate"]["w"], s["up"]["w"], s["down"]["w"],
                            a, prec)


def expert_layer(p, a, cfg, prec, first=None):
    """The held experts' part as the Qwen3-MoE reference computes it
    (``block_diffusion_lm.expert_layer``: the same router, the same
    masked sum one held expert at a time; ``first`` its first held
    expert, None: the configuration's) and the shared expert under its
    gate."""
    return routed_experts(p, a, cfg, prec, first) + shared_expert(
        p, a.reshape(-1, a.shape[-1]), prec).reshape(a.shape)


# --- the stack and the loss --------------------------------------------------

MIXERS = {"L": delta_mixer, "*": attention_layer}


def layer(p, h, first, *, kind, cfg, prec="f32"):
    """``h + mixer(rms(h))`` of one of the program's layers; ``first``
    is an expert layer's first held expert (None: the configuration's,
    and in the other kinds)."""
    a = rms_norm(p["norm"]["bias"], h, cfg["norm_eps"])
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
    return rms_norm(params["norm"]["bias"], h, cfg["norm_eps"])


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
