"""Plain reference for a looped language model (the Ouro family, Zhu et
al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): forward pass of every pass, the exit gate, the
first-stage loss and, through ``reference.perceiver_io.train_steps``,
gradients and three AdamW steps, in float32 at ``highest`` matmul
precision. It imports nothing of the program and no kernel; weights
come from ``benchmarks/weights.py`` in the program's tree layout.

From the published ``config.json``: hidden 2048, 16 query and 16
key/value heads of 128, SwiGLU width 5632 (``silu``), RMSNorm eps 1e-6,
rotary theta 1,000,000 over the whole head dim, vocabulary 49,152,
embedding and head untied, every layer ``full_attention``, no window,
``total_ut_steps`` 4.

One layer, on a row ``h`` of ``S`` positions (sandwich norms, four a
layer)::

    a = rms1(h);  q, k, v = a Wq, a Wk, a Wv            (no biases; H heads x D)
    q, k = rope(q), rope(k)                              (theta, position i, pairs (j, j + D/2))
    o = softmax(q k^T / sqrt(D) + causal) v
    h = h + rms2(o Wo)
    m = rms3(h);  h = h + rms4((silu(m Wg) * (m Wu)) Wd)
    rms(x) = x / sqrt(mean(x^2) + eps) * scale

The loop, with ``Layers`` = the L layers in order and the **same**
parameters in every pass::

    h0 = E[ids]
    for t = 1..T:   ht = rms_f(Layers(h(t-1)))           (the normed state feeds the next pass)
                    logits_t = ht Wh                      (no bias)
                    lam_t    = sigmoid(ht wg + bg)        (one gate, Linear(C, 1))
    p_1 = lam_1;  p_t = lam_t * prod_{j<t}(1 - lam_j) for 1 < t < T;  p_T = prod_{j<T}(1 - lam_j)
    nll_t,i = CE(logits_t,i , ids_{i+1})                 (the row's last position has no label)
    loss = (1/N) sum_i [ sum_t p_t,i nll_t,i  -  beta * H(p_.,i) ],   H(p) = - sum_t p_t log p_t

``N`` counts the positions with a label: the paper's first-stage
objective, the expected loss under the exit distribution,
entropy-regularised against a uniform prior. Not stated in
``config.json`` and taken from the family's public ``modeling_ouro.py``
and the paper: the four-norm placement, the final norm inside the loop,
the gate reading the normed state, no projection biases, ``beta``
(``benchmarks/configs/ouro_2p6b.json`` lists each under ``assumed``).

Departures from ``perceiver_tpu/models/looped_lm.py`` and why:

* float32 everywhere, every product through ``matmul`` at
  ``Precision.HIGHEST`` (the program computes in bfloat16 on float32
  parameters and statistics); ``prec`` lowers the operands there and
  nowhere else, so the control shares every other line;
* blocking, so that it **fits** beside five parameter trees (12.25 GB
  at 612 M parameters): a pass and, inside it, each layer application
  are ``jax.checkpoint``-ed (a pass's input is saved and its layers'
  inputs live only while that pass is differentiated), the passes are
  a scan (one running sum of the shared layers' gradient), the heads are
  walked one at a time (the float32 scores of one head of one row are
  67 MB at 4096 positions, of all sixteen 1.07 GB) and the head
  projection and its CE go over the positions in chunks of
  ``LOGIT_CHUNK`` (a pass's float32 logits are 0.8 GB a row). Same
  mathematics, smaller live set; no kernel, no custom VJP, no packed
  projections, no fused loss;
* the exit distribution is the product formula above and ``log p``
  taken from it (the program works in logs of the sigmoid).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.perceiver_io import IGNORE, matmul

LOGIT_CHUNK = 1024


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * scale


def rope_tables(positions: int, dim: int, theta: float):
    """(cos, sin), each (positions, dim): column j and j + dim/2 turn
    by ``i * theta^(-2j/dim)``."""
    inv = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(positions, dtype=np.float64)[:, None] * inv
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rope(x, cos, sin):
    """x (B, S, H, D)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos[None, :, None, :] \
        + jnp.concatenate([-x2, x1], axis=-1) * sin[None, :, None, :]


def causal_attention(q, k, v, prec):
    """q, k, v (B, S, H, D) -> (B, S, H, D), one head at a time."""
    s, d = q.shape[1], q.shape[-1]
    visible = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv                                   # (B, S, D)
        scores = matmul("bqd,bkd->bqk", qh / math.sqrt(d), kh, prec)
        w = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return matmul("bqk,bkd->bqd", w, vh, prec)

    heads_first = [jnp.moveaxis(x, 2, 0) for x in (q, k, v)]
    return jnp.moveaxis(jax.lax.map(one_head, tuple(heads_first)), 0, 2)


def decoder_layer(p, h, cfg, tables, prec):
    b, s, c = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    a = rms_norm(p["attn_norm_in"]["scale"], h, eps)
    q, k, v = (matmul("bsi,io->bso", a, p["attn"][n]["w"], prec)
               .reshape(b, s, heads, -1) for n in ("q", "k", "v"))
    o = causal_attention(rope(q, *tables), rope(k, *tables), v, prec)
    o = matmul("bsi,io->bso", o.reshape(b, s, c), p["attn"]["out"]["w"], prec)
    h = h + rms_norm(p["attn_norm_out"]["scale"], o, eps)
    m = rms_norm(p["mlp_norm_in"]["scale"], h, eps)
    gate = matmul("bsi,io->bso", m, p["mlp"]["gate"]["w"], prec)
    up = matmul("bsi,io->bso", m, p["mlp"]["up"]["w"], prec)
    m = matmul("bsi,io->bso", jax.nn.silu(gate) * up,
               p["mlp"]["down"]["w"], prec)
    return h + rms_norm(p["mlp_norm_out"]["scale"], m, eps)


def pass_states(params, ids, cfg, prec="f32"):
    """The normed state after each pass, (T, B, S, C). The passes are
    a ``lax.scan`` and not a Python loop for the gradient's sake: the
    transpose of a scan adds each pass's gradient of the shared layers
    into one running sum, where an unrolled loop keeps all T stacked
    gradients until they are added."""
    tables = rope_tables(ids.shape[1], cfg["head_dim"], cfg["rope_theta"])

    @jax.checkpoint
    def one_pass(h, _):
        @jax.checkpoint
        def body(h, layer):
            return decoder_layer(layer, h, cfg, tables, prec), None

        h = jax.lax.scan(body, h, params["layers"])[0]
        h = rms_norm(params["norm"]["scale"], h, cfg["rms_norm_eps"])
        return h, h

    return jax.lax.scan(one_pass, params["embed"]["embed"][ids], None,
                        length=cfg["total_ut_steps"])[1]


def gate_probability(params, state, prec):
    """lam (B, S) of one pass."""
    z = matmul("bsc,co->bso", state, params["gate"]["w"], prec)[..., 0]
    return jax.nn.sigmoid(z + params["gate"]["b"][0])


def exit_distribution(lams):
    """p_t from the passes' lam_t (T of them): (T, B, S), sums to 1."""
    stay, ps = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        ps.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(ps + [stay])


def pass_logits(params, ids, cfg, prec="f32"):
    """Dense ``(logits (T, B, S, V), exit probabilities (T, B, S))``:
    for the tests at a toy size."""
    states = pass_states(params, ids, cfg, prec)
    logits = jnp.stack([matmul("bsc,cv->bsv", h, params["head"]["w"], prec)
                        for h in states])
    return logits, exit_distribution(
        [gate_probability(params, h, prec) for h in states])


def pass_nll(params, state, labels, prec):
    """CE of one pass's logits against ``labels`` (B, S), (B, S); the
    positions go through the head ``LOGIT_CHUNK`` at a time."""
    b, s, c = state.shape
    chunk = math.gcd(b * s, LOGIT_CHUNK)

    @jax.checkpoint
    def one_chunk(xs):
        h, y = xs
        logp = jax.nn.log_softmax(
            matmul("nc,cv->nv", h, params["head"]["w"], prec), axis=-1)
        return -jnp.take_along_axis(
            logp, jnp.clip(y, 0)[:, None], axis=-1)[:, 0]

    return jax.lax.map(one_chunk, (
        state.reshape(-1, chunk, c),
        labels.reshape(-1, chunk))).reshape(b, s)


def loss_sum(params, batch, cfg, prec):
    """(sum over the labelled positions of ``sum_t p_t nll_t - beta
    H(p)``, their number); ``batch`` holds ``input_ids`` and ``labels``
    (the next ids, ``IGNORE`` where there is none)."""
    ids, labels = batch["input_ids"], batch["labels"]
    states = pass_states(params, ids, cfg, prec)
    p = exit_distribution([gate_probability(params, h, prec)
                           for h in states])
    nll = jnp.stack([pass_nll(params, h, labels, prec) for h in states])
    entropy = -(p * jnp.log(p)).sum(0)
    w = (labels != IGNORE).astype(jnp.float32)
    terms = (p * nll).sum(0) - cfg["exit_entropy_beta"] * entropy
    return (terms * w).sum(), w.sum()
