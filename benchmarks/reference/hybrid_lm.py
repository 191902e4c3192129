"""Plain reference for a hybrid state-space / mixture-of-experts
language model (the ``nemotron_h`` family: NVIDIA Nemotron-H,
arXiv:2504.03624; NVIDIA-Nemotron-3-Nano-30B-A3B's ``config.json``):
forward pass, mean next-token cross-entropy and, through
``reference.perceiver_io.train_steps``, gradients and three AdamW steps,
in float32 at ``highest`` matmul precision. It imports nothing of the
program and no kernel; weights come from ``benchmarks/weights.py`` in
the program's tree layout.

The stack, one character of ``hybrid_override_pattern`` a layer (``d``
the hidden size, eps ``norm_eps``, no bias in any linear layer)::

    h0 = E[ids];   h = h + mixer(rms(h));   logits = rms_f(h) Wh
    rms(x) = x / sqrt(mean(x^2) + eps) * scale

``M``, Mamba-2 (``H`` heads of ``P``, ``G`` groups of state ``N``, head
``h`` reads group ``h // (H / G)``)::

    [z, xBC, dt] = u W_in                          (HP, HP + 2GN, H)
    xBC = silu(conv(xBC) + b_c)                    (causal, depthwise, conv_kernel taps)
    [x, B, C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    s_t = exp(dt_t A) s_(t-1) + dt_t x_t B_t^T     (P x N a head)
    y_t = s_t C_t + D x_t
    out = (grouprms(y * silu(z)) * g) W_out        (RMS over each group's HP / G channels)

``E``, experts (router over all ``n_routed_experts``, top
``num_experts_per_tok``)::

    s = sigmoid(a W_r);  chosen = top_k(s)         (e_score_correction_bias held at 0)
    w_i = s_i / (sum_chosen s + 1e-20) * routed_scaling_factor
    out = sum over the chosen experts HELD HERE of w_i f_i(a) + f_shared(a)
    f(a) = relu(a W_up)^2 W_down

The ``held_experts`` experts from ``first_expert`` on are this chip's
share: what the absent experts would have added is left out, here as in
the program (``held_experts`` absent or None: every expert). A batch
may say which share each expert layer holds (``first_experts``, a row
of first experts a batch row, every row alike), in the configuration's
place.

``*``, attention: ``q = a Wq`` (heads of ``head_dim``), ``k, v`` on
``num_key_value_heads``, causal softmax at ``1 / sqrt(head_dim)``,
query head ``i`` reads key/value head ``i // (heads / kv_heads)``, no
position embedding.

Departures from ``perceiver_tpu/models/hybrid_lm.py`` and why:

* float32 everywhere, every matrix product through ``matmul`` at
  ``Precision.HIGHEST``; ``prec`` lowers the operands there and nowhere
  else, so the control shares every other line. The recurrence's own
  arithmetic (decays, the outer product, the reading by ``C``) is
  elementwise float32 and is never lowered;
* the recurrence **position by position**: a ``lax.scan`` over chunks
  of ``SCAN_CHUNK`` positions, each a ``jax.checkpoint`` with a scan
  over its positions inside, so that a chunk's ``P x N`` states live
  only while that chunk is differentiated (every position's state of a
  4,096-token row is 8.6 GB). The program computes whole chunks as
  products (the SSD form);
* the experts as a **masked sum over the held experts**: every held
  expert multiplies every token and a weight that is 0 where the token
  did not choose it scales the result (the program sorts the
  assignments and multiplies each expert by its own rows);
* attention as a full masked softmax, one query head at a time (the
  program repeats the keys and values and runs fused kernels);
* each layer is a ``jax.checkpoint`` so that it fits beside five
  parameter trees, and the head and its CE go over the positions in
  chunks of ``LOGIT_CHUNK``. Same mathematics, smaller live set.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.perceiver_io import IGNORE, matmul

LOGIT_CHUNK = 1024
SCAN_CHUNK = 128
KINDS = {"M": "ssm", "E": "moe", "*": "attn"}


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * scale


def layer_names(cfg):
    return [f"{i:02d}_{KINDS[kind]}"
            for i, kind in enumerate(cfg["hybrid_override_pattern"])]


# --- M -----------------------------------------------------------------------


def causal_conv(w, bias, x):
    """x (B, S, C), w (K, C): ``out[t] = sum_k w[k] x[t - (K - 1) + k] +
    bias``, as a grouped convolution padded on the left."""
    taps, channels = w.shape
    out = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels,
        precision=jax.lax.Precision.HIGHEST)
    return out + bias


def recurrence(x, dt, a, b, c):
    """``y_t = s_t C_t`` with ``s_t = exp(dt_t A) s_(t-1) + dt_t x_t
    B_t^T``, position by position. x (B, S, H, P); dt (B, S, H); a (H,);
    b, c (B, S, H, N) (each head's group's). Returns (B, S, H, P)."""
    rows, seq, heads, width = x.shape
    pad = -seq % SCAN_CHUNK
    if pad:   # dt = 0: no decay, nothing written
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))[:v.ndim])
                       for v in (x, dt, b, c))

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, (state * c_t[..., None, :]).sum(-1)

    @jax.checkpoint
    def chunk(state, positions):
        return jax.lax.scan(position, state, positions)

    def chunked(v):   # (B, S, ...) -> (chunks, SCAN_CHUNK, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(-1, SCAN_CHUNK, *v.shape[1:])

    state = jnp.zeros((rows, heads, width, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(chunk, state, tuple(map(chunked, (x, dt, b, c))))
    return jnp.moveaxis(y.reshape(-1, rows, heads, width), 0, 1)[:, :seq]


def mamba_mixer(p, u, cfg, prec):
    rows, seq, _ = u.shape
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    inner, bc = heads * width, groups * state
    zxbcdt = matmul("bsi,io->bso", u, p["in_proj"]["w"], prec)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(causal_conv(p["conv"]["w"], p["conv"]["bias"], xbc))
    x = xbc[..., :inner].reshape(rows, seq, heads, width)
    # every head reads its group's B and C
    b, c = (jnp.repeat(v.reshape(rows, seq, groups, state),
                       heads // groups, axis=2)
            for v in (xbc[..., inner:inner + bc], xbc[..., inner + bc:]))
    dt = jax.nn.softplus(dt + p["dt"]["bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]["bias"]), b, c)
    y = (y + p["D"]["scale"][:, None] * x).reshape(rows, seq, inner)
    y = y * jax.nn.silu(z)
    y = rms_norm(1.0, y.reshape(rows, seq, groups, -1),
                 cfg["norm_eps"]).reshape(rows, seq, inner)
    return matmul("bsi,io->bso", y * p["norm"]["scale"],
                  p["out_proj"]["w"], prec)


# --- E -----------------------------------------------------------------------


def relu2_mlp(up, down, a, prec):
    hidden = jnp.square(jax.nn.relu(matmul("ti,io->to", a, up, prec)))
    return matmul("ti,io->to", hidden, down, prec)


def router_weights(p, a, cfg, prec):
    """(T, n_routed_experts): the weight of every expert for every
    token, 0 where the token did not choose it."""
    scores = jax.nn.sigmoid(matmul("tc,ce->te", a, p["router"]["w"], prec))
    k = cfg["num_experts_per_tok"]
    kth = jnp.sort(scores, axis=-1)[:, -k][:, None]
    chosen = scores >= kth
    picked = jnp.where(chosen, scores, 0.0)
    return picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]


def expert_layer(p, a, cfg, prec, first=None):
    """``first``: the first expert held (an int or a traced scalar);
    None: the configuration's."""
    shape = a.shape
    a = a.reshape(-1, shape[-1])
    if first is None:
        first = cfg.get("first_expert", 0)
    held = p["experts"]["up"]["w"].shape[0]
    weights = jax.lax.dynamic_slice_in_dim(
        router_weights(p, a, cfg, prec), first, held, axis=1)

    @jax.checkpoint
    def one_expert(total, expert):
        up, down, w = expert
        return total + w[:, None] * relu2_mlp(up, down, a, prec), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(a),
        (p["experts"]["up"]["w"], p["experts"]["down"]["w"], weights.T))
    shared = relu2_mlp(p["shared"]["up"]["w"], p["shared"]["down"]["w"],
                       a, prec)
    return (routed + shared).reshape(shape)


# --- * -----------------------------------------------------------------------


def attention_layer(p, a, cfg, prec):
    rows, seq, _ = a.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = matmul("bsi,io->bso", a, p["q"]["w"], prec).reshape(
        rows, seq, heads, d)
    k, v = (matmul("bsi,io->bso", a, p[n]["w"], prec).reshape(
        rows, seq, kv_heads, d) for n in ("k", "v"))
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
    o = jnp.moveaxis(o, 0, 2).reshape(rows, seq, heads * d)
    return matmul("bsi,io->bso", o, p["out"]["w"], prec)


# --- the stack and the loss --------------------------------------------------

MIXERS = {"M": mamba_mixer, "E": expert_layer, "*": attention_layer}


def layer(p, h, first, *, kind, cfg, prec="f32"):
    """``h + mixer(rms(h))`` of one layer of ``kind``; ``first`` is an
    expert layer's first held expert (None: the configuration's, and
    in the other kinds)."""
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


def nll(params, state, labels, prec):
    """CE of the head's logits against ``labels`` (B, S), (B, S); the
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
    """(sum of the labelled positions' next-token NLL, their number);
    ``batch`` holds ``input_ids`` and ``labels`` (the next ids,
    ``IGNORE`` where there is none) and may hold ``first_experts``."""
    labels = batch["labels"]
    firsts = batch.get("first_experts")
    state = final_state(params, batch["input_ids"], cfg, prec,
                        None if firsts is None else firsts[0])
    w = (labels != IGNORE).astype(jnp.float32)
    return (nll(params, state, labels, prec) * w).sum(), w.sum()
