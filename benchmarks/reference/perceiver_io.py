"""Plain reference for both benchmark configurations: Perceiver IO
forward pass, losses, gradients and three AdamW steps in float32.

It follows the published description (Jaegle et al., "Perceiver IO",
arXiv:2107.14795, in the simplified form of the perceiver-io repository
this program was modelled on): an input adapter, a learned latent
array, ``num_layers`` encoder layers of which the first has its own
weights and the others share one set, each a cross-attention (latent <-
input, key padding masked) followed by a block of self-attention
layers; a decoder of one cross-attention (output queries <- latent);
an output adapter. Every attention and MLP is pre-norm with a residual,
the MLP is Linear-GELU(erf)-Linear at the channel width.

It imports nothing of the program and reads nothing the program made:
weights come from ``benchmarks/weights.py`` (the benchmark's own, from
the seed), in the parameter-tree layout the program also takes.

Departures from ``perceiver_tpu/models/perceiver.py`` and why:

* float32 everywhere, matmuls at ``Precision.HIGHEST`` (the program
  computes in bfloat16 with float32 parameters and statistics);
* the stacked self-attention layers are a ``lax.scan`` over the stacked
  parameters with ``jax.checkpoint`` on a layer: the loop itself, so
  that the reference compiles in seconds and fits beside nothing else;
  no custom VJPs, no hoisted key/value projection, no packed loss, no
  kernels, no cache;
* the MLM loss reads every position's logits and weights the unmasked
  ones by zero (the program decodes only masked positions);
* ``mlm_mask`` re-derives the program's BERT masking from the trainer's
  seed with JAX's public PRNG, because the program masks inside its
  step: same key, same draws, same corrupted ids and labels;
* decode is one full forward pass per position over the whole prefix
  (the program keeps projected keys and values in a paged cache).

``matmul`` is the one place precision is chosen, so the control (the
same reference in the nearest precision below the configuration's
bfloat16: fp8-e4m3 operands with a per-tensor scale) shares every other
line with the float32 reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.comparisons import leaf_norms

IGNORE = -100
_HI = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "fp8")


def _q8(x):
    """Round to fp8-e4m3 under a per-tensor scale (amax -> 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = jax.lax.stop_gradient(amax / 448.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    # straight-through: the rounding has no gradient of its own
    return x + jax.lax.stop_gradient(q * s - x)


def _q16(x):
    return x + jax.lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def matmul(spec: str, a, b, precision: str):
    """``einsum`` in float32 on operands rounded to ``precision``."""
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    elif precision == "bf16":
        a, b = _q16(a), _q16(b)
    elif precision != "f32":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jnp.einsum(spec, a, b, precision=_HI)


# --- layers ------------------------------------------------------------------


def layer_norm(p, x, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def linear(p, x, prec):
    return matmul("...i,io->...o", x, p["w"], prec) + p["b"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def mlp(p, x, prec):
    h = linear(p["fc1"], layer_norm(p["norm"], x), prec)
    return linear(p["fc2"], gelu(h), prec)


def attention(p, xq, xkv, heads, pad_mask, prec):
    """Multi-head attention on already-normed inputs; ``pad_mask`` is
    (B, Lk), True at padding."""
    b, lq, c = xq.shape
    d = c // heads
    q = linear(p["q"], xq, prec).reshape(b, lq, heads, d)
    k = linear(p["k"], xkv, prec).reshape(b, -1, heads, d)
    v = linear(p["v"], xkv, prec).reshape(b, -1, heads, d)
    s = matmul("bqhd,bkhd->bhqk", q / math.sqrt(d), k, prec)
    if pad_mask is not None:
        s = jnp.where(pad_mask[:, None, None, :], -1e30, s)
    w = jax.nn.softmax(s, axis=-1)
    o = matmul("bhqk,bkhd->bqhd", w, v, prec).reshape(b, lq, c)
    return linear(p["out"], o, prec)


def cross_layer(p, xq, xkv, heads, pad_mask, prec):
    a = p["attn"]
    x = xq + attention(a["mha"], layer_norm(a["norm_q"], xq),
                       layer_norm(a["norm_kv"], xkv), heads, pad_mask, prec)
    return x + mlp(p["mlp"], x, prec)


def self_layer(p, x, heads, prec):
    xn = layer_norm(p["attn"]["norm"], x)
    x = x + attention(p["attn"]["mha"], xn, xn, heads, None, prec)
    return x + mlp(p["mlp"], x, prec)


def self_block(stacked, x, heads, prec):
    @jax.checkpoint
    def body(x, layer):
        return self_layer(layer, x, heads, prec), None

    return jax.lax.scan(body, x, stacked)[0]


# --- adapters ----------------------------------------------------------------


def text_embed(p, ids):
    c = p["embed"].shape[1]
    return p["embed"][ids] * math.sqrt(c) + p["pos"][:ids.shape[1]][None]


def fourier_encoding(spatial, bands):
    """(prod(spatial), ndim * (2 * bands + 1)): positions in [-1, 1],
    then sin(pi f p) for every dimension, then cos, with ``bands``
    frequencies from 1 to size / 2 per dimension."""
    coords = [np.linspace(-1.0, 1.0, s) for s in spatial]
    pos = np.stack(np.meshgrid(*coords, indexing="ij"), axis=-1)
    grids = [pos[..., i:i + 1] * np.linspace(1.0, s / 2.0, bands)
             for i, s in enumerate(spatial)]
    enc = np.concatenate([pos] + [np.sin(math.pi * g) for g in grids]
                         + [np.cos(math.pi * g) for g in grids], axis=-1)
    return enc.reshape(-1, enc.shape[-1]).astype(np.float32)


def image_embed(images, bands):
    b, *spatial, c = images.shape
    enc = jnp.asarray(fourier_encoding(tuple(spatial), bands))
    return jnp.concatenate(
        [images.reshape(b, -1, c),
         jnp.broadcast_to(enc[None], (b, *enc.shape))], axis=-1)


# --- model -------------------------------------------------------------------


def encoder(p, x, pad_mask, cfg, prec):
    heads_x = cfg["num_encoder_cross_attention_heads"]
    heads_s = cfg["num_encoder_self_attention_heads"]
    latent = jnp.broadcast_to(p["latent"][None],
                              (x.shape[0], *p["latent"].shape))
    for i in range(cfg["num_encoder_layers"]):
        layer = p["layer_1"] if i == 0 else p["layer_n"]
        latent = jax.checkpoint(cross_layer, static_argnums=(3, 5))(
            layer["cross"], latent, x, heads_x, pad_mask, prec)
        latent = self_block(layer["selfs"], latent, heads_s, prec)
    return latent


def decoder_hidden(p, latent, query, cfg, prec):
    return cross_layer(p["cross"], query, latent,
                       cfg["num_decoder_cross_attention_heads"], None, prec)


def mlm_logits(params, ids, pad_mask, cfg, prec="f32"):
    """(B, L, V) logits of the masked-language model on ``ids``."""
    x = text_embed(params["encoder"]["input_adapter"], ids)
    latent = encoder(params["encoder"], x, pad_mask, cfg, prec)
    pd = params["decoder"]
    query = jnp.broadcast_to(pd["query"][None, :ids.shape[1]],
                             (ids.shape[0], ids.shape[1],
                              pd["query"].shape[1]))
    hidden = decoder_hidden(pd, latent, query, cfg, prec)
    return linear(pd["output_adapter"]["linear"], hidden, prec)


def next_token_logits(params, ids, n, cfg, prec="f32"):
    """Decode as the program serves it: encode the first ``n[b]``
    tokens of row b (the rest is padding), then decode the one output
    query at position ``n[b]``. Returns (B, V)."""
    pad = jnp.arange(ids.shape[1])[None, :] >= n[:, None]
    x = text_embed(params["encoder"]["input_adapter"], ids)
    latent = encoder(params["encoder"], x, pad, cfg, prec)
    pd = params["decoder"]
    query = pd["query"][n][:, None, :]
    hidden = decoder_hidden(pd, latent, query, cfg, prec)
    return linear(pd["output_adapter"]["linear"], hidden, prec)[:, 0]


def image_logits(params, images, cfg, prec="f32"):
    x = image_embed(images, cfg["num_frequency_bands"])
    latent = encoder(params["encoder"], x, None, cfg, prec)
    pd = params["decoder"]
    query = jnp.broadcast_to(pd["query"][None],
                             (images.shape[0], *pd["query"].shape))
    hidden = decoder_hidden(pd, latent, query, cfg, prec)
    return linear(pd["output_adapter"]["linear"], hidden, prec)[:, 0]


# --- losses ------------------------------------------------------------------


def _nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(
        logp, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]


def mlm_mask(key, ids, pad_mask, cfg):
    """The program's masking for one step, re-derived: 15% of the
    positions that are neither padding nor [UNK] are selected; of
    those 90% are corrupted, a ninth of the corrupted by a random
    non-special id and the rest by [MASK]. ``key`` is the step's key
    (``trainer_step_keys``); the model splits it three ways and the
    masking takes the first part four ways."""
    k_mask = jax.random.split(key, 3)[0]
    r_sel, r_corrupt, r_rand, r_ids = jax.random.split(k_mask, 4)
    is_input = ~((ids == cfg["unk_token_id"]) | pad_mask)
    selected = (jax.random.uniform(r_sel, ids.shape) < cfg["mask_p"]) \
        & is_input
    corrupted = selected & (jax.random.uniform(r_corrupt, ids.shape) < 0.9)
    random = corrupted & (jax.random.uniform(r_rand, ids.shape) < 1.0 / 9.0)
    random_ids = jax.random.randint(
        r_ids, ids.shape, cfg["num_special_tokens"], cfg["vocab_size"],
        dtype=ids.dtype)
    masked = jnp.where(corrupted, cfg["mask_token_id"], ids)
    masked = jnp.where(random, random_ids, masked)
    return masked, jnp.where(selected, ids, IGNORE)


def trainer_step_keys(seed: int, steps: int):
    """The key each of the trainer's first ``steps`` steps hands its
    loss: the state's key is the second half of ``key(seed)``, and
    every step splits it into (next state key, step key)."""
    rng = jax.random.split(jax.random.key(seed))[1]
    keys = []
    for _ in range(steps):
        rng, step_key = jax.random.split(rng)
        keys.append(step_key)
    return keys


def mlm_loss_sum(params, batch, cfg, prec):
    """(sum of the masked positions' losses, their number)."""
    logits = mlm_logits(params, batch["masked_ids"], batch["pad_mask"],
                        cfg, prec)
    w = (batch["labels"] != IGNORE).astype(jnp.float32)
    return (_nll(logits, batch["labels"]) * w).sum(), w.sum()


def image_loss_sum(params, batch, cfg, prec):
    logits = image_logits(params, batch["image"], cfg, prec)
    return _nll(logits, batch["label"]).sum(), \
        jnp.float32(batch["label"].shape[0])


def loss_and_grads(params, batch, cfg, *, loss_sum, prec="f32", block=2):
    """Mean loss over the batch and its gradient, accumulated over
    blocks of ``block`` rows so that the float32 activations fit.
    ``loss_sum(params, batch, cfg, prec)`` is the task's: (sum of the
    rows' terms, their number)."""
    fn = _grad_fn(loss_sum, _freeze(cfg), prec)
    rows = next(iter(batch.values())).shape[0]
    total = count = 0.0
    grads = None
    for i in range(0, rows, block):
        part = {k: v[i:i + block] for k, v in batch.items()}
        (s, n), g = fn(params, part)
        total, count = total + s, count + n
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = jnp.maximum(count, 1.0)
    return total / count, jax.tree.map(lambda g: g / count, grads)


def _freeze(cfg):
    """``cfg`` as a hashable key (lists become tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


@functools.lru_cache(maxsize=None)
def _grad_fn(loss_sum, frozen, prec):
    """One jitted gradient function per (loss, configuration,
    precision), so that the steps' blocks share a compilation."""
    cfg = dict(frozen)
    return jax.jit(jax.value_and_grad(
        lambda p, b: loss_sum(p, b, cfg, prec), has_aux=True))


# --- optimizer ---------------------------------------------------------------


@jax.jit
def _adamw(params, grads, m, v, t, lr, wd):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def step(p, m, v):
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    return jax.tree.map(step, params, m, v), m, v


def train_steps(params, batches, cfg, *, loss_sum, lr, weight_decay,
                prec="f32", block=2):
    """Follow the trainer through ``len(batches)`` AdamW steps at a
    constant learning rate. Returns each step's loss, the first
    gradient's norm per leaf and the norm per leaf of the parameters'
    change over all the steps."""
    zeros = jax.tree.map(jnp.zeros_like, params)
    p, m, v = params, zeros, zeros
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grads(p, batch, cfg, loss_sum=loss_sum,
                                     prec=prec, block=block)
        losses.append(float(loss))
        if first is None:
            first = leaf_norms(grads)
        p, m, v = _adamw(p, grads, m, v, jnp.float32(t), jnp.float32(lr),
                         jnp.float32(weight_decay))
    moved = leaf_norms(jax.tree.map(jnp.subtract, p, params))
    return {"losses": losses, "grad_norms": first, "update_norms": moved}

