"""Memory-efficient fused linear + cross-entropy for huge vocabularies.

The reference computes MLM loss as CE over dense ``(B, M, V)`` logits
(``perceiver/lightning.py:223-226``) — fine at V=10003 on GPU batch 64,
but on TPU the fp32 log-softmax over ``(B, 512, 10003)`` is the HBM
hot spot: at batch 512 the logits alone exceed v5e HBM. Two TPU-first
levers, both exact w.r.t. the dense computation:

1. ``fused_linear_cross_entropy`` — never materializes the full logits.
   Positions are processed in chunks under ``jax.checkpoint``: each
   chunk projects to the vocab on the MXU, reduces to per-position NLL
   in fp32, and discards its logits; the backward pass recomputes them
   per chunk. Peak memory is one chunk of logits instead of all of them.

2. ``pack_positions`` — MLM loss touches only the ~15% of positions
   selected by BERT masking (labels of non-selected positions are the
   ignore value, so their NLL is multiplied by zero and their logit
   gradient is exactly zero). A cumsum + scatter packs the contributing
   positions into a fixed-capacity buffer, so the dominant vocab
   projection runs on ~15% of the rows. Gradients are identical to the
   dense computation (zero-weight rows contribute zero either way);
   the only approximation is the static capacity, chosen so overflow
   has negligible probability (a Chernoff bound at capacity 1.5× the
   expected count is astronomically small for B·M ≥ 2¹⁵).

``fused_linear_nll`` is lever 1 with the per-position NLL handed back
unreduced, for losses whose weights take a gradient (the exit
probabilities of ``models/looped_lm.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY


@device_scope("loss")
def pack_positions(hidden, labels, weight, capacity: int):
    """Scatter rows with nonzero ``weight`` into a ``capacity``-row buffer.

    hidden: (N, C); labels: (N,) int; weight: (N,) fp32 (0 or positive).
    Returns ``(hidden_p, labels_p, weight_p, overflow)`` where the
    packed arrays have leading dim ``capacity`` and ``overflow`` is the
    scalar int32 count of contributing rows DROPPED because they fell
    past ``capacity``. Rows beyond the number of contributing positions
    have weight 0. Overflow silently biases the loss (the dropped rows'
    gradients vanish), so callers must surface a nonzero count instead
    of swallowing it — size ``capacity`` generously (module docstring)
    and treat ``overflow > 0`` as a configuration error to report.
    """
    n, c = hidden.shape
    contributes = weight > 0
    dest = jnp.cumsum(contributes.astype(jnp.int32)) - 1
    # all-zero weight: cumsum[-1]=0 → dest[-1]+1 = 0, no guard needed
    n_contributing = dest[-1] + 1
    overflow = jnp.maximum(n_contributing - capacity, 0)
    # non-contributing and overflow rows all land on a dump row that is
    # sliced off below (duplicate scatter indices are fine there)
    dest = jnp.where(contributes & (dest < capacity), dest, capacity)
    hidden_p = jnp.zeros((capacity + 1, c), hidden.dtype).at[dest].set(hidden)
    labels_p = jnp.zeros((capacity + 1,), labels.dtype).at[dest].set(labels)
    weight_p = jnp.zeros((capacity + 1,), jnp.float32).at[dest].set(
        weight.astype(jnp.float32))
    return (hidden_p[:capacity], labels_p[:capacity], weight_p[:capacity],
            overflow)


def _project_f32(policy, params, h):
    """fp32-accumulated vocab projection: one fp32 logits write instead
    of a compute-dtype write plus an fp32 convert copy (the log-softmax
    consumer needs fp32 either way)."""
    w = policy.cast_param(params["w"])
    b = params["b"].astype(jnp.float32)
    return jnp.dot(policy.cast_compute(h), w,
                   preferred_element_type=jnp.float32) + b


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunk_nll_sum(policy, params, h, y, w):
    """``sum(w · nll(linear(h), y))`` for one chunk, via logsumexp.

    The custom VJP is what keeps this memory-bounded: forward reduces
    the fp32 logits straight to per-row ``(lse, picked-logit)`` without
    materializing the log-probabilities, and backward recomputes the
    logits once and emits the compute-dtype softmax-minus-onehot
    cotangent directly into the two grad contractions. Autodiff of the
    naive form writes + rereads the fp32 ``(chunk, V)`` log-softmax
    block three times per step (round-5 trace, vocab-CE bucket).
    """
    logits = _project_f32(policy, params, h)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, jnp.clip(y, 0)[:, None], axis=1)
    nll = (lse - picked)[:, 0]
    return (nll * w).sum()


def _chunk_nll_fwd(policy, params, h, y, w):
    return _chunk_nll_sum(policy, params, h, y, w), (params, h, y, w)


def _chunk_nll_bwd(policy, res, g):
    params, h, y, w = res
    logits = _project_f32(policy, params, h)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, jnp.clip(y, 0)[:, None], axis=1)
    # d nll / d logits = softmax - onehot, weighted per row
    wg = (w * g).astype(jnp.float32)[:, None]
    onehot = (jnp.arange(logits.shape[-1])[None, :]
              == jnp.clip(y, 0)[:, None])
    dlogits = (jnp.exp(logits - lse) - onehot) * wg
    db = jnp.sum(dlogits, axis=0).astype(params["b"].dtype)
    # compute-dtype operands for the two big contractions (MXU rate);
    # the fp32 chain above fuses into this one reduced-precision write
    dl = dlogits.astype(policy.compute_dtype)
    hc = policy.cast_compute(h)
    wc = policy.cast_param(params["w"])
    dw = jnp.dot(hc.T, dl,
                 preferred_element_type=jnp.float32).astype(
                     params["w"].dtype)
    dh = jnp.dot(dl, wc.T).astype(h.dtype)
    dwt = ((lse - picked)[:, 0] * g).astype(w.dtype)
    return {"w": dw, "b": db}, dh, None, dwt


_chunk_nll_sum.defvjp(_chunk_nll_fwd, _chunk_nll_bwd)


@device_scope("loss")
def fused_linear_cross_entropy(linear_params, hidden, labels, weight, *,
                               chunk_size: int = 8192,
                               policy: Policy = DEFAULT_POLICY):
    """Weighted-mean CE of ``linear(hidden)`` vs ``labels``, chunked.

    hidden: (N, C) flattened positions; labels: (N,) int (any value on
    zero-weight rows); weight: (N,) fp32. Numerically identical to
    ``cross_entropy(linear_apply(params, hidden), labels)`` with the
    same fp32 log-softmax statistics, but peak memory is one
    ``(chunk, V)`` logits block and the backward pass recomputes
    logits chunk-by-chunk (``_chunk_nll_sum``).
    Returns scalar ``sum(w·nll) / max(sum(w), 1)``.
    """
    n, c = hidden.shape
    if n % chunk_size != 0:
        pad = chunk_size - n % chunk_size
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        weight = jnp.pad(weight, (0, pad))
        n += pad
    k = n // chunk_size
    hidden = hidden.reshape(k, chunk_size, c)
    labels = labels.reshape(k, chunk_size)
    weight = weight.reshape(k, chunk_size).astype(jnp.float32)

    def body(carry, xs):
        h, y, w = xs
        return carry + _chunk_nll_sum(policy, linear_params, h, y, w), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (hidden, labels, weight))
    return total / jnp.maximum(weight.sum(), 1.0)


# --- per-position NLL --------------------------------------------------------
# The sibling for losses that are not a weighted mean with constant
# weights: a looped LM reads the head once a pass and mixes the passes'
# NLLs with exit probabilities that take a gradient themselves, and
# divides by the number of labelled positions. Returning the NLL per
# position leaves the mixing (and its gradient to the weights) to plain
# autodiff outside; the logits stay one chunk at a time as above. The
# backward pass is written out, not the transpose of the forward scan:
# that keeps the head's fp32 gradient twice (the running sum and each
# chunk's term) and carries the fp32 head through both loops, which
# costs a copy of it a loop once the step donates its parameters. Here
# the head is cast once, the loops carry the cast, and the gradient is
# one fp32 accumulator added into in place.


def _nll_logits(policy, w, b, h):
    logits = jnp.dot(policy.cast_compute(h), w,
                     preferred_element_type=jnp.float32)
    return logits if b is None else logits + b.astype(jnp.float32)


def _lse_and_picked(logits, y):
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True))
    return lse, jnp.take_along_axis(logits, jnp.clip(y, 0)[:, None], axis=1)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nll_chunks(policy, params, hidden, labels):
    """``nll(linear(h), y)`` per row, fp32: hidden (K, chunk, C),
    labels (K, chunk) -> (K, chunk)."""
    return _nll_chunks_fwd(policy, params, hidden, labels)[0]


def _nll_chunks_fwd(policy, params, hidden, labels):
    w, b = policy.cast_param(params["w"]), params.get("b")

    def body(_, xs):
        lse, picked = _lse_and_picked(_nll_logits(policy, w, b, xs[0]),
                                      xs[1])
        return None, (lse - picked)[:, 0]

    _, nll = jax.lax.scan(body, None, (hidden, labels))
    return nll, (params, w, hidden, labels)


def _nll_chunks_bwd(policy, res, g):
    """The logits of a chunk are recomputed once; (softmax - onehot) x
    the rows' cotangents goes in the compute dtype into the two
    contractions, as in ``_chunk_nll_bwd``."""
    params, w, hidden, labels = res
    b = params.get("b")

    def body(acc, xs):
        h, y, g_rows = xs
        logits = _nll_logits(policy, w, b, h)
        lse, _ = _lse_and_picked(logits, y)
        onehot = (jnp.arange(logits.shape[-1])[None, :]
                  == jnp.clip(y, 0)[:, None])
        dlogits = (jnp.exp(logits - lse) - onehot) \
            * g_rows.astype(jnp.float32)[:, None]
        dl = dlogits.astype(policy.compute_dtype)
        acc = dict(acc, w=acc["w"] + jnp.dot(
            policy.cast_compute(h).T, dl,
            preferred_element_type=jnp.float32))
        if b is not None:
            acc["b"] = acc["b"] + jnp.sum(dlogits, axis=0)
        return acc, jnp.dot(dl, w.T).astype(h.dtype)

    acc, dh = jax.lax.scan(
        body, {k: jnp.zeros(v.shape, jnp.float32)
               for k, v in params.items()}, (hidden, labels, g))
    return ({k: v.astype(params[k].dtype) for k, v in acc.items()}, dh,
            None)


_nll_chunks.defvjp(_nll_chunks_fwd, _nll_chunks_bwd)


@device_scope("loss")
def fused_linear_nll(linear_params, hidden, labels, *,
                     chunk_size: int = 8192,
                     policy: Policy = DEFAULT_POLICY):
    """Per-position NLL of ``linear(hidden)`` vs ``labels``, chunked.

    hidden: (N, C); labels: (N,) int (clipped at 0: give unlabelled
    rows any id and weight them out). ``linear_params`` may lack ``b``.
    Returns (N,) fp32. Differentiable in ``hidden`` and the head; what
    the caller multiplies the rows by takes its gradient from autodiff.
    Peak memory is one ``(chunk, V)`` logits block in either pass.
    """
    n, c = hidden.shape
    chunk_size = min(chunk_size, n)
    pad = -n % chunk_size
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
    k = (n + pad) // chunk_size
    nll = _nll_chunks(policy, linear_params,
                      hidden.reshape(k, chunk_size, c),
                      labels.reshape(k, chunk_size))
    return nll.reshape(-1)[:n]
