"""Serve-graph builders: one pure forward function per task.

This module is the single source of truth for what a *served* forward
pass computes — the engine AOT-compiles these functions per shape
bucket (``serving/engine.py``) and the static-analysis subsystem
lowers the very same functions as canonical serving targets
(``analysis/targets.py``), so the graph the gates certify is the graph
production dispatches. It therefore must not import from
``perceiver_tpu.analysis`` or ``perceiver_tpu.serving.engine``.

Design rules (mirroring the train-step targets):

- **bf16 policy end to end** — every matmul in the serve graph runs on
  bf16 operands (``dtype_policy`` pins the MLM serve graph's
  FLOP-weighted bf16 fraction at 1.0); statistics (softmax, top-k
  scores) are computed in fp32.
- **Device-side post-processing** — top-k, argmax, and mask filling
  happen inside the compiled graph, so the host round trip carries
  kilobytes (predictions), not the (B, L, V) logits tensor.
- **Donation where it aliases** — the MLM graph returns ``filled_ids``
  (same shape/dtype as ``input_ids``) and ``is_masked`` (same as
  ``pad_mask``), so both request buffers are donated and re-used by
  XLA in place. Graphs with no alias-compatible output donate nothing
  (a donated-but-unaliasable buffer is a ``donation_check`` violation,
  not an optimization).
- **No host callbacks** — a callback stalls the device and makes the
  executable uncacheable; ``transfer_guard`` runs over every
  registered serving target with an empty allowlist.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_tpu.ops.linear import linear_apply
from perceiver_tpu.ops.mlp import mlp_apply
from perceiver_tpu.ops.norm import layer_norm_apply
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY
from perceiver_tpu.tokenizer import MASK_TOKEN_ID, PAD_TOKEN_ID


@dataclasses.dataclass(frozen=True)
class InputSpec:
    """One request-tensor slot of a serve graph.

    ``shape(batch, seq)`` yields the bucket shape (``seq`` is ignored
    by fixed-shape tasks); ``pad_value`` is what bucket padding fills
    with — chosen so padded positions are inert (PAD tokens, masked-out
    key positions, zero pixels the segmentation pad-mask drops).
    """

    name: str
    dtype: object
    shape: Callable[[int, int], Tuple[int, ...]]
    pad_value: object


@dataclasses.dataclass(frozen=True)
class ServeGraph:
    """A task's serve computation plus everything needed to bucket it.

    ``fn(params, *inputs)`` returns a dict of device arrays whose
    leading axis is the bucket batch. ``donate_argnums`` index into
    ``fn``'s positional args (params is argnum 0 and never donated —
    it stays device-resident across requests)."""

    kind: str
    model: object
    fn: Callable
    inputs: Tuple[InputSpec, ...]
    output_names: Tuple[str, ...]
    donate_argnums: Tuple[int, ...]
    # text graphs bucket over (batch, seq); image graphs only batch
    seq_bucketable: bool
    # largest servable sequence (model position table size); None for
    # fixed-shape tasks
    max_seq_len: Optional[int] = None
    # outputs whose axis 1 is the (bucket-padded) sequence axis —
    # ``serving.api.materialize`` slices them back to request length
    seq_axis_outputs: Tuple[str, ...] = ()

    def init_params(self, seed: int = 0):
        return self.model.init(jax.random.key(seed))


def mlm_serve_graph(model, *, policy: Policy = DEFAULT_POLICY,
                    top_k: int = 3,
                    max_seq_len: Optional[int] = None) -> ServeGraph:
    """MLM fill-mask graph from a built ``PerceiverMLM`` — the entry
    the ``utils/predict.py`` compat wrapper uses (it holds a model +
    params, not a task config)."""
    if max_seq_len is None:
        # TextOutputAdapter: output_shape = (max_seq_len, channels)
        max_seq_len = model.decoder.output_adapter.output_shape[0]

    def fn(params, input_ids, pad_mask):
        logits, _ = model.apply(params, input_ids, pad_mask,
                                masking=False, policy=policy)
        # scores in fp32 (norm-dtype convention); the vocab projection
        # itself ran in bf16 inside the adapter
        scores, topk_ids = jax.lax.top_k(
            logits.astype(jnp.float32), top_k)
        topk_ids = topk_ids.astype(input_ids.dtype)
        is_masked = input_ids == MASK_TOKEN_ID
        filled_ids = jnp.where(is_masked, topk_ids[..., 0], input_ids)
        return {"filled_ids": filled_ids, "topk_ids": topk_ids,
                "topk_scores": scores, "is_masked": is_masked}

    return ServeGraph(
        kind="mlm", model=model, fn=fn,
        inputs=(
            InputSpec("input_ids", jnp.int32, lambda b, s: (b, s),
                      PAD_TOKEN_ID),
            InputSpec("pad_mask", jnp.bool_, lambda b, s: (b, s), True),
        ),
        output_names=("filled_ids", "topk_ids", "topk_scores",
                      "is_masked"),
        seq_axis_outputs=("filled_ids", "topk_ids", "topk_scores",
                          "is_masked"),
        # input_ids → filled_ids and pad_mask → is_masked alias
        # exactly (shape and dtype), so both request buffers donate
        donate_argnums=(1, 2),
        seq_bucketable=True, max_seq_len=max_seq_len)


def _mlm_graph(task, policy: Policy, top_k: int) -> ServeGraph:
    return mlm_serve_graph(task.build(), policy=policy, top_k=top_k,
                           max_seq_len=task.max_seq_len)


def _classifier_fn(model, policy: Policy):
    def fn(params, *inputs):
        logits = model.apply(params, *inputs, policy=policy)
        logits = logits.astype(jnp.float32)
        return {"logits": logits,
                "probs": jax.nn.softmax(logits, axis=-1),
                "label": jnp.argmax(logits, axis=-1).astype(jnp.int32)}
    return fn


def _text_clf_graph(task, policy: Policy) -> ServeGraph:
    model = task.build()
    return ServeGraph(
        kind="text_clf", model=model, fn=_classifier_fn(model, policy),
        inputs=(
            InputSpec("input_ids", jnp.int32, lambda b, s: (b, s),
                      PAD_TOKEN_ID),
            InputSpec("pad_mask", jnp.bool_, lambda b, s: (b, s), True),
        ),
        output_names=("logits", "probs", "label"),
        # (B, L) int32/bool cannot alias the (B, C)/(B,) outputs —
        # donating them would only trip donation_check
        donate_argnums=(),
        seq_bucketable=True, max_seq_len=task.max_seq_len)


def _img_clf_graph(task, policy: Policy) -> ServeGraph:
    model = task.build()
    shape = tuple(task.image_shape)
    return ServeGraph(
        kind="img_clf", model=model, fn=_classifier_fn(model, policy),
        inputs=(InputSpec("image", jnp.float32,
                          lambda b, s: (b, *shape), 0.0),),
        output_names=("logits", "probs", "label"),
        donate_argnums=(), seq_bucketable=False)


def _seg_graph(task, policy: Policy) -> ServeGraph:
    model = task.build()
    h, w, _ = task.image_shape

    def fn(params, image):
        logits = task.forward(model, params, image, policy=policy)
        logits = logits.astype(jnp.float32)
        b = image.shape[0]
        classes = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        conf = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
        return {"classes": classes.reshape(b, h, w),
                "confidence": conf.reshape(b, h, w)}

    return ServeGraph(
        kind="seg", model=model, fn=fn,
        inputs=(InputSpec("image", jnp.float32,
                          lambda b, s: (b, h, w), 0.0),),
        output_names=("classes", "confidence"),
        donate_argnums=(), seq_bucketable=False)


def build_serve_graph(task, *, policy: Policy = DEFAULT_POLICY,
                      top_k: int = 3) -> ServeGraph:
    """Serve graph for a task config (dispatch on the task type)."""
    # imported here so graphs stays importable without the full task
    # registry at module-import time
    from perceiver_tpu.tasks import (
        ImageClassifierTask,
        MaskedLanguageModelTask,
        SegmentationTask,
        TextClassifierTask,
    )

    if isinstance(task, MaskedLanguageModelTask):
        return _mlm_graph(task, policy, top_k)
    if isinstance(task, TextClassifierTask):
        return _text_clf_graph(task, policy)
    if isinstance(task, SegmentationTask):
        return _seg_graph(task, policy)
    if isinstance(task, ImageClassifierTask):
        return _img_clf_graph(task, policy)
    raise TypeError(
        f"no serve graph for task type {type(task).__name__}; supported: "
        "MaskedLanguageModelTask, TextClassifierTask, "
        "ImageClassifierTask, SegmentationTask")


def serve_graph_shardings(graph: ServeGraph, params, mesh):
    """GSPMD shardings for a serve graph's jit over a data×model mesh:
    params take the tensor-parallel layout (``parallel/sharding``),
    request tensors and every output shard their leading (batch) axis
    over ``data``. Donation survives sharding — a donated request
    buffer and the output it aliases carry the same spec, so the
    per-shard buffers still alias in place. Returns
    ``(params_sharding, input_shardings, output_shardings)`` ready for
    ``jax.jit(graph.fn, in_shardings=..., out_shardings=...)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perceiver_tpu.parallel.sharding import param_sharding

    batch_sh = NamedSharding(mesh, P("data"))
    return (param_sharding(params, mesh),
            tuple(batch_sh for _ in graph.inputs),
            {name: batch_sh for name in graph.output_names})


# --- packed (ragged) serve graphs --------------------------------------------
#
# The packed path replaces the [B, S] rectangle with one concatenated
# token axis plus per-request (row_offsets, lengths) descriptors — the
# layout the Pallas ragged kernels (ops/ragged_attention.py) consume.
# Padding then exists only at the tail of the token buffer (to the
# token-budget bucket) and in unused request rows, and both are inert:
# the ragged cross-attention kernel skips kv blocks outside a request's
# span, and zero-length rows produce zero latents.


@dataclasses.dataclass(frozen=True)
class PackedServeGraph:
    """A seq-bucketable task's serve computation over a packed batch.

    ``fn(params, packed_ids, row_offsets, lengths)`` returns a dict of
    device arrays: *token-axis* outputs are shaped ``(T, ...)`` along
    the packed token buffer (slice per request with ``row_offsets`` /
    ``lengths``); *request-axis* outputs are shaped ``(R, ...)``.
    ``inputs`` shape callables take ``(tokens, rows)`` — the
    token-budget bucket. ``max_seq_len`` caps any single request (the
    model's position-table size)."""

    kind: str
    model: object
    fn: Callable
    inputs: Tuple[InputSpec, ...]
    output_names: Tuple[str, ...]
    donate_argnums: Tuple[int, ...]
    max_seq_len: int
    token_axis_outputs: Tuple[str, ...] = ()
    request_axis_outputs: Tuple[str, ...] = ()

    def init_params(self, seed: int = 0):
        return self.model.init(jax.random.key(seed))


_PACKED_INPUTS = (
    InputSpec("packed_ids", jnp.int32, lambda t, r: (t,), PAD_TOKEN_ID),
    # pad value is a placeholder: the engine pads unused rows with the
    # batch's total real token count (an empty span parked at the end
    # of the real tokens), not a constant
    InputSpec("row_offsets", jnp.int32, lambda t, r: (r,), 0),
    InputSpec("lengths", jnp.int32, lambda t, r: (r,), 0),
)


def _packed_rows_positions(row_offsets, lengths, tokens: int,
                           max_seq_len: int):
    """Per-token (row, in-request position) from the span descriptors.

    ``searchsorted(side="right") - 1`` maps token index → owning row;
    repeated offsets (zero-length rows) resolve to the *last* row
    starting there, and tail padding clamps to the final row — both
    yield garbage rows whose outputs the host never reads (it slices by
    real spans), so only finiteness matters there."""
    del lengths
    n_rows = row_offsets.shape[0]
    tok = jnp.arange(tokens, dtype=jnp.int32)
    rows = jnp.clip(
        jnp.searchsorted(row_offsets, tok, side="right").astype(jnp.int32) - 1,
        0, n_rows - 1)
    positions = jnp.clip(tok - jnp.take(row_offsets, rows), 0,
                         max_seq_len - 1)
    return rows, positions


def _packed_encoder_apply(encoder, params, packed_ids, positions,
                          row_offsets, lengths, *, policy: Policy,
                          block_k: int = 128):
    """Encoder forward over a packed token axis → (R, N, C) latents.

    Mirrors ``PerceiverEncoder.apply`` (hoisted kv, layer_1 then a
    ``layer_n`` scan) with the masked einsum cross-attention swapped
    for ``ragged_cross_attention``: the kv projections run ONCE over
    the packed buffer — total real tokens, not B×S — and each
    request's latents attend only to the kv blocks its span covers."""
    from perceiver_tpu.models.perceiver import self_attention_block_apply
    from perceiver_tpu.ops.attention import cross_attention_kv
    from perceiver_tpu.ops.ragged_attention import ragged_cross_attention

    n_req = row_offsets.shape[0]
    n_lat, channels = encoder.latent_shape
    num_heads = encoder.num_cross_attention_heads
    max_len = encoder.input_adapter.max_seq_len

    # (T, C) → (1, T, C): the kv projections expect a batch axis
    x_kv = encoder.input_adapter.apply_packed(
        params["input_adapter"], packed_ids, positions, policy=policy)[None]
    latent = jnp.broadcast_to(
        policy.cast_param(params["latent"])[None], (n_req, n_lat, channels))

    def layer_kv(layer_params):
        kh, vh = cross_attention_kv(layer_params["cross"]["attn"], x_kv,
                                    policy=policy)

        # (1, T, H*Dh) → (H, T, Dh)
        def heads(x):
            return x[0].reshape(x.shape[1], num_heads, -1).swapaxes(0, 1)

        return heads(kh), heads(vh)

    def one_layer(layer_params, kv, lat):
        attn = layer_params["cross"]["attn"]
        kh, vh = kv
        xq = layer_norm_apply(attn["norm_q"], lat, policy=policy)
        qh = linear_apply(attn["mha"]["q"], xq, policy=policy)
        head_dim = qh.shape[-1] // num_heads
        q = qh.reshape(n_req, n_lat, num_heads, head_dim).transpose(
            0, 2, 1, 3)
        o = ragged_cross_attention(
            q, kh, vh, row_offsets, lengths,
            scale=1.0 / (head_dim ** 0.5), block_k=block_k,
            max_len=max_len)
        o = o.transpose(0, 2, 1, 3).reshape(n_req, n_lat,
                                            num_heads * head_dim)
        o = linear_apply(attn["mha"]["out"], o, policy=policy)
        y = lat + o
        y = y + mlp_apply(layer_params["cross"]["mlp"], y, policy=policy)
        return self_attention_block_apply(
            layer_params["selfs"], y,
            num_heads=encoder.num_self_attention_heads, policy=policy)

    latent = one_layer(params["layer_1"], layer_kv(params["layer_1"]),
                       latent)
    if encoder.num_layers > 1:
        layer_n = params["layer_n"]
        kv_n = layer_kv(layer_n)

        def body(carry, _):
            return one_layer(layer_n, kv_n,
                             policy.cast_compute(carry)), None

        latent, _ = jax.lax.scan(body, latent, None,
                                 length=encoder.num_layers - 1)
    return latent


def _packed_mlm_decode(decoder, params, latent, positions, rows, *,
                       policy: Policy):
    """Per-token MLM decode: each packed token queries ITS request's
    latents via the block-diagonal ragged decode kernel, so the decoder
    runs over total real tokens instead of B×S query rows."""
    from perceiver_tpu.ops.ragged_attention import ragged_decode_attention

    n_req, n_lat, _ = latent.shape
    num_heads = decoder.num_cross_attention_heads
    tokens = positions.shape[0]
    attn = params["cross"]["attn"]

    query = jnp.take(policy.cast_param(params["query"]), positions, axis=0)
    xq = layer_norm_apply(attn["norm_q"], query, policy=policy)
    qh = linear_apply(attn["mha"]["q"], xq, policy=policy)
    head_dim = qh.shape[-1] // num_heads
    q = qh.reshape(tokens, num_heads, head_dim).swapaxes(0, 1)  # (H, T, Dh)

    xkv = layer_norm_apply(attn["norm_kv"], latent, policy=policy)
    kh = linear_apply(attn["mha"]["k"], xkv, policy=policy)
    vh = linear_apply(attn["mha"]["v"], xkv, policy=policy)
    kh = kh.reshape(n_req * n_lat, num_heads, head_dim).swapaxes(0, 1)
    vh = vh.reshape(n_req * n_lat, num_heads, head_dim).swapaxes(0, 1)

    o = ragged_decode_attention(q, kh, vh, rows, latents_per_row=n_lat,
                                scale=1.0 / (head_dim ** 0.5))
    o = o.swapaxes(0, 1).reshape(tokens, num_heads * head_dim)
    o = linear_apply(attn["mha"]["out"], o, policy=policy)
    x = query + o
    hidden = x + mlp_apply(params["cross"]["mlp"], x, policy=policy)
    return linear_apply(params["output_adapter"]["linear"], hidden,
                        policy=policy)  # (T, V)


def packed_mlm_serve_graph(model, *, policy: Policy = DEFAULT_POLICY,
                           top_k: int = 3,
                           max_seq_len: Optional[int] = None,
                           block_k: int = 128) -> PackedServeGraph:
    if max_seq_len is None:
        max_seq_len = model.decoder.output_adapter.output_shape[0]

    def fn(params, packed_ids, row_offsets, lengths):
        tokens = packed_ids.shape[0]
        rows, positions = _packed_rows_positions(
            row_offsets, lengths, tokens, max_seq_len)
        latent = _packed_encoder_apply(
            model.encoder, params["encoder"], packed_ids, positions,
            row_offsets, lengths, policy=policy, block_k=block_k)
        logits = _packed_mlm_decode(model.decoder, params["decoder"],
                                    latent, positions, rows, policy=policy)
        scores, topk_ids = jax.lax.top_k(
            logits.astype(jnp.float32), top_k)
        topk_ids = topk_ids.astype(packed_ids.dtype)
        is_masked = packed_ids == MASK_TOKEN_ID
        # lax.select, not jnp.where: jnp.where is a jitted wrapper
        # whose module-level _where func dedups against the identical
        # inner func of the jitted takes — a dedup that depends on
        # jit-cache retention across lowerings, so module text (and the
        # exec-cache key) would drift with process history
        filled_ids = jax.lax.select(is_masked, topk_ids[..., 0],
                                    packed_ids)
        return {"filled_ids": filled_ids, "topk_ids": topk_ids,
                "topk_scores": scores, "is_masked": is_masked}

    return PackedServeGraph(
        kind="mlm_packed", model=model, fn=fn, inputs=_PACKED_INPUTS,
        output_names=("filled_ids", "topk_ids", "topk_scores",
                      "is_masked"),
        token_axis_outputs=("filled_ids", "topk_ids", "topk_scores",
                            "is_masked"),
        # packed_ids (T,) int32 aliases filled_ids exactly; the span
        # descriptors are tiny and re-read by the host, so they stay
        donate_argnums=(1,),
        max_seq_len=max_seq_len)


def packed_text_clf_serve_graph(task, *,
                                policy: Policy = DEFAULT_POLICY,
                                block_k: int = 128) -> PackedServeGraph:
    model = task.build()
    max_seq_len = task.max_seq_len

    def fn(params, packed_ids, row_offsets, lengths):
        tokens = packed_ids.shape[0]
        _, positions = _packed_rows_positions(
            row_offsets, lengths, tokens, max_seq_len)
        latent = _packed_encoder_apply(
            model.encoder, params["encoder"], packed_ids, positions,
            row_offsets, lengths, policy=policy, block_k=block_k)
        # per-request latents are an ordinary (R, N, C) batch — the
        # rectangular decoder applies unchanged (latent kv, no padding)
        logits = model.decoder.apply(params["decoder"], latent,
                                     policy=policy)
        logits = logits.astype(jnp.float32)
        return {"logits": logits,
                "probs": jax.nn.softmax(logits, axis=-1),
                "label": jnp.argmax(logits, axis=-1).astype(jnp.int32)}

    return PackedServeGraph(
        kind="text_clf_packed", model=model, fn=fn, inputs=_PACKED_INPUTS,
        output_names=("logits", "probs", "label"),
        request_axis_outputs=("logits", "probs", "label"),
        donate_argnums=(),
        max_seq_len=max_seq_len)


def build_packed_serve_graph(task, *, policy: Policy = DEFAULT_POLICY,
                             top_k: int = 3) -> PackedServeGraph:
    """Packed serve graph for a seq-bucketable task config. Fixed-shape
    (image) tasks have nothing to pack — rectangles remain their only
    path."""
    from perceiver_tpu.tasks import (
        MaskedLanguageModelTask,
        TextClassifierTask,
    )

    if isinstance(task, MaskedLanguageModelTask):
        return packed_mlm_serve_graph(task.build(), policy=policy,
                                      top_k=top_k,
                                      max_seq_len=task.max_seq_len)
    if isinstance(task, TextClassifierTask):
        return packed_text_clf_serve_graph(task, policy=policy)
    raise TypeError(
        f"no packed serve graph for task type {type(task).__name__}; "
        "supported: MaskedLanguageModelTask, TextClassifierTask "
        "(fixed-shape tasks serve rectangles)")
