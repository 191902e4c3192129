"""Canonical lowering targets: one jitted train step per task, at the
shapes the benchmarks and runbooks actually pin.

Each target rebuilds, from scratch, the step the trainer runs
(forward + backward + AdamW, params and optimizer state donated) and
lowers it on the CPU backend — StableHLO lowering is platform-
independent, so the dtype/transfer/donation properties gated here are
the ones the chip will see. The targets also define the per-config
allowlists: every exception is written down next to the config it
covers, with a reason (the allowlist is the audit trail, not an
escape hatch).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

from perceiver_tpu.analysis.report import (
    DtypeAllow,
    ReplicationAllow,
    TransferAllow,
)

@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh for a sharded target: ordered (axis, size)
    pairs, outermost first — ``(("data", 2), ("model", 2))`` is the
    dp2×tp2 layout ``parallel/mesh.make_mesh`` builds. Declarative so
    targets stay import-cheap (no jax at module import) and the
    descriptor can key caches/manifests without building devices."""

    axes: Tuple[Tuple[str, int], ...]

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.axes)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(n for _, n in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    @property
    def descriptor(self) -> str:
        """Stable string identity: ``"data2_model2"`` — the manifest
        key suffix and the lowering-cache key extra."""
        return "_".join(f"{name}{n}" for name, n in self.axes)

    def build(self):
        """Mesh over the first ``n_devices`` devices in iota order —
        the same layout ``parallel/mesh.make_mesh`` produces, and the
        order the collective-attribution pass assumes. On CPU, run
        under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
        (conftest.py and scripts/check.py both force it)."""
        import jax
        import numpy as np

        devices = jax.devices()
        if len(devices) < self.n_devices:
            raise ValueError(
                f"mesh {self.descriptor} needs {self.n_devices} devices, "
                f"backend has {len(devices)}; on CPU set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8")
        arr = np.array(devices[:self.n_devices]).reshape(self.shape)
        return jax.sharding.Mesh(arr, self.axis_names)


@dataclasses.dataclass(frozen=True)
class StepTarget:
    """One canonical (task config, input shapes) pair to lower and gate.

    ``build`` returns a fresh ``(task, batch)`` every call — the
    recompile-budget pass relies on independent rebuilds producing
    byte-identical step signatures.

    ``kind`` selects what gets lowered: ``"train"`` is the full
    forward + backward + AdamW step (``make_train_step``); ``"serve"``
    is the task's serve graph (``serving/graphs.py``) at its bucket
    shapes — the exact executable ``ServingEngine`` AOT-compiles, so
    the gates certify the graph production dispatches.

    ``mesh`` turns the target SPMD: the step is built with explicit
    shardings over ``mesh.build()`` (``training/spmd.py`` /
    ``serving/graphs.serve_graph_shardings``) and additionally
    compiled, because GSPMD inserts collectives during SPMD
    partitioning — the shardcheck passes parse the optimized HLO.
    """

    name: str
    build: Callable[[], Tuple[object, dict]]
    # headline targets additionally assert bf16_flop_fraction == 1.0
    headline: bool = False
    transfer_allow: Tuple[TransferAllow, ...] = ()
    dtype_allow: Tuple[DtypeAllow, ...] = ()
    kind: str = "train"
    mesh: Optional[MeshSpec] = None
    replication_allow: Tuple[ReplicationAllow, ...] = ()
    # the name of another canonical target this one MUST share its
    # step signature with — a positive gate, not an allowlist entry:
    # the recompile_budget pass asserts the two fingerprints are
    # EQUAL (and excludes the twin from the distinct-targets collapse
    # check). Used by the multi-tenant decode round, whose whole claim
    # is that tenancy never mints a new compile key.
    signature_twin: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class LoweredStep:
    """A lowered target: the StableHLO text plus the donation contract
    derived from the live arguments."""

    target: StepTarget
    text: str
    # leaves of (params, opt_state) — every one must be donated AND
    # aliased onto an output by lowering
    expected_donated: int
    # None when the step was reconstructed from a cache record —
    # Python's salted str hashing makes task hashes incomparable
    # across processes, so cached steps opt out of that check
    task_hash: Optional[int]
    # XLA HLO-cost-analysis "bytes accessed" of the lowered module
    # (scan/while bodies counted once) — the hbm_budget pass's metric.
    # None when the backend exposes no lowering-time cost analysis.
    bytes_accessed: Optional[float] = None
    # True when served from a persistent lowering record (a previous
    # process's lowering of the same source tree) instead of a fresh
    # trace — see perceiver_tpu/cache
    cached: bool = False
    # optimized-HLO text of the compiled executable — mesh targets
    # only (GSPMD collectives exist nowhere else). None when the
    # target is unsharded or the caller asked to skip compilation.
    compiled_text: Optional[str] = None


def cost_bytes_accessed(lowered) -> Optional[float]:
    """``bytes accessed`` from a ``jax.stages.Lowered`` cost analysis,
    or None where the backend only exposes post-compile analysis."""
    try:
        cost = lowered.cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    value = cost.get("bytes accessed")
    return float(value) if value is not None else None


def make_train_step(task, batch):
    """The canonical single-optimizer-step jit: forward + backward +
    AdamW with (params, opt_state) donated — the step every benchmark
    and the trainer's hot loop run. Returns ``(jitted_fn, args)``."""
    import jax
    import optax

    from perceiver_tpu.ops.policy import Policy

    model = task.build()
    policy = Policy.bf16()
    params = model.init(jax.random.key(0))
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch_i, key):
        def loss_fn(p):
            loss, _ = task.loss_and_metrics(
                model, p, batch_i, rng=key, deterministic=False,
                policy=policy)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step, (params, opt_state, batch, jax.random.key(1))


def make_serve_step(task, batch):
    """The canonical serve-graph jit for a task: the same function —
    with the same donation layout — that ``ServingEngine`` AOT-compiles
    per bucket. Returns ``(jitted_fn, args, expected_donated)``; only
    the donated request buffers (which alias outputs by construction,
    see serving/graphs.py) count toward ``expected_donated``."""
    import jax

    from perceiver_tpu.serving.graphs import build_serve_graph

    graph = build_serve_graph(task)
    params = graph.init_params()
    args = (params,) + tuple(batch[spec.name] for spec in graph.inputs)
    jitted = jax.jit(graph.fn, donate_argnums=graph.donate_argnums)
    donated_args = tuple(args[i] for i in graph.donate_argnums)
    expected = len(jax.tree_util.tree_leaves(donated_args))
    return jitted, args, expected


def make_packed_serve_step(task, batch):
    """The packed ragged serve-graph jit for a task — the executable
    ``ServingEngine.dispatch_packed`` AOT-compiles per token-budget
    bucket. Returns ``(jitted_fn, args, expected_donated)``: the MLM
    packed graph donates ``packed_ids`` (aliases ``filled_ids``)."""
    import jax

    from perceiver_tpu.serving.graphs import build_packed_serve_graph

    graph = build_packed_serve_graph(task)
    params = graph.init_params()
    args = (params,) + tuple(batch[spec.name] for spec in graph.inputs)
    jitted = jax.jit(graph.fn, donate_argnums=graph.donate_argnums)
    donated_args = tuple(args[i] for i in graph.donate_argnums)
    expected = len(jax.tree_util.tree_leaves(donated_args))
    return jitted, args, expected


def make_decode_step(task, batch):
    """The unified prefill+decode step jit — the exact executable
    ``DecodeEngine`` AOT-compiles once per pool geometry and then runs
    for every step of every stream (serving/decode.py). ``batch``
    carries the ``DecodeGeometry`` plus one MIXED-phase round of
    per-slot ``tokens`` (streams × max_chunk lanes) and ``qlens``
    (chunked-prefill rows feed >1 token, decode rows feed 1) — the
    gates certify the single signature both phases share. Returns
    ``(jitted_fn, args, expected_donated)``: the whole carry (KV pools,
    lengths, page tables) is donated — every leaf aliases an output, so
    the step's HBM high-water mark is ONE copy of the paged cache."""
    import jax

    from perceiver_tpu.serving.decode import build_decode_graph

    graph = build_decode_graph(task.build(), batch["geometry"],
                               attn_impl=batch.get("attn_impl", "pallas"))
    params = graph.init_params()
    carry = graph.init_carry()
    args = (params, carry, batch["tokens"], batch["qlens"])
    jitted = jax.jit(graph.fn, donate_argnums=graph.donate_argnums)
    expected = len(jax.tree_util.tree_leaves(carry))
    return jitted, args, expected


def make_sharded_decode_step(task, batch, mesh):
    """The sharded decode step: params tensor-parallel (``model``),
    per-stream rows (tokens/qlens/lengths/page tables) batch-sharded
    over ``data``, and the KV pools replicated — each pool is a shared
    arena indexed by data-local page tables, and at canonical geometry
    it sits far below the replication floor (the replication pass still
    audits it). Lowers the ``"reference"`` attention path: GSPMD
    partitions gathers/einsums, not Pallas calls. Donation survives
    sharding — carry leaves and the outputs they alias carry identical
    specs. Returns ``(jitted_fn, args, expected_donated)``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perceiver_tpu.parallel.sharding import param_sharding
    from perceiver_tpu.serving.decode import build_decode_graph

    graph = build_decode_graph(task.build(), batch["geometry"],
                               attn_impl=batch.get("attn_impl",
                                                   "reference"))
    params = graph.init_params()
    carry = graph.init_carry()
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("data"))
    carry_sh = {
        "kv": {name: rep for name in carry["kv"]},
        "lengths": row,
        "page_tables": NamedSharding(mesh, P("data", None)),
    }
    args = (params, carry, batch["tokens"], batch["qlens"])
    # tokens are (streams, max_chunk): rows shard on data, the chunk
    # lanes stay local to the row's device
    tok_sh = NamedSharding(mesh, P("data", None))
    jitted = jax.jit(
        graph.fn, donate_argnums=graph.donate_argnums,
        in_shardings=(param_sharding(params, mesh), carry_sh, tok_sh,
                      row),
        out_shardings=(carry_sh,
                       {name: row for name in graph.output_names}))
    expected = len(jax.tree_util.tree_leaves(carry))
    return jitted, args, expected


def make_sharded_serve_step(task, batch, mesh):
    """The sharded serve-graph jit: the same graph + donation layout
    as ``make_serve_step``, under explicit GSPMD shardings (params
    tensor-parallel on ``model``, request/response batch axes on
    ``data``). Returns ``(jitted_fn, args, expected_donated)``."""
    import jax

    from perceiver_tpu.serving.graphs import (
        build_serve_graph,
        serve_graph_shardings,
    )

    graph = build_serve_graph(task)
    params = graph.init_params()
    p_sh, in_sh, out_sh = serve_graph_shardings(graph, params, mesh)
    args = (params,) + tuple(batch[spec.name] for spec in graph.inputs)
    jitted = jax.jit(graph.fn, donate_argnums=graph.donate_argnums,
                     in_shardings=(p_sh,) + in_sh, out_shardings=out_sh)
    donated_args = tuple(args[i] for i in graph.donate_argnums)
    expected = len(jax.tree_util.tree_leaves(donated_args))
    return jitted, args, expected


def lower_target(target: StepTarget, cache=None,
                 want_compiled: bool = True) -> LoweredStep:
    """Build the target's task + batch, lower its step (train or
    serve), and package the properties the graph passes gate on.

    ``cache`` (a ``perceiver_tpu.cache.ExecutableCache``) consults the
    persistent lowering records first: the key binds the target name
    to the jax/jaxlib versions, the backend topology, and a content
    hash of the whole source tree, so a hit is exactly the text a
    fresh trace of this code would produce — and any code edit is a
    miss. Fresh lowerings are stored back for the next process.

    Mesh targets are also XLA-compiled (collectives exist only in
    optimized HLO); the compiled text rides in the lowering record so
    warm ``check.py`` runs stay compile-free. ``want_compiled=False``
    skips that compile for callers that only need StableHLO (the
    recompile-stability re-lowering)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    key = None
    if cache is not None:
        extra = (target.mesh.descriptor,) if target.mesh else ()
        key = cache.lowering_key(target.name, extra=extra)
        record = cache.load_lowering(key)
        # a record stored by a want_compiled=False lowering of a mesh
        # target has no compiled text — useless to the collective
        # passes, so fall through to a fresh lowering
        usable = record is not None and not (
            target.mesh and want_compiled
            and not record.get("compiled_text"))
        if usable:
            return LoweredStep(
                target=target, text=record["text"],
                expected_donated=int(record["expected_donated"]),
                task_hash=None,
                bytes_accessed=record.get("bytes_accessed"),
                cached=True,
                compiled_text=record.get("compiled_text"))
    task, batch = target.build()
    mesh = target.mesh.build() if target.mesh else None
    if mesh is not None and target.kind == "train":
        from perceiver_tpu.training.spmd import make_sharded_train_step

        step, args = make_sharded_train_step(task, batch, mesh)
        params, opt_state = args[0], args[1]
        expected = len(jax.tree_util.tree_leaves((params, opt_state)))
    elif mesh is not None and target.kind == "serve":
        step, args, expected = make_sharded_serve_step(task, batch, mesh)
    elif mesh is not None and target.kind == "decode":
        step, args, expected = make_sharded_decode_step(task, batch, mesh)
    elif target.kind == "serve":
        step, args, expected = make_serve_step(task, batch)
    elif target.kind == "packed_serve":
        step, args, expected = make_packed_serve_step(task, batch)
    elif target.kind == "decode":
        step, args, expected = make_decode_step(task, batch)
    else:
        step, args = make_train_step(task, batch)
        params, opt_state = args[0], args[1]
        expected = len(jax.tree_util.tree_leaves((params, opt_state)))
    lowered = step.lower(*args)
    compiled_text = None
    if mesh is not None and want_compiled:
        from perceiver_tpu.cache import compile_lowered

        compiled_text = compile_lowered(lowered).as_text()
    result = LoweredStep(target=target, text=lowered.as_text(),
                         expected_donated=expected, task_hash=hash(task),
                         bytes_accessed=cost_bytes_accessed(lowered),
                         compiled_text=compiled_text)
    # a compile-less mesh lowering must not overwrite (or seed) a
    # record — warm runs would then miss compiled text forever
    if cache is not None and not (target.mesh and compiled_text is None):
        from perceiver_tpu.analysis import hlo

        cache.store_lowering(key, {
            "target": target.name,
            "text": result.text,
            "expected_donated": result.expected_donated,
            "bytes_accessed": result.bytes_accessed,
            "fingerprint": hlo.module_fingerprint(result.text),
            "text_hash": hlo.text_hash(result.text),
            **({"compiled_text": compiled_text, "mesh": target.mesh.descriptor}
               if target.mesh else {}),
        })
    return result


# --------------------------------------------------------------------------
# Canonical configs. Shapes are the BASELINE MLM recipe's (vocab, seq)
# at toy widths and the runbook configs' (ROADMAP D7).

def _build_mlm(batch: int = 512, channels: int = 64, seq_len: int = 512,
               vocab: int = 10003, loss_impl: str = "packed"):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=vocab, max_seq_len=seq_len, loss_impl=loss_impl,
        num_latent_channels=channels)
    rng = np.random.default_rng(0)
    data = {
        "input_ids": jnp.asarray(
            rng.integers(3, vocab, (batch, seq_len)), jnp.int32),
        "pad_mask": jnp.zeros((batch, seq_len), bool),
    }
    return task, data


def _build_text_clf(batch: int = 64, seq_len: int = 512,
                    vocab: int = 10003):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tasks import TextClassifierTask

    task = TextClassifierTask(vocab_size=vocab, max_seq_len=seq_len)
    rng = np.random.default_rng(0)
    data = {
        "input_ids": jnp.asarray(
            rng.integers(3, vocab, (batch, seq_len)), jnp.int32),
        "pad_mask": jnp.zeros((batch, seq_len), bool),
        "label": jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32),
    }
    return task, data


def _build_img_clf(batch: int = 512):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tasks import ImageClassifierTask

    task = ImageClassifierTask(
        image_shape=(28, 28, 1), num_classes=10, num_frequency_bands=32,
        num_latents=32, num_latent_channels=128, num_encoder_layers=3,
        num_encoder_self_attention_layers_per_block=3,
        num_decoder_cross_attention_heads=1)
    rng = np.random.default_rng(0)
    data = {
        "image": jnp.asarray(
            rng.normal(0, 1, (batch, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, (batch,)), jnp.int32),
    }
    return task, data


def _build_seg(batch: int = 1, side: int = 512):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tasks import SegmentationTask

    task = SegmentationTask(image_shape=(side, side, 1),
                            query_chunk_size=min(16384, side * side))
    rng = np.random.default_rng(0)
    data = {
        "image": jnp.asarray(
            rng.random((batch, side, side, 1)) *
            (rng.random((batch, side, side, 1)) < 0.01), jnp.float32),
        "label": jnp.asarray(
            rng.integers(0, 3, (batch, side, side)), jnp.int32),
    }
    return task, data


# --------------------------------------------------------------------------
# Serving targets: the serve graph of each task at its largest default
# engine bucket (serving/engine.py defaults: batch ≤ 32, seq ≤ 512 for
# the canonical text recipe) — the shapes steady-state traffic pads
# into, so the budget/dtype/transfer/donation/recompile gates certify
# the executable production actually dispatches. Forward-only, so all
# four lower in seconds.

def _serve_batch_mlm(batch: int = 32, seq_len: int = 512,
                     vocab: int = 10003, channels: int = 64):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tasks import MaskedLanguageModelTask
    from perceiver_tpu.tokenizer import MASK_TOKEN_ID

    task = MaskedLanguageModelTask(
        vocab_size=vocab, max_seq_len=seq_len,
        num_latent_channels=channels)
    rng = np.random.default_rng(0)
    ids = rng.integers(3, vocab, (batch, seq_len))
    ids[:, ::7] = MASK_TOKEN_ID  # representative fill-mask density
    return task, {
        "input_ids": jnp.asarray(ids, jnp.int32),
        "pad_mask": jnp.zeros((batch, seq_len), bool),
    }


def _serve_batch_text_clf(batch: int = 32, seq_len: int = 512,
                          vocab: int = 10003):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tasks import TextClassifierTask

    task = TextClassifierTask(vocab_size=vocab, max_seq_len=seq_len)
    rng = np.random.default_rng(0)
    return task, {
        "input_ids": jnp.asarray(
            rng.integers(3, vocab, (batch, seq_len)), jnp.int32),
        "pad_mask": jnp.zeros((batch, seq_len), bool),
    }


def _serve_batch_img_clf(batch: int = 32):
    import jax.numpy as jnp
    import numpy as np

    task, _ = _build_img_clf(batch=batch)
    rng = np.random.default_rng(0)
    return task, {
        "image": jnp.asarray(rng.normal(0, 1, (batch, 28, 28, 1)),
                             jnp.float32),
    }


def _serve_batch_seg(batch: int = 1, side: int = 512):
    import jax.numpy as jnp
    import numpy as np

    task, _ = _build_seg(batch=batch, side=side)
    rng = np.random.default_rng(0)
    img = (rng.random((batch, side, side))
           * (rng.random((batch, side, side)) < 0.01))
    return task, {"image": jnp.asarray(img, jnp.float32)}


SERVING_TARGETS = (
    # headline: the serve graph is pure forward under Policy.bf16 —
    # every dot FLOP must run on bf16 operands, same bar as the
    # headline train step
    StepTarget(name="serve_mlm_b32_s512", build=_serve_batch_mlm,
               kind="serve", headline=True),
    StepTarget(name="serve_text_clf_b32_s512",
               build=_serve_batch_text_clf, kind="serve"),
    StepTarget(name="serve_img_clf_b32", build=_serve_batch_img_clf,
               kind="serve"),
    StepTarget(name="serve_seg_512x512_b1", build=_serve_batch_seg,
               kind="serve"),
)


# Packed (ragged) serving targets: the mixed-length headline workload
# — the same 32 requests serve_mlm_b32_s512 pads to a (32, 512)
# rectangle, packed into one 8192-token buffer (7680 real tokens,
# lengths cycling 64/128/256/512). The hbm_budget pin on these targets
# IS the merge gate for the padding-free claim: the packed executable
# must stay ≥ 25% below the rectangular equivalent's pinned bytes
# (tests/test_graphcheck.py).

def _packed_serve_lengths(rows: int):
    import numpy as np

    return np.array([(64, 128, 256, 512)[i % 4] for i in range(rows)],
                    np.int32)


def _packed_serve_batch(rows: int, tokens: int, vocab: int,
                        mask_every: int = 0):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.tokenizer import MASK_TOKEN_ID, PAD_TOKEN_ID

    lens = _packed_serve_lengths(rows)
    total = int(lens.sum())
    if total > tokens:
        raise ValueError(f"lengths sum {total} exceeds bucket {tokens}")
    rng = np.random.default_rng(0)
    ids = rng.integers(3, vocab, (tokens,))
    if mask_every:
        ids[::mask_every] = MASK_TOKEN_ID
    ids[total:] = PAD_TOKEN_ID
    offs = np.zeros(rows, np.int32)
    offs[1:] = np.cumsum(lens)[:-1]
    return {
        "packed_ids": jnp.asarray(ids, jnp.int32),
        "row_offsets": jnp.asarray(offs, jnp.int32),
        "lengths": jnp.asarray(lens, jnp.int32),
    }


def _serve_batch_mlm_packed(tokens: int = 8192, rows: int = 32,
                            vocab: int = 10003, channels: int = 64):
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=vocab, max_seq_len=512, num_latent_channels=channels)
    # same representative fill-mask density as _serve_batch_mlm
    return task, _packed_serve_batch(rows, tokens, vocab, mask_every=7)


def _serve_batch_text_clf_packed(tokens: int = 8192, rows: int = 32,
                                 vocab: int = 10003):
    from perceiver_tpu.tasks import TextClassifierTask

    task = TextClassifierTask(vocab_size=vocab, max_seq_len=512)
    return task, _packed_serve_batch(rows, tokens, vocab)


PACKED_SERVING_TARGETS = (
    StepTarget(name="serve_mlm_packed_t8192_r32",
               build=_serve_batch_mlm_packed, kind="packed_serve"),
    StepTarget(name="serve_text_clf_packed_t8192_r32",
               build=_serve_batch_text_clf_packed, kind="packed_serve"),
)


# --------------------------------------------------------------------------
# Decode targets: ONE stepped executable per pool geometry — the
# unified step DecodeEngine runs for chunked prefill AND decode. The
# canonical geometry is 8 slots over a 64-page × 16-token shared KV
# pool with 8 chunk lanes, at the BASELINE MLM recipe shapes. The
# batch is deliberately MIXED-phase (half the rows prefill a full
# chunk, half decode one token) so the gates certify the signature
# both phases share. The hbm_budget pin on this target IS the O(1)
# memory gate for the paged-decode claim: the step's bytes accessed
# are geometry-bound (pools + params), independent of how many tokens
# any stream has generated — a regression that makes cost grow with
# sequence position would move the pin.

def _decode_batch_mlm(vocab: int = 10003, seq: int = 512,
                      channels: int = 64, streams: int = 8,
                      num_pages: int = 64, page_size: int = 16,
                      max_chunk: int = 8, attn_impl: str = "pallas",
                      spec_k: int = 0):
    import jax.numpy as jnp
    import numpy as np

    from perceiver_tpu.serving.decode import DecodeGeometry
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=vocab, max_seq_len=seq, num_latent_channels=channels)
    rng = np.random.default_rng(0)
    if spec_k:
        # all three row phases of a speculative engine in one batch:
        # prefill chunk / k+1-lane verify window / plain decode
        pattern = (max_chunk, spec_k + 1, 1)
        qlens = np.array([pattern[i % 3] for i in range(streams)],
                         np.int32)
    else:
        # alternate prefill (full chunk) and decode (1 token) rows
        qlens = np.array([max_chunk if i % 2 == 0 else 1
                          for i in range(streams)], np.int32)
    return task, {
        "geometry": DecodeGeometry(
            max_streams=streams, num_pages=num_pages,
            page_size=page_size, max_seq_len=seq, max_chunk=max_chunk,
            spec_k=spec_k),
        "tokens": jnp.asarray(
            rng.integers(3, vocab, (streams, max_chunk)), jnp.int32),
        "qlens": jnp.asarray(qlens),
        "attn_impl": attn_impl,
    }


def _decode_batch_mlm_spmd():
    # reference attention: GSPMD partitions gathers, not Pallas calls;
    # vocab/seq follow the SPMD serve rung (_SPMD_MLM) so the model
    # axis divides the vocab projection evenly
    return _decode_batch_mlm(vocab=8192, seq=256, num_pages=48,
                             attn_impl="reference")


def _multitenant_qlens(streams: int, max_chunk: int):
    """The per-slot qlens a mixed-TENANT round actually feeds: three
    tenants (weights 2/1/1) share the step's token budget through the
    same ``weighted_fair_shares`` split the continuous batcher's
    per-tenant planner uses (``serving/batcher.py take(tenant_budgets=
    ...)``) — each tenant prefills full chunks until its fair share is
    spent, then its remaining rows decode one token. Deterministic by
    construction (no RNG), so the target re-lowers byte-identically."""
    import numpy as np

    from perceiver_tpu.serving.tenancy import weighted_fair_shares

    owners = ["a" if i < streams // 2 else
              "b" if i < 3 * streams // 4 else "c"
              for i in range(streams)]
    budget = streams * max_chunk // 2
    remaining = weighted_fair_shares(
        budget, {"a": 2.0, "b": 1.0, "c": 1.0})
    qlens = []
    for tenant in owners:
        q = max(1, min(max_chunk, remaining[tenant]))
        remaining[tenant] = max(0, remaining[tenant] - q)
        qlens.append(q)
    return np.array(qlens, np.int32)


def _decode_batch_mlm_multitenant(vocab: int = 10003, seq: int = 512,
                                  num_pages: int = 64,
                                  attn_impl: str = "pallas"):
    """The canonical MULTI-TENANT decode round: same geometry as
    ``decode_mixed_mlm_r8_p64x16_q8``, but the qlens are the
    fair-share plan of three tenants sharing the step (see
    ``_multitenant_qlens``). Tenancy is host-side state only — quota
    ledgers, fair-share planning, and shed decisions all happen before
    tokens reach the device — so this target MUST lower to the
    byte-identical module of its single-tenant twin
    (tests/test_graphcheck.py pins the fingerprint equality). The
    pinned hbm budget is therefore the same O(1) gate: admitting a
    tenant costs zero compiles and zero step-cost growth."""
    task, batch = _decode_batch_mlm(vocab=vocab, seq=seq,
                                    num_pages=num_pages,
                                    attn_impl=attn_impl)
    import jax.numpy as jnp

    geometry = batch["geometry"]
    batch["qlens"] = jnp.asarray(
        _multitenant_qlens(geometry.max_streams, geometry.max_chunk))
    return task, batch


def _decode_batch_mlm_multitenant_spmd():
    return _decode_batch_mlm_multitenant(vocab=8192, seq=256,
                                         num_pages=48,
                                         attn_impl="reference")


def _decode_batch_mlm_spec():
    # the speculative verify executable: k=4 drafted lanes + feedback
    # fold 5 latent-rebuild windows per stream into the kernel row
    # axis — the hbm pin certifies the widened step stays
    # geometry-bound (same pools, W× latents only)
    return _decode_batch_mlm(spec_k=4)


def _decode_batch_mlm_spec_spmd():
    return _decode_batch_mlm(vocab=8192, seq=256, num_pages=48,
                             attn_impl="reference", spec_k=4)


DECODE_TARGETS = (
    StepTarget(name="decode_mixed_mlm_r8_p64x16_q8",
               build=_decode_batch_mlm, kind="decode"),
    StepTarget(name="decode_spec_mlm_r8_p64x16_q8_k4",
               build=_decode_batch_mlm_spec, kind="decode"),
    StepTarget(name="decode_multitenant_mlm_r8_p64x16_q8",
               build=_decode_batch_mlm_multitenant, kind="decode",
               signature_twin="decode_mixed_mlm_r8_p64x16_q8"),
)


# --------------------------------------------------------------------------
# Sharded (SPMD) targets: the first mesh rung — dp2×tp2 over 4 CPU
# devices (virtual via --xla_force_host_platform_device_count; the
# same specs place on a v4-8 slice unchanged). Shapes shrink from the
# headline rung so lower+compile stays seconds, and vocab drops to
# 8192 so the model axis divides the vocab projection evenly (the odd
# 10003 vocab would fall back to replication — exactly what the
# replication pass exists to flag).

DP2_TP2 = MeshSpec(axes=(("data", 2), ("model", 2)))

_SPMD_MLM = dict(batch=32, channels=64, seq_len=256, vocab=8192)


def _build_mlm_spmd():
    return _build_mlm(loss_impl="packed", **_SPMD_MLM)


def _serve_batch_mlm_spmd():
    return _serve_batch_mlm(**_SPMD_MLM)


# the input embedding table (vocab×C fp32) is replicated by design:
# the sharding rules keep embeddings whole on every device (read-only
# per step, gathered by token id), and only its ZeRO moments shard
_SPMD_MLM_EMBED_ALLOW = (
    ReplicationAllow(
        type="8192x64xf32", max_count=2,
        reason="input-embedding table (and its aliased output copy) — "
               "replicated by design per parallel/sharding.py; its "
               "optimizer moments ARE data-sharded (ZeRO)"),
)

SHARDED_TARGETS = (
    StepTarget(name="mlm_spmd_b32_s256_dp2_tp2", build=_build_mlm_spmd,
               mesh=DP2_TP2,
               replication_allow=_SPMD_MLM_EMBED_ALLOW),
    StepTarget(name="serve_mlm_spmd_b32_s256_dp2_tp2",
               build=_serve_batch_mlm_spmd, kind="serve", mesh=DP2_TP2,
               replication_allow=_SPMD_MLM_EMBED_ALLOW),
    StepTarget(name="decode_mixed_mlm_spmd_r8_p48x16_q8_dp2_tp2",
               build=_decode_batch_mlm_spmd, kind="decode",
               mesh=DP2_TP2,
               replication_allow=_SPMD_MLM_EMBED_ALLOW,
               # the reference paged-attention path upcasts q/k/v to
               # fp32 (ops/paged_attention.py) to match the Pallas
               # kernel's fp32 online-softmax accumulator bit-for-bit
               # in tests — two QK^T and two PV dots per step (layer_1
               # + the scanned layer_n), ~9% of step dot-FLOPs each
               dtype_allow=(
                   DtypeAllow(
                       dtype="f32", max_count=4,
                       reason="reference paged-attention fp32 "
                              "accumulation — parity twin of the "
                              "Pallas kernel's fp32 online-softmax "
                              "accumulator; production decode lowers "
                              "the bf16 Pallas kernel instead"),)),
    StepTarget(name="decode_multitenant_mlm_spmd_r8_p48x16_q8_dp2_tp2",
               build=_decode_batch_mlm_multitenant_spmd, kind="decode",
               signature_twin="decode_mixed_mlm_spmd_r8_p48x16_q8_dp2_tp2",
               mesh=DP2_TP2,
               replication_allow=_SPMD_MLM_EMBED_ALLOW,
               # same reference-path fp32 parity twin as the other
               # spmd decode targets — the multi-tenant qlens plan is
               # host-side data, so the lowered dots are unchanged
               dtype_allow=(
                   DtypeAllow(
                       dtype="f32", max_count=4,
                       reason="reference paged-attention fp32 "
                              "accumulation — parity twin of the "
                              "Pallas kernel's fp32 online-softmax "
                              "accumulator; production decode lowers "
                              "the bf16 Pallas kernel instead"),)),
    StepTarget(name="decode_spec_mlm_spmd_r8_p48x16_q8_k4_dp2_tp2",
               build=_decode_batch_mlm_spec_spmd, kind="decode",
               mesh=DP2_TP2,
               replication_allow=_SPMD_MLM_EMBED_ALLOW,
               # window tiling folds the k+1 verify lanes into the row
               # axis of the SAME attention dots, so the fp32 count is
               # unchanged from the non-speculative twin
               dtype_allow=(
                   DtypeAllow(
                       dtype="f32", max_count=4,
                       reason="reference paged-attention fp32 "
                              "accumulation — parity twin of the "
                              "Pallas kernel's fp32 online-softmax "
                              "accumulator; production decode lowers "
                              "the bf16 Pallas kernel instead"),)),
)


# The headline MLM target (B=512/C=64/packed) plus
# one target per remaining task at its canonical shapes, plus the
# serving targets. "fast" targets keep tracing under a few seconds for
# the tier-1 subset; --all adds the expensive ones (the 262k-query
# segmentation train step — its forward-only serve twin stays fast).
CANONICAL_TARGETS = (
    StepTarget(name="mlm_b512_c64_packed", build=_build_mlm,
               headline=True),
    StepTarget(name="text_clf_b64", build=_build_text_clf),
    StepTarget(name="img_clf_b512", build=_build_img_clf),
    StepTarget(name="seg_512x512_b1", build=_build_seg),
) + (SERVING_TARGETS + PACKED_SERVING_TARGETS + DECODE_TARGETS
     + SHARDED_TARGETS)

# --fast also drops the mesh targets: they are the only targets that
# must be XLA-COMPILED (collectives appear post-partitioning), and the
# fast tier exists to keep the tier-1 wall clock bounded. --all and
# --graph still run them, which is where the shardcheck gates live.
FAST_TARGETS = tuple(t for t in CANONICAL_TARGETS
                     if t.name != "seg_512x512_b1" and t.mesh is None)
