#!/usr/bin/env python
"""Smoke of the main path on the chip: train, checkpoint, serve, decode.

One process drives the program's normal entry points at the widths of
``scripts/configs/perceiver_lm_v5p16.yaml`` (1024×512 latents, 12
self-attention layers per block, 8 heads, seq 2048, vocab 32000, bf16)
with the batch cut to one chip's share:

1. train       ``Trainer`` + ``MaskedLanguageModelTask`` take a few
               optimizer steps on seeded synthetic tokens; the
               validation loss must be finite and lower afterwards;
2. checkpoint  the trainer's checkpoint is restored into a fresh state
               and compared bitwise with the trained parameters;
3. serve       ``MLMServer`` over ``ServingEngine`` answers masked-token
               requests from the restored parameters, rectangular and
               packed (the ragged kernels), and the two must agree;
4. decode      ``GenerationServer`` over ``DecodeEngine`` (paged Pallas
               kernel) runs streams of mixed prompt length with chunked
               prefill and a shared prefix; one short stream is held to
               the full-recompute oracle.

``--chips 4`` runs instead, and only: the same train step on a dp2×tp2
mesh over four local chips against the same seeded batches on one of
them. ``--rehearse`` runs either path at toy width on whatever backend
JAX has (the CPU rehearsal); without it a platform other than ``tpu``
is an error. Weights and data come from ``--seed``; nothing is
downloaded and git is never called.

The last line of stdout is one JSON object; ``ok`` is true only when
the platform is ``tpu`` and every phase passed. Seconds printed on the
earlier lines are smoke timings, not measurements. Everything the run
writes goes to a temporary directory that is removed on exit, except
JAX's compile cache (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
_CONFIG = os.path.join(_REPO, "scripts", "configs",
                       "perceiver_lm_v5p16.yaml")

# what --rehearse swaps in for the published widths (head dim 16)
_TOY_MODEL = dict(num_latents=16, num_latent_channels=32,
                  num_encoder_self_attention_layers_per_block=2,
                  num_encoder_cross_attention_heads=2,
                  num_encoder_self_attention_heads=2,
                  num_decoder_cross_attention_heads=2)
_TOY_VOCAB, _TOY_SEQ = 512, 64


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# --- configuration -----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""

    vocab: int
    seq: int
    model: dict
    batch: int            # rows per optimizer step
    steps: int
    serve_lengths: tuple  # tokens per fill-mask request
    seq_buckets: tuple
    packed_bucket: tuple  # (tokens, rows)
    page_size: int
    max_chunk: int
    prompt_lengths: tuple  # decode streams; the first is the oracle's
    prefix_len: int        # shared by the last two streams
    max_new: int


def sizes(rehearse: bool) -> Sizes:
    import yaml

    with open(_CONFIG) as f:
        cfg = yaml.safe_load(f)
    if rehearse:
        return Sizes(vocab=_TOY_VOCAB, seq=_TOY_SEQ,
                     model={**cfg["model"], **_TOY_MODEL},
                     batch=4, steps=6, serve_lengths=(9, 20, 33, 64),
                     seq_buckets=(32, 64), packed_bucket=(128, 4),
                     page_size=4, max_chunk=8,
                     prompt_lengths=(5, 11, 19, 23), prefix_len=16,
                     max_new=4)
    return Sizes(vocab=cfg["data"]["vocab_size"],
                 seq=cfg["data"]["max_seq_len"], model=dict(cfg["model"]),
                 batch=4, steps=8,
                 serve_lengths=(40, 300, 700, 1500, 2048, 2048),
                 seq_buckets=(512, 2048), packed_bucket=(8192, 8),
                 page_size=16, max_chunk=128,
                 prompt_lengths=(24, 200, 517, 1100), prefix_len=256,
                 max_new=8)


def print_cuts(sz: Sizes, rehearse: bool, chips: int) -> None:
    if rehearse:
        say(f"cut: REHEARSAL at toy width {sz.model['num_latents']}x"
            f"{sz.model['num_latent_channels']} latents, seq {sz.seq}, "
            f"vocab {sz.vocab} — not the published widths")
    say(f"cut: global batch 64 of the v5p-16 config -> {sz.batch} rows "
        f"per step (its share of one chip)")
    say(f"cut: trainer mesh dp4 x sp2 x tp2 on 16 chips -> "
        f"{'dp2 x tp2 on four' if chips == 4 else 'one chip, no mesh'}")
    say(f"cut: max_steps 100000 -> {sz.steps}; OneCycleLR -> constant "
        f"lr 1e-3; synthetic seeded tokens in place of IMDB")
    say("depth is not cut: 3 encoder layers x "
        f"{sz.model['num_encoder_self_attention_layers_per_block']} "
        "self-attention layers per block, as shipped")


# --- seeded data and its tokenizer -------------------------------------------


def make_tokenizer(vocab_size: int, build_dir: str):
    """A WordPiece tokenizer whose vocabulary is the specials plus one
    word per remaining id, so seeded ids round-trip through text. The
    native engine is built into ``build_dir``; a failed build raises."""
    from perceiver_tpu.tokenizer import SPECIAL_TOKENS, create_tokenizer
    from perceiver_tpu.tokenizer import native

    native.load(build_dir)
    tok = create_tokenizer()
    vocab = {t: i for i, t in enumerate(SPECIAL_TOKENS)}
    for i in range(len(SPECIAL_TOKENS), vocab_size):
        vocab[f"w{i}"] = i
    tok.vocab = vocab
    tok.ids_to_tokens = {i: t for t, i in vocab.items()}
    if tok._native_vocab() is None:
        raise OSError("native tokenizer unavailable after a good build")
    return tok


def zipf_ids(rng, vocab_size: int, n_special: int, shape) -> np.ndarray:
    """Skewed token ids: a unigram distribution a few optimizer steps
    can start to learn (uniform ids would leave nothing to lower)."""
    ranks = np.arange(1, vocab_size - n_special + 1, dtype=np.float64)
    p = 1.0 / ranks
    p /= p.sum()
    return (rng.choice(len(ranks), size=shape, p=p)
            + n_special).astype(np.int32)


def to_text(ids, mask_every: int = 0) -> str:
    from perceiver_tpu.tokenizer import MASK_TOKEN

    words = [f"w{int(i)}" for i in ids]
    if mask_every:
        for j in range(2, len(words), mask_every):
            words[j] = MASK_TOKEN
    return " ".join(words)


class SyntheticTokens:
    """Datamodule over seeded full-length token rows (the IMDB
    module's surface, without a corpus)."""

    def __init__(self, vocab_size: int, seq_len: int, batch_size: int,
                 train_rows: int, seed: int):
        self.vocab_size, self.max_seq_len = vocab_size, seq_len
        self.batch_size, self.train_rows, self.seed = \
            batch_size, train_rows, seed
        self._train = self._val = None

    def prepare_data(self):
        pass

    def setup(self, stage=None):
        if self._train is not None:
            return
        from perceiver_tpu.data.core import ArrayDataset
        from perceiver_tpu.tokenizer import SPECIAL_TOKENS

        rng = np.random.default_rng(self.seed)

        def split(rows):
            ids = zipf_ids(rng, self.vocab_size, len(SPECIAL_TOKENS),
                           (rows, self.max_seq_len))
            # ragged tails: rows end between half and full length
            lengths = rng.integers(self.max_seq_len // 2,
                                   self.max_seq_len + 1, rows)
            pad = np.arange(self.max_seq_len)[None, :] >= lengths[:, None]
            ids[pad] = 0
            return ArrayDataset(label=np.zeros(rows, np.int32),
                                input_ids=ids, pad_mask=pad)

        self._train = split(self.train_rows)
        self._val = split(self.batch_size)

    def train_dataloader(self):
        from perceiver_tpu.data.core import BatchIterator

        self.setup()
        return BatchIterator(self._train, self.batch_size, shuffle=True,
                             seed=self.seed, drop_last=True)

    def val_dataloader(self):
        from perceiver_tpu.data.core import BatchIterator

        self.setup()
        return BatchIterator(self._val, self.batch_size)

    test_dataloader = val_dataloader


# --- bookkeeping -------------------------------------------------------------


class Run:
    """Phase results and the scratch directory."""

    def __init__(self, tmp: str):
        self.tmp = tmp
        self.phases = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax

        from perceiver_tpu.cache import compile_events

        say(f"phase {name}: start")
        t0 = time.perf_counter()
        with compile_events() as compiles:
            try:
                yield
                ok, err = True, None
            except Exception as e:  # noqa: BLE001 — reported, then fatal
                import traceback

                traceback.print_exc()
                ok, err = False, f"{type(e).__name__}: {e}"[:300]
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        self.phases[name] = ok
        say(f"phase {name}: {'PASS' if ok else 'FAIL'} in "
            f"{time.perf_counter() - t0:.1f} s (smoke timing), "
            f"{len(compiles)} programs compiled in {sum(compiles):.1f} s, "
            f"peak device bytes "
            f"{peak if peak is not None else 'not reported'}")
        if not ok:
            raise PhaseFailed(f"{name}: {err}")


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    say(f"  ok: {what}")


def trainer_config(sz: Sizes, root: str, experiment: str, seed: int,
                   accelerator: str):
    from perceiver_tpu.training import TrainerConfig

    return TrainerConfig(
        max_steps=sz.steps, precision="bf16", accelerator=accelerator,
        log_every_n_steps=1, num_sanity_val_steps=0,
        enable_checkpointing=True, save_top_k=1,
        default_root_dir=os.path.join(root, "logs"),
        experiment=experiment,
        telemetry_dir=os.path.join(root, "telemetry", experiment),
        # the AOT first-dispatch path on every backend: what the chip
        # takes anyway (no lowering-only cost analysis there), and the
        # one that holds a step to its compiled input shardings
        exec_cache_dir=os.path.join(root, "exec_cache"),
        seed=seed)


_ADAMW = {"class_path": "AdamW",
          "init_args": {"lr": 1e-3, "weight_decay": 0.01}}


def step_losses(telemetry_dir: str) -> list:
    """Per-step train losses the trainer's telemetry recorded."""
    out = []
    with open(os.path.join(telemetry_dir, "telemetry.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "loss" in rec and "step" in rec:
                out.append((int(rec["step"]), float(rec["loss"])))
    return [loss for _, loss in sorted(out)]


def assert_mosaic(compiled, what: str) -> None:
    """On the chip the compiled program must hold the Mosaic kernel,
    not the interpreter's expansion of it."""
    import jax

    if jax.devices()[0].platform != "tpu":
        say(f"  {what}: tpu_custom_call not checked off-TPU "
            "(interpret mode)")
        return
    check("tpu_custom_call" in compiled.as_text(),
          f"{what} contains tpu_custom_call")


# --- phases ------------------------------------------------------------------


def phase_train(run: Run, sz: Sizes, task, seed: int, accelerator: str):
    import jax

    from perceiver_tpu.training import Trainer

    dm = SyntheticTokens(sz.vocab, sz.seq, sz.batch,
                         train_rows=sz.steps * sz.batch, seed=seed)
    cfg = trainer_config(sz, run.tmp, "chip_smoke", seed, accelerator)
    trainer = Trainer(task, dm, cfg, optimizer_init=_ADAMW)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(trainer.model.init, jax.random.key(0))))
    say(f"  model: {n_params / 1e6:.1f} M parameters, batch "
        f"{sz.batch} x {sz.seq} tokens, loss_impl={task.loss_impl}, "
        f"remat={task.remat}")
    before = trainer.validate(trainer._build_state())
    state = trainer.fit()
    after = trainer.validate(state)
    losses = step_losses(cfg.telemetry_dir)
    say(f"  val_loss {before['val_loss']:.4f} -> {after['val_loss']:.4f} "
        f"over {trainer.global_step} steps; train losses "
        + " ".join(f"{x:.3f}" for x in losses))
    check(trainer.global_step == sz.steps,
          f"{sz.steps} optimizer steps taken")
    check(bool(np.isfinite(losses).all())
          and np.isfinite(after["val_loss"]), "losses finite")
    check(after["val_loss"] < before["val_loss"],
          "validation loss lower after the steps")
    return trainer, dm, state


def phase_checkpoint(run: Run, sz: Sizes, task, trainer, dm, state,
                     seed: int, accelerator: str):
    import jax

    from perceiver_tpu.training import Trainer
    from perceiver_tpu.training.checkpoint import CheckpointHook

    ckpt_dir = os.path.join(trainer.log_dir, "checkpoints")
    fresh_trainer = Trainer(
        task, dm, trainer_config(sz, run.tmp, "chip_smoke_restore",
                                 seed + 1, accelerator),
        optimizer_init=_ADAMW)
    fresh = fresh_trainer._build_state()
    restored = CheckpointHook(ckpt_dir).restore_latest(fresh)
    check(restored is not None, f"a checkpoint was committed under "
          f"{os.path.relpath(ckpt_dir, run.tmp)}")
    trained = jax.tree.leaves(state.params)
    got = jax.tree.leaves(restored.params)
    check(len(trained) == len(got) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(trained, got)),
        f"all {len(got)} restored parameter arrays bitwise equal")
    check(not all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree.leaves(fresh.params), got)),
          "the fresh state differed before the restore")
    check(int(restored.step) == sz.steps, "restored step counter")
    size = sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(ckpt_dir) for f in fs)
    say(f"  checkpoint on disk: {size / 1e6:.1f} MB (params + AdamW "
        "moments), in the temporary directory")
    return restored.params


def _top1_agree(a, b, what: str, tol: float = 0.05) -> None:
    """Two servers' fills agree: same top-1 token at every masked
    position, unless both rank the two candidates within ``tol`` of
    each other (a bf16 near-tie), and scores within ``tol``."""
    check(a.masked_positions == b.masked_positions and
          len(a.masked_positions) > 0, f"{what}: same masked positions")
    flips = 0
    for ta, sa, tb, sb in zip(a.topk_tokens, a.topk_scores,
                              b.topk_tokens, b.topk_scores):
        if not (np.isfinite(sa).all() and np.isfinite(sb).all()):
            raise AssertionError(f"{what}: non-finite scores")
        if ta[0] != tb[0]:
            flips += 1
            if abs(sa[0] - sa[1]) > tol or tb[0] not in ta:
                raise AssertionError(
                    f"{what}: top-1 {ta[0]}!={tb[0]} outside a near-tie "
                    f"({sa} vs {sb})")
        elif abs(sa[0] - sb[0]) > tol:
            raise AssertionError(
                f"{what}: top-1 score {sa[0]} vs {sb[0]}")
    say(f"  ok: {what}: {len(a.topk_tokens)} masked positions agree "
        f"({flips} near-tie flips)")


def phase_serve(run: Run, sz: Sizes, task, params, tok, seed: int):
    from perceiver_tpu.cache import compile_events
    from perceiver_tpu.ops.policy import Policy
    from perceiver_tpu.serving import MLMServer, ServingEngine
    from perceiver_tpu.tokenizer import SPECIAL_TOKENS

    rng = np.random.default_rng(seed + 2)
    texts = [to_text(zipf_ids(rng, sz.vocab, len(SPECIAL_TOKENS), n),
                     mask_every=7) for n in sz.serve_lengths]
    ids, lengths = tok.encode_batch_padded(texts, sz.seq)
    check(list(lengths) == list(sz.serve_lengths),
          "the native tokenizer returns one id per seeded word")

    engine = ServingEngine(
        task, params, batch_buckets=(1, 4), seq_buckets=sz.seq_buckets,
        packed_buckets=(sz.packed_bucket,), policy=Policy.bf16(),
        exec_cache=os.path.join(run.tmp, "exec_cache"))
    say(f"  warmed {len(engine.compiled_buckets)} buckets: rect "
        f"{engine.batch_buckets} x {engine.seq_buckets}, packed "
        f"t{sz.packed_bucket[0]}_r{sz.packed_bucket[1]}")
    assert_mosaic(engine._exe[("packed", *sz.packed_bucket)],
                  "packed serve program")
    rect = MLMServer(engine, tok, max_delay_ms=20.0)
    packed = MLMServer(engine, tok, max_delay_ms=20.0, packed=True)
    try:
        with compile_events() as compiles:
            out = {}
            for name, server in (("rect", rect), ("packed", packed)):
                futures = [server.submit(t) for t in texts]
                out[name] = [f.result() for f in futures]
        check(len(compiles) == 0,
              f"zero compiles after warm-up over {2 * len(texts)} "
              "requests")
    finally:
        rect.close()
        packed.close()
    for i, (a, b) in enumerate(zip(out["rect"], out["packed"])):
        check(len(a.predictions) == 3 and len(a.masked_positions)
              == len(range(2, sz.serve_lengths[i], 7)),
              f"request {i} ({sz.serve_lengths[i]} tokens): "
              f"{len(a.masked_positions)} masks filled, top-3")
        _top1_agree(a, b, f"request {i} rect vs packed")


def _oracle(task, params, policy, width: int):
    """Full-recompute reference for decode: re-encode the whole prefix
    (padded to ``width``), decode one query at the next position."""
    import jax
    import jax.numpy as jnp

    from perceiver_tpu.models.perceiver import cross_attention_layer_apply
    from perceiver_tpu.ops.linear import linear_apply

    model = task.build()

    @jax.jit
    def logits_at(params, ids, n):
        pad = jnp.arange(width)[None, :] >= n
        latents, _ = model.encoder.apply(params["encoder"], ids,
                                         pad_mask=pad, policy=policy)
        pd = params["decoder"]
        q = jnp.take(policy.cast_param(pd["query"]), n, axis=0)[None, None]
        hidden = cross_attention_layer_apply(
            pd["cross"], q, latents,
            num_heads=model.decoder.num_cross_attention_heads,
            policy=policy)
        return linear_apply(pd["output_adapter"]["linear"], hidden,
                            policy=policy)[0, 0].astype(jnp.float32)

    def run(prefix):
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(prefix)] = prefix
        return np.asarray(logits_at(params, ids, len(prefix)))

    return run


def phase_decode(run: Run, sz: Sizes, task, params, tok, seed: int):
    from perceiver_tpu.cache import compile_events
    from perceiver_tpu.ops.policy import Policy
    from perceiver_tpu.serving.api import GenerationServer
    from perceiver_tpu.serving.decode import (
        DecodeEngine,
        DecodeGeometry,
        DecodeResult,
    )
    from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig
    from perceiver_tpu.tokenizer import SPECIAL_TOKENS

    policy = Policy.bf16()
    streams = len(sz.prompt_lengths)
    geometry = DecodeGeometry(
        max_streams=streams, page_size=sz.page_size, max_seq_len=sz.seq,
        num_pages=streams * (sz.seq // sz.page_size) + 1,
        max_chunk=sz.max_chunk)
    rng = np.random.default_rng(seed + 3)
    n_special = len(SPECIAL_TOKENS)
    prompts = [zipf_ids(rng, sz.vocab, n_special, n)
               for n in sz.prompt_lengths]
    # the last two streams share a page-aligned prefix
    prompts[-1][:sz.prefix_len] = prompts[-2][:sz.prefix_len]

    width = sz.prompt_lengths[0] + sz.max_new
    oracle = _oracle(task, params, policy, width)
    oracle(prompts[0])  # compile outside the counted window

    engine = DecodeEngine(
        task, params, geometry=geometry, policy=policy,
        attn_impl="pallas", prefix_cache=PrefixCacheConfig(),
        exec_cache=os.path.join(run.tmp, "exec_cache"), seed=seed)
    say(f"  decode executable {geometry.descriptor}: {streams} streams, "
        f"pool {geometry.num_pages} pages x {sz.page_size}, prefill "
        f"chunks of {sz.max_chunk}")
    assert_mosaic(engine._exe, "decode step program")
    server = GenerationServer(engine, tok)
    try:
        # warm-up: the stream that publishes the shared prefix
        first = server.submit(to_text(prompts[-2]),
                              max_new_tokens=sz.max_new).result(600.0)
        with compile_events() as compiles:
            handles = [server.submit(to_text(p), max_new_tokens=sz.max_new)
                       for p in prompts[:-2] + prompts[-1:]]
            results = [h.result(600.0) for h in handles]
        results.insert(len(prompts) - 2, first)
        stats = server.prefix_cache_stats()
        steps = int(engine._m_steps.value)
        chunks = int(engine._m_prefill_chunks.value)
    finally:
        server.close()
    for n, r in zip(sz.prompt_lengths, results):
        check(isinstance(r, DecodeResult) and r.finished == "complete"
              and len(r.tokens) == sz.max_new and r.prompt_len == n,
              f"stream of {n} prompt tokens generated {sz.max_new}")
    check(len(compiles) == 0, "zero compiles after warm-up over "
          f"{len(handles)} streams joining mid-flight")
    check(chunks > streams,
          f"chunked prefill: {chunks} chunks in {steps} steps")
    check(stats["hits"] >= 1 and results[-1].cached_tokens
          >= sz.prefix_len - sz.page_size,
          f"shared prefix: {results[-1].cached_tokens} prompt tokens "
          f"served from cached pages ({stats})")

    # teacher-forced oracle on the short stream: at every position the
    # engine's token must be the oracle's argmax, or tie with it in bf16
    toks, exact = list(prompts[0]), 0
    for t in results[0].tokens:
        logits = oracle(toks)
        best = int(logits.argmax())
        if t == best:
            exact += 1
        elif logits[best] - logits[t] > 2.0 ** -6 * max(
                1.0, abs(float(logits[best]))):
            raise AssertionError(
                f"decode diverged from the oracle at position "
                f"{len(toks)}: engine {t} (logit {logits[t]}) vs "
                f"argmax {best} (logit {logits[best]})")
        toks.append(t)
    say(f"  ok: {len(results[0].tokens)} decode tokens equal the "
        f"full-recompute oracle ({exact} exact, "
        f"{len(results[0].tokens) - exact} bf16 ties)")


def run_one_chip(run: Run, sz: Sizes, seed: int, accelerator: str):
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(vocab_size=sz.vocab,
                                   max_seq_len=sz.seq, **sz.model)
    tok = make_tokenizer(sz.vocab, run.tmp)
    say("tokenizer: native C++ WordPiece, built with g++ into the "
        f"temporary directory, {tok.get_vocab_size()} entries")
    with run.phase("train"):
        trainer, dm, state = phase_train(run, sz, task, seed, accelerator)
    with run.phase("checkpoint"):
        params = phase_checkpoint(run, sz, task, trainer, dm, state,
                                  seed, accelerator)
    del trainer, state  # the optimizer moments leave the device
    with run.phase("serve"):
        phase_serve(run, sz, task, params, tok, seed)
    with run.phase("decode"):
        phase_decode(run, sz, task, params, tok, seed)


# --- four chips --------------------------------------------------------------


def run_four_chips(run: Run, sz: Sizes, seed: int, accelerator: str):
    import jax

    from perceiver_tpu.parallel import make_mesh
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    from perceiver_tpu.training import Trainer

    task = MaskedLanguageModelTask(vocab_size=sz.vocab,
                                   max_seq_len=sz.seq, **sz.model)

    def fit(experiment, mesh):
        dm = SyntheticTokens(sz.vocab, sz.seq, sz.batch,
                             train_rows=sz.steps * sz.batch, seed=seed)
        cfg = dataclasses.replace(
            trainer_config(sz, run.tmp, experiment, seed, accelerator),
            enable_checkpointing=False)
        trainer = Trainer(task, dm, cfg, optimizer_init=_ADAMW,
                          mesh=mesh)
        state = trainer.fit()
        return state, step_losses(cfg.telemetry_dir)

    with run.phase("train_dp2_tp2"):
        mesh = make_mesh(4, model_parallel=2)
        say(f"  mesh {dict(mesh.shape)} over "
            f"{[d.id for d in mesh.devices.flat]}")
        state, sharded = fit("chip_smoke_dp2_tp2", mesh)
        say("  sharded train losses " + " ".join(f"{x:.4f}"
                                                for x in sharded))
        check(len(sharded) == sz.steps and np.isfinite(sharded).all(),
              f"{sz.steps} finite sharded steps")
        leaves = jax.tree.leaves(state.params)
        split = [x for x in leaves
                 if not x.sharding.is_fully_replicated]
        check(len(split) > 0, f"{len(split)} of {len(leaves)} parameter "
              "arrays are split over the model axis")
        biggest = max(split, key=lambda x: x.size)
        shards = biggest.addressable_shards
        check(len({s.device for s in shards}) == 4 and
              len({s.index for s in shards}) == 2,
              f"largest split array {biggest.shape}: shards "
              f"{[s.data.shape for s in shards]} on devices "
              f"{sorted(s.device.id for s in shards)}")
        per_device = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in mesh.devices.flat]
        if None in per_device:
            say("  per-device bytes in use: not reported off-TPU")
        else:
            check(min(per_device) > 0.5 * max(per_device),
                  f"state spread over the devices, bytes in use "
                  f"{per_device}")
        del state
    with run.phase("train_one_chip"):
        _, single = fit("chip_smoke_one_chip", None)
        say("  one-chip train losses " + " ".join(f"{x:.4f}"
                                                 for x in single))
        check(len(single) == sz.steps and np.isfinite(single).all(),
              f"{sz.steps} finite one-chip steps")
        check(abs(sharded[0] - single[0]) <= 0.02 * abs(single[0]),
              f"first-step loss agrees: {sharded[0]:.4f} vs "
              f"{single[0]:.4f} (bf16 tolerance 2%)")
        check(np.allclose(sharded, single, rtol=0.05),
              "every step's loss agrees within 5%")


# --- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the dp2 x tp2 train step against one "
                         "chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy width on whatever backend JAX has (the "
                         "CPU rehearsal); ok is never true off-TPU")
    args = ap.parse_args(argv)

    import jax

    from perceiver_tpu.cache import compile_events, enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found platform {platform!r}, not a TPU; "
              "pass --rehearse for the toy-width rehearsal",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    say(f"device: {device}; jax {jax.__version__}")
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    say(f"compile cache: {cache_dir} "
        + ("(from JAX_COMPILATION_CACHE_DIR)" if from_env
           else "(default, in the checkout)"))

    counts = {"hits": 0, "misses": 0}

    def on_event(name, **kwargs):
        if name == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    sz = sizes(args.rehearse)
    print_cuts(sz, args.rehearse, args.chips)
    tmp = tempfile.mkdtemp(prefix="chip_smoke-")
    run = Run(tmp)
    t0 = time.perf_counter()
    failed = None
    try:
        with compile_events() as outside:
            try:
                if args.chips == 4:
                    run_four_chips(run, sz, args.seed, platform)
                else:
                    run_one_chip(run, sz, args.seed, platform)
            except PhaseFailed as e:
                failed = str(e)
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        shutil.rmtree(tmp, ignore_errors=True)
    cache_bytes = sum(os.path.getsize(os.path.join(d, f))
                      for d, _, fs in os.walk(cache_dir) for f in fs)
    say(f"compiles: {len(outside)} programs in {sum(outside):.1f} s "
        f"(smoke timing); persistent cache hits {counts['hits']}, "
        f"misses {counts['misses']}; cache now {cache_bytes / 1e6:.1f} MB")
    say(f"total {time.perf_counter() - t0:.1f} s (smoke timing); phases "
        + ", ".join(f"{k}={'pass' if v else 'FAIL'}"
                    for k, v in run.phases.items()))
    if failed:
        say(f"FAILED: {failed}")
    passed = failed is None and bool(run.phases) and all(
        run.phases.values())
    ok = passed and platform == "tpu" and not args.rehearse
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
