"""CPU end-to-end tests of the serving subsystem (ISSUE 3).

The acceptance properties, each pinned here:

- after engine warmup, a mixed-shape load (3 seq lengths × 2 batch
  sizes) completes with ZERO new XLA compiles (jax.monitoring compile
  events counted around the dispatches);
- engine outputs are bitwise-identical to a fresh jit of the same
  serve graph AND consistent with direct ``model.apply``;
- requests land in the smallest fitting bucket and the dispatch /
  occupancy / padding metrics record exactly the work performed;
- the micro-batcher coalesces concurrent requests, sheds on a full
  queue and on expired deadlines with typed ``Overloaded`` results;
- ``predict_masked_samples`` (the rewritten ``utils/predict.py``)
  performs zero new compiles on a second call at the same shapes —
  the regression the old re-jitting helper failed.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_tpu.cache import compile_events
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.serving import (
    MicroBatcher,
    MLMServer,
    Overloaded,
    RequestTooLarge,
    ServingEngine,
    TokenBudgetBatcher,
    materialize,
    materialize_packed,
)
from perceiver_tpu.serving.metrics import MetricsRegistry
from perceiver_tpu.tasks import MaskedLanguageModelTask
from perceiver_tpu.tokenizer import MASK_TOKEN_ID

VOCAB = 110


def tiny_mlm_task(**overrides):
    kwargs = dict(
        vocab_size=VOCAB, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    kwargs.update(overrides)
    return MaskedLanguageModelTask(**kwargs)


def request_arrays(batch, length, seed=0, mask_every=4):
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, VOCAB, (batch, length)).astype(np.int32)
    ids[:, ::mask_every] = MASK_TOKEN_ID
    pad_mask = np.zeros((batch, length), bool)
    return {"input_ids": ids, "pad_mask": pad_mask}


@pytest.fixture(scope="module")
def engine():
    return ServingEngine(tiny_mlm_task(), batch_buckets=(1, 4),
                         seq_buckets=(16, 32))


class TestEngine:
    def test_warmup_compiles_every_bucket(self, engine):
        assert engine.compiled_buckets == ((1, 16), (1, 32), (4, 16),
                                           (4, 32))
        assert engine.compile_count == 4
        assert engine.metrics.get(
            "serving_compile_total").value_of(phase="warmup") == 4

    def test_mixed_shape_load_zero_new_compiles(self, engine):
        """≥3 seq lengths × ≥2 batch sizes post warmup: zero XLA
        compiles (the acceptance criterion)."""
        shapes = [(1, 7), (3, 7), (1, 16), (2, 23), (4, 32), (3, 12)]
        with compile_events() as events:
            for i, (b, length) in enumerate(shapes):
                res = engine.dispatch(request_arrays(b, length, seed=i))
                assert res.batch == b and res.length == length
            # force materialization too — execution must not compile
            materialize(res, engine.graph)
        assert events == [], f"post-warmup dispatch compiled: {events}"
        assert engine.compile_count == 4

    def test_smallest_fitting_bucket_and_counters(self):
        metrics = MetricsRegistry()
        eng = ServingEngine(tiny_mlm_task(), batch_buckets=(1, 4),
                            seq_buckets=(16, 32), metrics=metrics)
        for b, length in [(1, 9), (2, 9), (4, 16), (1, 17), (3, 32)]:
            assert eng.dispatch(request_arrays(b, length)).bucket == (
                (1 if b == 1 else 4), (16 if length <= 16 else 32))
        dispatch = metrics.get("serving_bucket_dispatch_total")
        assert dispatch.value_of(bucket="b1_s16") == 1
        assert dispatch.value_of(bucket="b4_s16") == 2
        assert dispatch.value_of(bucket="b1_s32") == 1
        assert dispatch.value_of(bucket="b4_s32") == 1
        assert dispatch.value == 5
        waste = metrics.get("serving_padding_waste_fraction")
        assert waste.count == 5
        # (1,9)→bucket(1,16): waste 1-9/16; (2,9)→(4,16): 1-18/64; ...
        expect = [1 - 9 / 16, 1 - 18 / 64, 0.0, 1 - 17 / 32,
                  1 - 96 / 128]
        assert waste.sum == pytest.approx(sum(expect))
        occ = metrics.get("serving_batch_occupancy")
        assert occ.count == 5
        assert occ.sum == pytest.approx(1 + 0.5 + 1 + 1 + 0.75)

    def test_aot_executable_matches_fresh_jit_bitwise(self, engine):
        arrays = request_arrays(3, 13, seed=42)
        out = materialize(engine.dispatch(dict(arrays)), engine.graph)
        bucket = engine.bucket_for(3, 13)
        padded = engine._pad_to_bucket(arrays, bucket)
        fresh = jax.jit(engine.graph.fn)(engine._params, *padded)
        for name, got in out.items():
            want = np.asarray(fresh[name])[:3, :13]
            np.testing.assert_array_equal(got, want, err_msg=name)

    def test_consistent_with_direct_model_apply(self, engine):
        """Semantic parity: top-k over direct ``model.apply`` logits at
        the same padded shapes reproduces the engine's predictions."""
        arrays = request_arrays(2, 16, seed=7)
        out = materialize(engine.dispatch(dict(arrays)), engine.graph)
        model = engine.graph.model
        logits, _ = jax.jit(
            lambda p, i, m: model.apply(p, i, m, masking=False,
                                        policy=engine.policy)
        )(engine._params, arrays["input_ids"], arrays["pad_mask"])
        scores, idx = jax.lax.top_k(logits.astype(jnp.float32), 3)
        np.testing.assert_array_equal(out["topk_ids"], np.asarray(idx))
        np.testing.assert_array_equal(out["topk_scores"],
                                      np.asarray(scores))
        filled = np.where(arrays["input_ids"] == MASK_TOKEN_ID,
                          np.asarray(idx)[..., 0], arrays["input_ids"])
        np.testing.assert_array_equal(out["filled_ids"], filled)

    def test_dispatch_does_not_clobber_request_arrays(self, engine):
        """The MLM graph donates its request buffers — donation must
        consume the device COPY, never the caller's host arrays."""
        arrays = request_arrays(2, 16, seed=3)
        ids_before = arrays["input_ids"].copy()
        engine.dispatch(arrays)
        engine.dispatch(arrays)  # same host arrays again
        np.testing.assert_array_equal(arrays["input_ids"], ids_before)

    def test_request_too_large(self, engine):
        with pytest.raises(RequestTooLarge):
            engine.dispatch(request_arrays(5, 16))  # batch > 4
        with pytest.raises(ValueError):
            engine.dispatch({"input_ids": np.zeros((1, 4), np.int32)})

    def test_seq_bucket_beyond_model_rejected(self):
        with pytest.raises(ValueError, match="max_seq_len"):
            ServingEngine(tiny_mlm_task(), batch_buckets=(1,),
                          seq_buckets=(64,), warmup=False)

    def test_update_params_refreshes_without_recompile(self):
        eng = ServingEngine(tiny_mlm_task(), batch_buckets=(2,),
                            seq_buckets=(16,))
        arrays = request_arrays(2, 16, seed=5)
        before = materialize(eng.dispatch(dict(arrays)), eng.graph)
        new_params = eng.graph.init_params(seed=123)
        with compile_events() as events:
            eng.update_params(new_params)
            after = materialize(eng.dispatch(dict(arrays)), eng.graph)
        assert events == []
        assert not np.array_equal(before["topk_scores"],
                                  after["topk_scores"])
        with pytest.raises(ValueError, match="same pytree structure"):
            eng.update_params({"nope": np.zeros(3)})

    def test_update_params_concurrent_dispatch_no_torn_pytree(self):
        """Dispatches racing ``update_params`` swaps: every result
        must come entirely from the old params or entirely from the
        new — a torn (half-swapped) pytree would produce a third
        output value. This is the replica-side invariant the fleet's
        rolling update builds on (docs/SERVING.md "Fleet")."""
        eng = ServingEngine(tiny_mlm_task(), batch_buckets=(1,),
                            seq_buckets=(16,))
        arrays = request_arrays(1, 16, seed=7)
        params_a = eng.graph.init_params(seed=111)
        params_b = eng.graph.init_params(seed=222)
        eng.update_params(params_a)
        out_a = materialize(eng.dispatch(dict(arrays)),
                            eng.graph)["topk_scores"]
        eng.update_params(params_b)
        out_b = materialize(eng.dispatch(dict(arrays)),
                            eng.graph)["topk_scores"]
        assert not np.array_equal(out_a, out_b)

        torn, errors = [], []

        def dispatcher():
            try:
                for _ in range(20):
                    got = materialize(eng.dispatch(dict(arrays)),
                                      eng.graph)["topk_scores"]
                    if not (np.array_equal(got, out_a)
                            or np.array_equal(got, out_b)):
                        torn.append(got)
                        return
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=dispatcher) for _ in range(4)]
        for t in threads:
            t.start()
        swaps = 0
        while any(t.is_alive() for t in threads):
            eng.update_params(params_a if swaps % 2 == 0 else params_b)
            swaps += 1
        for t in threads:
            t.join(30)
        assert not errors, errors
        assert not torn, "a dispatch saw a torn params pytree"
        assert swaps >= 2  # the race actually raced

    def test_checkpoint_restore_roundtrip(self, tmp_path):
        from perceiver_tpu.training.checkpoint import save_params

        task = tiny_mlm_task()
        params = task.build().init(jax.random.key(9))
        save_params(str(tmp_path / "ck"), params)
        eng = ServingEngine(task, checkpoint=str(tmp_path / "ck"),
                            batch_buckets=(1,), seq_buckets=(16,))
        ref = ServingEngine(task, params, batch_buckets=(1,),
                            seq_buckets=(16,))
        arrays = request_arrays(1, 16, seed=11)
        out = materialize(eng.dispatch(dict(arrays)), eng.graph)
        want = materialize(ref.dispatch(dict(arrays)), ref.graph)
        for name in out:
            np.testing.assert_array_equal(out[name], want[name], name)


class TestMicroBatcher:
    def test_coalesces_concurrent_requests(self):
        seen_batches = []
        done = threading.Event()

        def runner(items):
            seen_batches.append(len(items))
            done.wait(0.2)  # hold the first batch so the rest queue up
            return [x * 10 for x in items]

        mb = MicroBatcher(runner, max_batch=4, max_delay_ms=50,
                          max_depth=64)
        try:
            futs = [mb.submit(i) for i in range(9)]
            done.set()
            results = [f.result(timeout=10) for f in futs]
            assert results == [i * 10 for i in range(9)]
            assert sum(seen_batches) == 9
            assert max(seen_batches) <= 4
            assert len(seen_batches) >= 3
            m = mb.metrics
            assert m.get("serving_requests_total").value_of(
                outcome="ok") == 9
            assert m.get("serving_request_latency_seconds").count == 9
            assert m.get("serving_batch_size").count == len(seen_batches)
        finally:
            mb.close()

    def test_sheds_queue_full_with_typed_result(self):
        release = threading.Event()

        def runner(items):
            release.wait(5)
            return items

        mb = MicroBatcher(runner, max_batch=1, max_delay_ms=0,
                          max_depth=2)
        try:
            futs = [mb.submit(i) for i in range(12)]
            release.set()
            results = [f.result(timeout=10) for f in futs]
            shed = [r for r in results if isinstance(r, Overloaded)]
            served = [r for r in results if not isinstance(r, Overloaded)]
            assert shed and served
            assert all(s.reason == "queue_full" for s in shed)
            assert mb.metrics.get("serving_shed_total").value_of(
                reason="queue_full") == len(shed)
            # the queue never exceeded its bound, so at most
            # max_depth + in-flight requests were ever accepted
            assert mb.depth == 0
        finally:
            mb.close()

    def test_deadline_expired_requests_are_shed_unserved(self):
        ran = []
        release = threading.Event()

        def runner(items):
            release.wait(5)
            ran.extend(items)
            return items

        mb = MicroBatcher(runner, max_batch=1, max_delay_ms=0,
                          max_depth=16)
        try:
            blocker = mb.submit("blocker")  # occupies the runner
            time.sleep(0.05)
            doomed = mb.submit("doomed", timeout_ms=1)
            time.sleep(0.05)  # deadline passes while queued
            release.set()
            assert blocker.result(timeout=10) == "blocker"
            r = doomed.result(timeout=10)
            assert isinstance(r, Overloaded) and r.reason == "deadline"
            assert "doomed" not in ran  # shed BEFORE compute
            assert mb.metrics.get("serving_shed_total").value_of(
                reason="deadline") == 1
        finally:
            mb.close()

    def test_drain_waits_for_queued_and_inflight(self):
        release = threading.Event()

        def runner(items):
            release.wait(5)
            return items

        mb = MicroBatcher(runner, max_batch=2, max_delay_ms=0,
                          max_depth=16)
        try:
            futs = [mb.submit(i) for i in range(6)]
            # a batch is wedged inside the runner: drain must time out,
            # not report idle while requests are unresolved
            assert not mb.drain(timeout=0.1)
            release.set()
            assert mb.drain(timeout=10)
            assert mb.depth == 0 and mb.inflight == 0
            assert [f.result(timeout=1) for f in futs] == list(range(6))
        finally:
            mb.close()

    def test_close_is_idempotent_and_resolves_every_future(self):
        def runner(items):
            time.sleep(0.005)
            return [x * 2 for x in items]

        mb = MicroBatcher(runner, max_batch=4, max_delay_ms=5,
                          max_depth=32)
        futs = [mb.submit(i) for i in range(8)]
        mb.close()
        # close drains: every accepted request resolved with a result
        assert [f.result(timeout=1) for f in futs] == [
            i * 2 for i in range(8)]
        mb.close()  # second close returns immediately, no error
        mb.close()

    def test_close_fails_stranded_futures_typed_when_runner_wedged(self):
        from perceiver_tpu.serving.errors import Unavailable

        wedge = threading.Event()

        def runner(items):
            wedge.wait(30)  # far past close()'s timeout
            return items

        mb = MicroBatcher(runner, max_batch=1, max_delay_ms=0,
                          max_depth=16)
        futs = [mb.submit(i) for i in range(4)]
        time.sleep(0.05)  # let the worker wedge on the first batch
        mb.close(timeout=0.2)
        stranded = 0
        for f in futs:
            if f.done() and f.exception() is not None:
                assert isinstance(f.exception(), Unavailable)
                assert f.exception().reason == "shutting_down"
                stranded += 1
        assert stranded >= 1  # queued-but-unserved futures got typed
        wedge.set()  # unwedge so the daemon worker exits

    def test_runner_error_fails_batch_not_worker(self):
        calls = []

        def runner(items):
            calls.append(list(items))
            if len(calls) == 1:
                raise RuntimeError("boom")
            return items

        mb = MicroBatcher(runner, max_batch=8, max_delay_ms=1)
        try:
            f1 = mb.submit("a")
            with pytest.raises(RuntimeError, match="boom"):
                f1.result(timeout=10)
            f2 = mb.submit("b")
            assert f2.result(timeout=10) == "b"
            assert mb.metrics.get("serving_requests_total").value_of(
                outcome="error") == 1
        finally:
            mb.close()


def make_tiny_tokenizer():
    from perceiver_tpu.tokenizer import create_tokenizer, train_tokenizer
    from perceiver_tpu.tokenizer.wordpiece import Replace

    corpus = ["the quick brown fox jumps over the lazy dog",
              "the lazy dog sleeps deeply near the quick fox",
              "a quick movie about a lazy brown dog"] * 5
    tok = create_tokenizer(Replace("<br />", " "))
    train_tokenizer(tok, corpus, vocab_size=VOCAB)
    assert tok.get_vocab_size() <= VOCAB
    return tok


class TestMLMServerEndToEnd:
    @pytest.fixture(scope="class")
    def server(self):
        metrics = MetricsRegistry()
        engine = ServingEngine(tiny_mlm_task(), batch_buckets=(1, 4),
                               seq_buckets=(16, 32), metrics=metrics)
        server = MLMServer(engine, make_tiny_tokenizer(),
                           max_delay_ms=10, max_depth=32)
        yield server
        server.close()

    def test_concurrent_fill_mask_across_buckets(self, server):
        short = "the quick [MASK] jumps"             # → seq bucket 16
        long = ("the quick brown fox jumps over the lazy dog and the "
                "lazy dog sleeps near the quick [MASK] fox deeply")
        texts = [short, long, "a [MASK] movie about a [MASK] dog",
                 short, long]
        with compile_events() as events:
            futs = [server.submit(t) for t in texts]
            results = [f.result(timeout=30) for f in futs]
        assert events == [], "serving traffic must not compile"
        for t, r in zip(texts, results):
            assert not isinstance(r, Overloaded)
            assert r.text == t
            assert len(r.predictions) == 3  # top-k fills, decoded
            assert len(r.masked_positions) == t.count("[MASK]")
            assert all(len(toks) == 3 for toks in r.topk_tokens)
            for p in r.predictions:
                assert "[MASK]" not in p

    def test_fill_parity_with_model_apply(self, server):
        """Bitwise: the served fill equals top-k over a direct jitted
        ``model.apply`` on the same encoded+padded request."""
        text = "the lazy [MASK] sleeps"
        r = server.fill_mask(text)
        eng, tok = server.engine, server.tokenizer
        ids_row = np.asarray(tok.encode(text).ids, np.int32)
        n = len(ids_row)
        bucket = eng.bucket_for(1, n)
        ids = np.full((1, bucket[1]), 0, np.int32)
        ids[0, :n] = ids_row
        pad = np.arange(bucket[1])[None, :] >= n
        model = eng.graph.model
        logits, _ = jax.jit(
            lambda p, i, m: model.apply(p, i, m, masking=False,
                                        policy=eng.policy)
        )(eng._params, ids, pad)
        _, idx = jax.lax.top_k(logits.astype(jnp.float32), 3)
        idx = np.asarray(idx)[0, :n]
        expect = []
        for k in range(3):
            filled = np.where(ids_row == MASK_TOKEN_ID, idx[:, k],
                              ids_row)
            expect.append(tok.decode(filled.tolist()))
        assert r.predictions == expect

    def test_metrics_account_for_work_performed(self, server):
        m = server.metrics
        served = m.get("serving_requests_total").value_of(outcome="ok")
        assert served >= 6  # the two tests above
        assert m.get("serving_request_latency_seconds").count == served
        # every dispatch recorded occupancy + waste + a bucket label
        dispatched = m.get("serving_bucket_dispatch_total").value
        assert m.get("serving_batch_occupancy").count == dispatched
        assert m.get("serving_padding_waste_fraction").count == dispatched
        # engine compiled exactly its warmup grid, nothing more
        assert m.get("serving_compile_total").value == 4
        text = server.metrics_text()
        assert "serving_request_latency_seconds_bucket{le=" in text
        assert "serving_bucket_dispatch_total{bucket=" in text

    def test_saturated_queue_sheds_with_deadline(self, server):
        """Deadline shedding under a saturated queue: hold the worker
        with a long batch, then stack requests whose deadlines expire
        while queued."""
        before = server.metrics.get("serving_shed_total").value_of(
            reason="deadline")
        futs = [server.submit("the [MASK] dog", timeout_ms=0.01)
                for _ in range(8)]
        results = [f.result(timeout=30) for f in futs]
        shed = [r for r in results if isinstance(r, Overloaded)]
        assert shed, "0.01 ms deadlines must shed under queueing"
        assert all(s.reason == "deadline" for s in shed)
        after = server.metrics.get("serving_shed_total").value_of(
            reason="deadline")
        assert after - before == len(shed)

    def test_close_drains_then_is_idempotent(self, server):
        """Must run last in this class (it closes the shared server):
        close() resolves every accepted request before tearing the
        worker down, and repeat closes (the fixture teardown makes a
        third) are no-ops."""
        futs = [server.submit("the [MASK] dog") for _ in range(4)]
        server.close()
        for f in futs:
            r = f.result(timeout=1)  # resolved, not stranded
            assert isinstance(r, Overloaded) or r.predictions
        server.close()  # idempotent


class TestPredictCompat:
    """utils/predict.py is now a serving-engine wrapper (satellite 3)."""

    def _fixture(self):
        task = tiny_mlm_task()
        model = task.build()
        params = model.init(jax.random.key(0))
        tok = make_tiny_tokenizer()

        def encode_fn(texts):
            ids, lengths = tok.encode_batch_padded(texts, 16, pad_id=0)
            pad_mask = np.arange(16)[None, :] >= lengths[:, None]
            return ids, pad_mask

        return task, model, params, tok, encode_fn

    def test_matches_legacy_implementation(self):
        from perceiver_tpu.utils.predict import predict_masked_samples

        task, model, params, tok, encode_fn = self._fixture()
        samples = ["the quick [MASK] jumps", "a [MASK] dog"]
        got = predict_masked_samples(samples, encode_fn, tok, model,
                                     params, num_predictions=2)
        # the reference semantics, computed the pre-serving way
        ids, pad_mask = encode_fn(samples)
        logits, _ = jax.jit(
            lambda p, x, m: model.apply(p, x, m, masking=False)
        )(params, jnp.asarray(ids), jnp.asarray(pad_mask))
        _, top = jax.lax.top_k(logits.astype(jnp.float32), 2)
        top = np.asarray(top)
        for b in range(len(samples)):
            mask_pos = np.nonzero(ids[b] == MASK_TOKEN_ID)[0]
            for k in range(2):
                filled = ids[b].copy()
                filled[mask_pos] = top[b, mask_pos, k]
                assert got[b][k] == tok.decode(filled.tolist())

    def test_second_call_same_shapes_zero_new_compiles(self):
        """The regression the old helper failed: it re-jit a fresh
        lambda per call, recompiling every time."""
        from perceiver_tpu.utils.predict import predict_masked_samples

        task, model, params, tok, encode_fn = self._fixture()
        samples = ["the [MASK] fox", "the lazy [MASK]"]
        first = predict_masked_samples(samples, encode_fn, tok, model,
                                       params)
        with compile_events() as events:
            second = predict_masked_samples(samples, encode_fn, tok,
                                            model, params)
        assert events == [], f"second predict call compiled: {events}"
        assert first == second
        # weight refresh keeps the cache warm too (trainer behavior:
        # fresh params every validation epoch, same shapes)
        new_params = model.init(jax.random.key(1))
        with compile_events() as events:
            predict_masked_samples(samples, encode_fn, tok, model,
                                   new_params)
        assert events == []

    def test_repeat_shapes_warm_across_processes(self, tmp_path):
        """With PERCEIVER_EXEC_CACHE set, the serving engine behind
        ``predict_masked_samples`` persists its lazily-compiled
        executables — a SECOND PROCESS at the same shapes performs
        zero XLA compiles during predict and reproduces the first
        process's fills bitwise."""
        import json
        import os
        import subprocess
        import sys

        script = tmp_path / "predict_child.py"
        script.write_text(_PREDICT_CHILD)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        results = []
        for _ in range(2):
            r = subprocess.run(
                [sys.executable, str(script)],
                env=dict(os.environ, JAX_PLATFORMS="cpu",
                         PERCEIVER_EXEC_CACHE=str(tmp_path / "ec")),
                cwd=repo, capture_output=True, text=True, timeout=600)
            assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
            results.append(json.loads(
                r.stdout.strip().splitlines()[-1]))
        first, second = results
        assert first["predict_compile_events"] > 0
        assert second["predict_compile_events"] == 0, \
            "warm-process predict must not compile"
        assert second["preds"] == first["preds"]


def ragged_requests(lengths, seed=0, mask_every=4):
    """Per-request id rows + the packed/rect encodings of the batch."""
    rng = np.random.default_rng(seed)
    rows = []
    for n in lengths:
        ids = rng.integers(3, VOCAB, (int(n),)).astype(np.int32)
        ids[::mask_every] = MASK_TOKEN_ID
        rows.append(ids)
    lens = np.asarray(lengths, np.int32)
    offs = np.zeros_like(lens)
    offs[1:] = np.cumsum(lens)[:-1]
    packed = {"packed_ids": np.concatenate(rows),
              "row_offsets": offs, "lengths": lens}
    return rows, packed


class TestPackedEngine:
    """Packed (ragged) dispatch: parity with the rectangular path per
    request, exact waste accounting, AOT-only bucketing (ISSUE 9)."""

    @pytest.fixture(scope="class")
    def packed_engine(self):
        # fp32 so packed-vs-rect comparisons are numerical, not
        # bf16-rounding roulette; registered canonical targets cover
        # the bf16 serve policy
        return ServingEngine(tiny_mlm_task(), batch_buckets=(1, 4),
                             seq_buckets=(16, 32),
                             packed_buckets=((64, 4), (128, 8)),
                             policy=Policy.fp32())

    def test_warmup_includes_packed_buckets(self, packed_engine):
        assert packed_engine.compiled_buckets == (
            (1, 16), (1, 32), (4, 16), (4, 32),
            ("packed", 64, 4), ("packed", 128, 8))

    def _rect_single(self, engine, ids_row):
        n = len(ids_row)
        arrays = {"input_ids": ids_row[None, :],
                  "pad_mask": np.zeros((1, n), bool)}
        return materialize(engine.dispatch(arrays), engine.graph)

    @pytest.mark.parametrize("lengths", [
        [9, 30, 3, 16],      # mixed, mid-bucket occupancy
        [13],                # single request
        [16, 16, 16, 16],    # exactly fills the (64, 4) bucket
    ])
    def test_parity_with_rect_per_request(self, packed_engine, lengths):
        rows, packed = ragged_requests(lengths, seed=17)
        res = packed_engine.dispatch_packed(packed)
        out = materialize_packed(res, packed_engine.packed_graph)
        off = 0
        for ids_row in rows:
            n = len(ids_row)
            want = self._rect_single(packed_engine, ids_row)
            got_filled = out["filled_ids"][off:off + n]
            np.testing.assert_array_equal(got_filled,
                                          want["filled_ids"][0])
            np.testing.assert_array_equal(out["is_masked"][off:off + n],
                                          want["is_masked"][0])
            np.testing.assert_array_equal(out["topk_ids"][off:off + n],
                                          want["topk_ids"][0])
            np.testing.assert_allclose(out["topk_scores"][off:off + n],
                                       want["topk_scores"][0],
                                       atol=1e-4, rtol=1e-4)
            off += n

    def test_zero_new_compiles_across_packed_shapes(self, packed_engine):
        shapes = [[5], [9, 30, 3], [16, 16, 16, 16], [32, 32, 31],
                  [1, 1, 1, 1, 1]]
        with compile_events() as events:
            for i, lengths in enumerate(shapes):
                _, packed = ragged_requests(lengths, seed=i)
                res = packed_engine.dispatch_packed(packed)
                materialize_packed(res, packed_engine.packed_graph)
        assert events == [], f"packed dispatch compiled: {events}"

    def test_smallest_fitting_token_bucket(self, packed_engine):
        assert packed_engine.packed_bucket_for(10, 2) == ("packed", 64, 4)
        assert packed_engine.packed_bucket_for(64, 4) == ("packed", 64, 4)
        assert packed_engine.packed_bucket_for(65, 2) == ("packed", 128, 8)
        assert packed_engine.packed_bucket_for(10, 5) == ("packed", 128, 8)
        with pytest.raises(RequestTooLarge):
            packed_engine.packed_bucket_for(129, 1)
        with pytest.raises(RequestTooLarge):
            packed_engine.packed_bucket_for(8, 9)

    def test_request_longer_than_model_rejected(self, packed_engine):
        # 40 tokens fits the 64-token budget but exceeds max_seq_len=32
        _, packed = ragged_requests([40], seed=3)
        with pytest.raises(RequestTooLarge, match="max_seq_len"):
            packed_engine.dispatch_packed(packed)

    def test_input_validation(self, packed_engine):
        _, packed = ragged_requests([5, 6], seed=4)
        with pytest.raises(ValueError, match="inputs"):
            packed_engine.dispatch_packed(
                {"packed_ids": packed["packed_ids"]})
        bad = dict(packed)
        bad["row_offsets"] = bad["row_offsets"][:1]
        with pytest.raises(ValueError, match="row_offsets"):
            packed_engine.dispatch_packed(bad)

    def test_engine_without_packed_mode_rejects(self):
        eng = ServingEngine(tiny_mlm_task(), batch_buckets=(1,),
                            seq_buckets=(16,))
        _, packed = ragged_requests([5], seed=5)
        with pytest.raises(ValueError, match="packed"):
            eng.dispatch_packed(packed)

    def test_padded_token_accounting_exact(self):
        """Satellite 1: the waste metrics count TRUE padded tokens.
        Rect dispatch with per-request lengths no longer undercounts
        intra-batch padding; packed dispatch counts only its bucket
        tail."""
        metrics = MetricsRegistry()
        eng = ServingEngine(tiny_mlm_task(), batch_buckets=(1, 4),
                            seq_buckets=(16, 32),
                            packed_buckets=((64, 4),), metrics=metrics)
        counter = metrics.get("serving_padded_tokens_total")
        waste = metrics.get("serving_padding_waste_fraction")

        rows, packed = ragged_requests([9, 30, 3], seed=6)
        # rect: requests padded to width 30 upstream, bucket (4, 32)
        ids = np.zeros((3, 30), np.int32)
        for i, r in enumerate(rows):
            ids[i, :len(r)] = r
        pad = np.arange(30)[None, :] >= packed["lengths"][:, None]
        res = eng.dispatch({"input_ids": ids, "pad_mask": pad},
                           lengths=packed["lengths"])
        assert res.lengths is packed["lengths"]
        assert counter.value_of(mode="rect") == 4 * 32 - 42
        assert waste.sum == pytest.approx(1 - 42 / 128)

        # packed: same requests, 64-token bucket, 22-token tail
        eng.dispatch_packed(packed)
        assert counter.value_of(mode="packed") == 64 - 42
        assert waste.sum == pytest.approx((1 - 42 / 128) + (1 - 42 / 64))

    def test_rect_without_lengths_keeps_lower_bound(self):
        metrics = MetricsRegistry()
        eng = ServingEngine(tiny_mlm_task(), batch_buckets=(1,),
                            seq_buckets=(16,), metrics=metrics)
        eng.dispatch(request_arrays(1, 9))
        # no lengths: only the bucket-width padding is visible
        assert metrics.get("serving_padded_tokens_total").value_of(
            mode="rect") == 16 - 9

    def test_packed_bucket_dispatch_labels(self, packed_engine):
        # packed buckets get their own t{tokens}_r{rows} label family,
        # disjoint from the rect b{batch}_s{seq} names
        dispatch = packed_engine.metrics.get(
            "serving_bucket_dispatch_total")
        assert dispatch.value_of(bucket="t64_r4") > 0
        assert dispatch.value_of(bucket="b1_s16") > 0


class TestPackedTextClassifier:
    def _tiny_clf_task(self):
        from perceiver_tpu.tasks import TextClassifierTask
        return TextClassifierTask(
            num_classes=2, vocab_size=VOCAB, max_seq_len=32,
            num_latents=4, num_latent_channels=8, num_encoder_layers=1,
            num_encoder_self_attention_layers_per_block=1,
            num_encoder_cross_attention_heads=1,
            num_encoder_self_attention_heads=1,
            num_decoder_cross_attention_heads=1)

    def test_parity_with_rect_per_request(self):
        eng = ServingEngine(self._tiny_clf_task(), batch_buckets=(1, 4),
                            seq_buckets=(16, 32),
                            packed_buckets=((64, 4),),
                            policy=Policy.fp32())
        rows, packed = ragged_requests([9, 30, 3], seed=21)
        res = eng.dispatch_packed(packed)
        out = materialize_packed(res, eng.packed_graph)
        assert out["logits"].shape == (3, 2)
        for i, ids_row in enumerate(rows):
            n = len(ids_row)
            arrays = {"input_ids": ids_row[None, :],
                      "pad_mask": np.zeros((1, n), bool)}
            want = materialize(eng.dispatch(arrays), eng.graph)
            np.testing.assert_allclose(out["logits"][i],
                                       want["logits"][0],
                                       atol=1e-4, rtol=1e-4)
            assert out["label"][i] == want["label"][0]


class TestTokenBudgetBatcher:
    """Continuous batching by token budget (satellite 3): grouping by
    cost, and the MicroBatcher contract — deadline shed, drain,
    close — intact through the subclass."""

    def test_groups_by_token_budget(self):
        seen = []
        hold = threading.Event()

        def runner(items):
            seen.append(list(items))
            hold.wait(0.2)
            return [x * 10 for x in items]

        tb = TokenBudgetBatcher(runner, token_budget=10,
                                cost_fn=lambda x: x, max_delay_ms=50,
                                max_depth=64)
        try:
            costs = [4, 4, 4, 11, 2, 9]
            futs = [tb.submit(c) for c in costs]
            hold.set()
            assert [f.result(timeout=10) for f in futs] == [
                c * 10 for c in costs]
            for batch in seen:
                # over-budget batches only as a head-of-line singleton
                assert sum(batch) <= 10 or len(batch) == 1
            # the 11-cost request went alone even though budget is 10
            assert [11] in seen
        finally:
            tb.close()

    def test_max_requests_caps_rows(self):
        hold = threading.Event()
        seen = []

        def runner(items):
            seen.append(list(items))
            hold.wait(0.2)
            return items

        tb = TokenBudgetBatcher(runner, token_budget=10_000,
                                cost_fn=lambda x: 1, max_requests=3,
                                max_delay_ms=50)
        try:
            futs = [tb.submit(i) for i in range(10)]
            hold.set()
            [f.result(timeout=10) for f in futs]
            assert max(len(b) for b in seen) <= 3
        finally:
            tb.close()

    def test_invalid_budget_rejected(self):
        with pytest.raises(ValueError, match="token_budget"):
            TokenBudgetBatcher(lambda x: x, token_budget=0,
                               cost_fn=lambda x: 1)

    def test_deadline_shed_before_compute(self):
        ran = []
        release = threading.Event()

        def runner(items):
            release.wait(5)
            ran.extend(items)
            return items

        tb = TokenBudgetBatcher(runner, token_budget=8,
                                cost_fn=lambda x: 4, max_delay_ms=0,
                                max_depth=16)
        try:
            blocker = tb.submit("blocker")
            time.sleep(0.05)
            doomed = tb.submit("doomed", timeout_ms=1)
            time.sleep(0.05)
            release.set()
            assert blocker.result(timeout=10) == "blocker"
            r = doomed.result(timeout=10)
            assert isinstance(r, Overloaded) and r.reason == "deadline"
            assert "doomed" not in ran
        finally:
            tb.close()

    def test_queue_full_sheds_typed(self):
        release = threading.Event()

        def runner(items):
            release.wait(5)
            return items

        tb = TokenBudgetBatcher(runner, token_budget=4,
                                cost_fn=lambda x: 4, max_delay_ms=0,
                                max_depth=2)
        try:
            futs = [tb.submit(i) for i in range(12)]
            release.set()
            results = [f.result(timeout=10) for f in futs]
            shed = [r for r in results if isinstance(r, Overloaded)]
            assert shed
            assert all(s.reason == "queue_full" for s in shed)
        finally:
            tb.close()

    def test_drain_and_close_contract(self):
        release = threading.Event()

        def runner(items):
            release.wait(5)
            return items

        tb = TokenBudgetBatcher(runner, token_budget=6,
                                cost_fn=lambda x: 3, max_delay_ms=0,
                                max_depth=16)
        futs = [tb.submit(i) for i in range(5)]
        assert not tb.drain(timeout=0.1)
        release.set()
        assert tb.drain(timeout=10)
        assert tb.depth == 0 and tb.inflight == 0
        assert [f.result(timeout=1) for f in futs] == list(range(5))
        tb.close()
        tb.close()  # idempotent

    def test_close_strands_typed_when_wedged(self):
        from perceiver_tpu.serving.errors import Unavailable

        wedge = threading.Event()

        def runner(items):
            wedge.wait(30)
            return items

        tb = TokenBudgetBatcher(runner, token_budget=4,
                                cost_fn=lambda x: 4, max_delay_ms=0,
                                max_depth=16)
        futs = [tb.submit(i) for i in range(4)]
        time.sleep(0.05)
        tb.close(timeout=0.2)
        stranded = 0
        for f in futs:
            if f.done() and f.exception() is not None:
                assert isinstance(f.exception(), Unavailable)
                assert f.exception().reason == "shutting_down"
                stranded += 1
        assert stranded >= 1
        wedge.set()


class TestPackedMLMServer:
    """The packed server path end to end: tokenizing at submit,
    token-budget batching, ragged dispatch, per-request slicing."""

    @pytest.fixture(scope="class")
    def engines(self):
        policy = Policy.fp32()
        rect = ServingEngine(tiny_mlm_task(), batch_buckets=(1, 4),
                             seq_buckets=(16, 32), policy=policy)
        packed = ServingEngine(tiny_mlm_task(), batch_buckets=(),
                               seq_buckets=(),
                               allow_unlisted_buckets=True,
                               packed_buckets=((64, 4), (128, 8)),
                               policy=policy)
        return rect, packed

    def test_packed_matches_rect_server_predictions(self, engines):
        rect_eng, packed_eng = engines
        tok = make_tiny_tokenizer()
        texts = ["the quick [MASK] jumps",
                 "a [MASK] movie about a [MASK] dog",
                 ("the quick brown fox jumps over the lazy dog and "
                  "the lazy dog sleeps near the [MASK] fox"),
                 "the [MASK] dog"]
        rect_srv = MLMServer(rect_eng, tok, max_delay_ms=10)
        packed_srv = MLMServer(packed_eng, tok, packed=True,
                               max_delay_ms=10)
        try:
            with compile_events() as events:
                rf = [rect_srv.submit(t) for t in texts]
                pf = [packed_srv.submit(t) for t in texts]
                rect_out = [f.result(timeout=30) for f in rf]
                packed_out = [f.result(timeout=30) for f in pf]
            assert events == [], "packed serving traffic compiled"
            for t, r, p in zip(texts, rect_out, packed_out):
                assert not isinstance(p, Overloaded)
                assert p.text == t
                assert p.predictions == r.predictions
                assert p.masked_positions == r.masked_positions
                assert p.topk_tokens == r.topk_tokens
        finally:
            rect_srv.close()
            packed_srv.close()

    def test_packed_requires_packed_engine(self, engines):
        rect_eng, _ = engines
        with pytest.raises(ValueError, match="packed_buckets"):
            MLMServer(rect_eng, make_tiny_tokenizer(), packed=True)

    def test_deadline_shed_in_packed_mode(self, engines):
        _, packed_eng = engines
        srv = MLMServer(packed_eng, make_tiny_tokenizer(), packed=True,
                        max_delay_ms=10)
        try:
            futs = [srv.submit("the [MASK] dog", timeout_ms=0.01)
                    for _ in range(8)]
            results = [f.result(timeout=30) for f in futs]
            shed = [r for r in results if isinstance(r, Overloaded)]
            assert shed
            assert all(s.reason == "deadline" for s in shed)
        finally:
            srv.close()

    def test_close_resolves_every_future_packed(self, engines):
        _, packed_eng = engines
        srv = MLMServer(packed_eng, make_tiny_tokenizer(), packed=True,
                        max_delay_ms=10)
        futs = [srv.submit("the [MASK] dog") for _ in range(4)]
        srv.close()
        for f in futs:
            r = f.result(timeout=1)
            assert isinstance(r, Overloaded) or r.predictions
        srv.close()  # idempotent


_PREDICT_CHILD = """
import json, os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from perceiver_tpu.tasks import MaskedLanguageModelTask
from perceiver_tpu.tokenizer import create_tokenizer, train_tokenizer
from perceiver_tpu.tokenizer.wordpiece import Replace
from perceiver_tpu.utils.predict import predict_masked_samples

corpus = ["the quick brown fox jumps over the lazy dog",
          "the lazy dog sleeps deeply near the quick fox",
          "a quick movie about a lazy brown dog"] * 5
tok = create_tokenizer(Replace("<br />", " "))
train_tokenizer(tok, corpus, vocab_size=110)
task = MaskedLanguageModelTask(
    vocab_size=110, max_seq_len=32, num_latents=4,
    num_latent_channels=8, num_encoder_layers=1,
    num_encoder_self_attention_layers_per_block=1,
    num_encoder_cross_attention_heads=1,
    num_encoder_self_attention_heads=1,
    num_decoder_cross_attention_heads=1, loss_impl="dense")
model = task.build()
params = model.init(jax.random.key(0))

def encode_fn(texts):
    ids, lengths = tok.encode_batch_padded(texts, 16, pad_id=0)
    pad_mask = np.arange(16)[None, :] >= lengths[:, None]
    return ids, pad_mask

from perceiver_tpu.cache import register_compile_listener
events = []
register_compile_listener(events.append)
preds = predict_masked_samples(
    ["the quick [MASK] jumps", "a [MASK] dog"], encode_fn, tok,
    model, params, num_predictions=2)
print(json.dumps({"predict_compile_events": len(events),
                  "preds": preds}))
"""
