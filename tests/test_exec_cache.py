"""Persistent compile cache (ISSUE 4): serialized AOT executables.

The acceptance properties, each pinned here:

- a warm-start ``ServingEngine`` warmup over the FULL bucket grid
  performs **zero** XLA compiles — asserted via ``jax.monitoring``
  compile events in a fresh subprocess against a cache a previous
  subprocess populated;
- every cache failure mode degrades to a real compile, never a crash:
  truncated/corrupt blob (+ corrupt/miss counters), doctored version
  sidecar, missing entries;
- version skew keys differently (a jaxlib bump can never load a stale
  executable);
- eviction respects the size cap, dropping least-recently-used
  entries first;
- two engines sharing one cache directory don't race (atomic
  tempfile + rename publication);
- the trainer loads its step through ``aot_compile``: stored once, a
  hit with zero compiles in the next process, an entry of the format
  the trainer wrote before still a hit, a step with host callbacks
  never stored, and without a cache the jitted function itself.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_tpu.cache import (
    ExecutableCache,
    aot_compile,
    default_cache,
    source_tree_digest,
)
from perceiver_tpu.cache import exec_cache as exec_cache_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache(tmp_path, **kw):
    return ExecutableCache(str(tmp_path / "ec"), **kw)


def _tiny_jit(mult=2.0):
    return jax.jit(lambda p, x: {"y": p * x + mult},
                   donate_argnums=(1,))


ARGS = (jnp.arange(4.0), jnp.ones((4,)))


class TestExecutableEntries:
    def test_miss_compile_store_then_hit_parity(self, tmp_path):
        cache = _cache(tmp_path)
        c1, info1 = aot_compile(_tiny_jit(), ARGS, cache=cache,
                                donate_argnums=(1,), label="t")
        assert not info1["hit"] and info1["bytes"] > 0
        assert cache.stats.misses == 1 and cache.stats.stores == 1
        c2, info2 = aot_compile(_tiny_jit(), ARGS, cache=cache,
                                donate_argnums=(1,))
        assert info2["hit"] and info2["key"] == info1["key"]
        assert cache.stats.hits == 1
        out1 = np.asarray(c1(jnp.arange(4.0), jnp.ones((4,)))["y"])
        out2 = np.asarray(c2(jnp.arange(4.0), jnp.ones((4,)))["y"])
        np.testing.assert_array_equal(out1, out2)
        # sidecar carries the cost analysis for warm-path consumers
        assert info2["sidecar"]["flops"] is not None

    def test_truncated_blob_falls_back_to_compile(self, tmp_path):
        cache = _cache(tmp_path)
        _, info = aot_compile(_tiny_jit(), ARGS, cache=cache)
        blob_path = cache._exe_path(info["key"])
        blob = open(blob_path, "rb").read()
        with open(blob_path, "wb") as f:
            f.write(blob[: len(blob) // 2])
        c, info2 = aot_compile(_tiny_jit(), ARGS, cache=cache)
        assert not info2["hit"], "corrupt entry must read as a miss"
        assert cache.stats.corrupt == 1
        # the fallback compiled + re-stored a good entry
        np.testing.assert_array_equal(
            np.asarray(c(jnp.arange(4.0), jnp.ones((4,)))["y"]),
            np.arange(4.0) + 2.0)
        _, info3 = aot_compile(_tiny_jit(), ARGS, cache=cache)
        assert info3["hit"]

    def test_garbage_blob_and_missing_sidecar(self, tmp_path):
        cache = _cache(tmp_path)
        _, info = aot_compile(_tiny_jit(), ARGS, cache=cache)
        key = info["key"]
        with open(cache._exe_path(key), "wb") as f:
            f.write(b"not a pickle at all")
        assert cache.load_executable(key) is None
        # the bad entry was dropped outright
        assert not os.path.exists(cache._exe_path(key))
        # entry without a sidecar is a miss, not a crash
        _, info = aot_compile(_tiny_jit(), ARGS, cache=cache)
        os.unlink(cache._sidecar_path(info["key"]))
        assert cache.load_executable(info["key"]) is None

    def test_jaxlib_version_mismatch_keys_differently(self, tmp_path,
                                                      monkeypatch):
        cache = _cache(tmp_path)
        text = "func.func public @main() { fake }"
        key_now = cache.executable_key(text)
        monkeypatch.setattr(exec_cache_mod, "_versions",
                            lambda: ("99.0.0", "99.0.0"))
        key_future = cache.executable_key(text)
        assert key_now != key_future, \
            "a jax/jaxlib bump must change every executable key"
        assert cache.load_executable(key_future) is None

    def test_doctored_version_sidecar_is_dropped(self, tmp_path):
        """Defense in depth: an entry whose sidecar claims another
        jaxlib (key collision / hand-copied file) is discarded."""
        cache = _cache(tmp_path)
        _, info = aot_compile(_tiny_jit(), ARGS, cache=cache)
        key = info["key"]
        side = json.load(open(cache._sidecar_path(key)))
        side["jaxlib"] = "0.0.1"
        with open(cache._sidecar_path(key), "w") as f:
            json.dump(side, f)
        assert cache.load_executable(key) is None
        assert not os.path.exists(cache._exe_path(key))

    def test_eviction_respects_size_cap_lru(self, tmp_path):
        cache = _cache(tmp_path)
        keys = []
        for i in range(3):
            _, info = aot_compile(_tiny_jit(float(i)), ARGS,
                                  cache=cache)
            keys.append(info["key"])
            time.sleep(0.02)  # distinct mtimes for LRU ordering
        per_entry = cache.entry_bytes() // 3
        # touch the oldest so the MIDDLE entry becomes LRU
        assert cache.load_executable(keys[0]) is not None
        time.sleep(0.02)
        small = ExecutableCache(cache.path,
                                max_bytes=2 * per_entry + per_entry // 2)
        small._evict()
        assert small.entry_bytes() <= small.max_bytes
        assert small.stats.evicted == 1
        assert not os.path.exists(small._exe_path(keys[1]))
        assert os.path.exists(small._exe_path(keys[0]))
        assert os.path.exists(small._exe_path(keys[2]))

    def test_default_cache_env_and_memoization(self, tmp_path,
                                               monkeypatch):
        monkeypatch.delenv("PERCEIVER_EXEC_CACHE", raising=False)
        assert default_cache() is None
        monkeypatch.setenv("PERCEIVER_EXEC_CACHE", str(tmp_path / "d"))
        c1 = default_cache()
        assert c1 is not None and c1 is default_cache()
        assert default_cache(str(tmp_path / "other")) is not c1

    def test_callback_graphs_bypass_cache(self, tmp_path):
        """jax.debug.print / io_callback graphs bake a host function
        pointer into the executable — garbage in any other process —
        so the cache must refuse them (compile fresh every time)."""
        from perceiver_tpu.cache import has_host_callbacks

        cache = _cache(tmp_path)

        def noisy(p, x):
            jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.debug.print("overflow {n}", n=v),
                lambda v: None, x.sum())
            return {"y": p * x}

        jitted = jax.jit(noisy)
        assert has_host_callbacks(jitted.lower(*ARGS).as_text())
        for _ in range(2):
            c, info = aot_compile(jitted, ARGS, cache=cache)
            assert not info["hit"] and info["key"] is None
            np.testing.assert_array_equal(
                np.asarray(c(jnp.arange(4.0), jnp.ones((4,)))["y"]),
                np.arange(4.0))
        assert cache.stats.stores == 0 and cache.stats.hits == 0

    def test_executable_key_is_stable_across_lowerings(self, tmp_path):
        """Two lowerings of the same callback-bearing program key the
        same (the installed jax prints a callback index, not a
        per-lowering wrapper address)."""
        cache = _cache(tmp_path)

        def make():
            def noisy(p, x):
                jax.lax.cond(
                    x.sum() > 0,
                    lambda v: jax.debug.print("n={n}", n=v),
                    lambda v: None, x.sum())
                return p * x
            return noisy

        t1 = jax.jit(make()).lower(*ARGS).as_text()
        t2 = jax.jit(make()).lower(*ARGS).as_text()
        assert cache.executable_key(t1) == cache.executable_key(t2)
        # a genuine program difference still keys differently
        t3 = jax.jit(lambda p, x: p * x + 1).lower(*ARGS).as_text()
        assert cache.executable_key(t1) != cache.executable_key(t3)

    def test_source_tree_digest_tracks_content(self, tmp_path):
        a, b = tmp_path / "a", tmp_path / "b"
        for root in (a, b):
            root.mkdir()
            (root / "m.py").write_text("x = 1\n")
        assert source_tree_digest(str(a)) == source_tree_digest(str(b))
        c = tmp_path / "c"
        c.mkdir()
        (c / "m.py").write_text("x = 2\n")
        assert source_tree_digest(str(a)) != source_tree_digest(str(c))


class TestLoweringRecords:
    def test_roundtrip_and_corruption(self, tmp_path):
        cache = _cache(tmp_path)
        key = cache.lowering_key("seeded_target")
        assert cache.load_lowering(key) is None
        record = {"text": "module {}", "expected_donated": 0,
                  "bytes_accessed": 123.0}
        assert cache.store_lowering(key, record)
        got = cache.load_lowering(key)
        assert got["text"] == "module {}"
        with open(cache._lowering_path(key), "w") as f:
            f.write("{ not json")
        assert cache.load_lowering(key) is None
        assert cache.stats.corrupt >= 0  # counted as miss, no crash

    def test_key_binds_source_digest(self, tmp_path, monkeypatch):
        cache = _cache(tmp_path)
        k1 = cache.lowering_key("t")
        monkeypatch.setattr(exec_cache_mod, "source_tree_digest",
                            lambda root=None: "deadbeef")
        assert cache.lowering_key("t") != k1, \
            "a source edit must invalidate lowering records"

    def test_key_forks_on_mesh_descriptor(self, tmp_path):
        """The same target lowered over two meshes must be two records
        — the shardings (and therefore the GSPMD collectives in the
        stored compiled text) differ per topology, so serving a
        data2_model2 record to a data4_model1 run would gate the wrong
        graph. ``lower_target`` passes ``mesh.descriptor`` as the key
        extra; axis NAMING forks too (a renamed axis changes every
        PartitionSpec even at the same shape)."""
        cache = _cache(tmp_path)
        keys = {cache.lowering_key("t", extra=extra)
                for extra in ((), ("data2_model2",), ("data4_model1",),
                              ("batch2_shard2",))}
        assert len(keys) == 4, "mesh descriptor must be key material"
        # and the record served back is the one stored under that mesh
        k22 = cache.lowering_key("t", extra=("data2_model2",))
        k41 = cache.lowering_key("t", extra=("data4_model1",))
        cache.store_lowering(k22, {"text": "module @m22 {}",
                                   "expected_donated": 0,
                                   "compiled_text": "HloModule m22",
                                   "mesh": "data2_model2"})
        cache.store_lowering(k41, {"text": "module @m41 {}",
                                   "expected_donated": 0,
                                   "compiled_text": "HloModule m41",
                                   "mesh": "data4_model1"})
        assert cache.load_lowering(k22)["mesh"] == "data2_model2"
        assert cache.load_lowering(k41)["compiled_text"] == \
            "HloModule m41"

    def test_executable_key_forks_on_pool_geometry(self, tmp_path):
        """ISSUE 14: the decode engine's AOT key carries the pool
        geometry descriptor (``DecodeGeometry.descriptor``) as key
        extra — two engines with different page counts or page sizes
        must never share an executable, even if a future refactor made
        their HLO coincide (the page-table ABI differs: table width and
        page-index range are geometry-bound host-side contracts)."""
        from perceiver_tpu.serving.decode import DecodeGeometry

        cache = _cache(tmp_path)
        geoms = (
            DecodeGeometry(max_streams=8, num_pages=64, page_size=16,
                           max_seq_len=512),
            DecodeGeometry(max_streams=8, num_pages=32, page_size=16,
                           max_seq_len=512),   # fewer pages
            DecodeGeometry(max_streams=8, num_pages=64, page_size=8,
                           max_seq_len=512),   # narrower pages
            DecodeGeometry(max_streams=4, num_pages=64, page_size=16,
                           max_seq_len=512),   # fewer slots
        )
        descriptors = {g.descriptor for g in geoms}
        assert len(descriptors) == 4, \
            "geometry descriptor must distinguish slots/pages/page size"
        text = "module @decode_step {}"  # same HLO for every key
        keys = {cache.executable_key(text, donate_argnums=(1,),
                                     extra=(g.descriptor,))
                for g in geoms}
        assert len(keys) == 4, "pool geometry must be key material"
        # identical geometry still dedupes to one key (warm restart hit)
        again = DecodeGeometry(max_streams=8, num_pages=64,
                               page_size=16, max_seq_len=512)
        assert cache.executable_key(
            text, donate_argnums=(1,),
            extra=(again.descriptor,)) in keys


class TestTrainerStepLoad:
    """``Trainer._load_step`` loads the train step through
    ``aot_compile``, the loader the serving engines use."""

    BATCH = {"input_ids": np.arange(3, 67, dtype=np.int32).reshape(4, 16),
             "pad_mask": np.zeros((4, 16), bool),
             "valid": np.ones((4,), bool)}

    def _trainer(self, tmp_path, cache_dir):
        from perceiver_tpu.training import Trainer, TrainerConfig

        trainer = Trainer(
            _tiny_task(), None,
            TrainerConfig(default_root_dir=str(tmp_path / "logs"),
                          enable_checkpointing=False,
                          exec_cache_dir=cache_dir),
            optimizer_init={"class_path": "AdamW",
                            "init_args": {"lr": 1e-3}})
        state = trainer._build_state()
        trainer._make_steps()
        return trainer, state, trainer._shard_batch(dict(self.BATCH))

    def test_store_once_then_hit_with_zero_compiles(self, tmp_path):
        from perceiver_tpu.cache import compile_events

        cache_dir = str(tmp_path / "ec")
        trainer, state, batch = self._trainer(tmp_path, cache_dir)
        stats = trainer._exec_cache.stats
        cold = trainer._load_step(trainer._train_step, state, batch, "t")
        assert (stats.stores, stats.hits) == (1, 0)
        assert cold is not trainer._train_step and trainer._step_loaded
        _, cold_metrics = cold(state, batch)

        trainer, state, batch = self._trainer(tmp_path, cache_dir)
        assert trainer._exec_cache.stats is stats   # one cache a directory
        with compile_events() as compiles:
            warm = trainer._load_step(trainer._train_step, state, batch,
                                      "t")
        assert (stats.stores, stats.hits) == (1, 1)
        assert compiles == [], compiles
        _, warm_metrics = warm(state, batch)
        assert float(warm_metrics["loss"]) == float(cold_metrics["loss"])

    def test_entry_written_by_the_parent_commit_loads(self, tmp_path):
        """Until PR 30 the trainer stored its step under
        ``executable_key(text)`` with a sidecar of ``label`` and the
        cost-analysis ``flops``: such an entry is a hit."""
        from perceiver_tpu.cache import compile_lowered

        trainer, state, batch = self._trainer(tmp_path,
                                              str(tmp_path / "ec"))
        cache = trainer._exec_cache
        lowered = trainer._train_step.lower(state, batch)
        cache.store_executable(
            cache.executable_key(lowered.as_text()),
            compile_lowered(lowered),
            sidecar={"label": "trainer:train_step", "flops": 1.5e9})
        step = trainer._load_step(trainer._train_step, state, batch,
                                  "trainer:train_step")
        assert (cache.stats.stores, cache.stats.hits) == (1, 1)
        _, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_step_with_host_callbacks_is_never_stored_or_loaded(
            self, tmp_path):
        """No shipped task's step calls back to the host
        (``tasks/mlm.py`` reports an overflow as a scalar for this
        reason); one that does is compiled fresh in every process."""
        from perceiver_tpu.cache import has_host_callbacks

        for _ in range(2):
            trainer, state, batch = self._trainer(tmp_path,
                                                  str(tmp_path / "ec"))
            inner = trainer._train_step

            def noisy_step(state, batch):
                jax.debug.print("step {}", state.step)
                return inner(state, batch)

            noisy = jax.jit(noisy_step)
            assert has_host_callbacks(noisy.lower(state, batch).as_text())
            step = trainer._load_step(noisy, state, batch, "t")
            assert step is not noisy
            stats = trainer._exec_cache.stats
            assert (stats.stores, stats.hits, stats.misses) == (0, 0, 0)
            assert os.listdir(trainer._exec_cache.path) == []
            _, metrics = step(state, batch)
            assert np.isfinite(float(metrics["loss"]))

    def test_without_a_cache_the_jitted_function_stays(self, tmp_path,
                                                       monkeypatch, capfd):
        monkeypatch.delenv("PERCEIVER_EXEC_CACHE", raising=False)
        trainer, state, batch = self._trainer(tmp_path, None)
        assert trainer._exec_cache is None and not trainer._step_loaded
        step = trainer._load_step(trainer._train_step, state, batch, "t")
        assert step is trainer._train_step and trainer._step_loaded
        # the trace still ran: the tallies have their call sites
        assert "attention call sites: materialized[backend]=3" in \
            capfd.readouterr().err


# --- engine integration ------------------------------------------------------


def _tiny_task():
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    return MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")


def _arrays(batch, length, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, 110, (batch, length)).astype(np.int32)
    return {"input_ids": ids,
            "pad_mask": np.zeros((batch, length), bool)}


class TestEngineIntegration:
    def test_two_engines_sharing_one_dir_do_not_race(self, tmp_path):
        """Concurrent warmups over one cache directory: atomic rename
        publication means both engines finish with working
        executables and the directory holds exactly one entry per
        bucket, no temp droppings."""
        from perceiver_tpu.serving import ServingEngine, materialize

        cache_dir = str(tmp_path / "shared")
        task = _tiny_task()
        engines = [ServingEngine(task, batch_buckets=(1, 2),
                                 seq_buckets=(16,), warmup=False,
                                 exec_cache=cache_dir)
                   for _ in range(2)]
        errors = []

        def warm(e):
            try:
                e.warmup()
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=warm, args=(e,))
                   for e in engines]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        outs = []
        for e in engines:
            assert e.compiled_buckets == ((1, 16), (2, 16))
            outs.append(materialize(e.dispatch(_arrays(1, 9)), e.graph))
        for name in outs[0]:
            np.testing.assert_array_equal(outs[0][name], outs[1][name])
        names = os.listdir(cache_dir)
        assert not [n for n in names if n.startswith(".tmp-")]
        assert len([n for n in names if n.endswith(".exe")]) == 2

    def test_corrupt_entry_engine_falls_back_and_counts(self, tmp_path):
        from perceiver_tpu.serving import ServingEngine

        cache_dir = tmp_path / "ec"
        task = _tiny_task()
        ServingEngine(task, batch_buckets=(1,), seq_buckets=(16,),
                      exec_cache=str(cache_dir))
        for name in os.listdir(cache_dir):
            if name.endswith(".exe"):
                with open(cache_dir / name, "wb") as f:
                    f.write(b"rotted")
        eng = ServingEngine(task, batch_buckets=(1,), seq_buckets=(16,),
                            exec_cache=str(cache_dir))
        m = eng.metrics
        assert eng.compile_count == 1  # real compile happened
        assert m.get("serving_exec_cache_misses_total").value == 1
        assert m.get("serving_exec_cache_hits_total").value == 0
        eng.dispatch(_arrays(1, 16))

    def test_prefix_cache_is_not_executable_key_material(self, tmp_path):
        """ISSUE 18 pin: content-addressed prefix sharing is pure
        host-side bookkeeping — enabling it must not change the
        geometry descriptor or fork the exec-cache key, so a replica
        that toggles the cache on warm-restarts into the SAME
        deserialized decode executable (zero XLA compiles)."""
        import re

        from perceiver_tpu.cache import compile_events
        from perceiver_tpu.ops.policy import Policy
        from perceiver_tpu.serving.decode import (
            DecodeEngine,
            DecodeGeometry,
        )
        from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig

        geometry = DecodeGeometry(max_streams=2, num_pages=9,
                                  page_size=4, max_seq_len=32)
        # the descriptor grammar is frozen: runs/pages/seq/chunk lanes
        # only — no prefix-cache material may ever leak into it
        assert re.fullmatch(r"r\d+_p\d+x\d+_s\d+_q\d+",
                            geometry.descriptor), geometry.descriptor
        cache_dir = str(tmp_path / "ec")
        cold = DecodeEngine(_tiny_task(), geometry=geometry,
                            policy=Policy.fp32(), auto_step=False,
                            exec_cache=cache_dir)
        cold.close(timeout=2.0)
        with compile_events() as events:
            warm = DecodeEngine(_tiny_task(), geometry=geometry,
                                policy=Policy.fp32(), auto_step=False,
                                exec_cache=cache_dir,
                                prefix_cache=PrefixCacheConfig())
            warm.close(timeout=2.0)
        assert events == [], (
            f"prefix caching forked the executable key: {events}")


# --- THE acceptance criterion ------------------------------------------------

_WARM_START_CHILD = """
import json, os, sys
sys.path.insert(0, os.getcwd())  # repo root (the test sets cwd)
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from perceiver_tpu.tasks import MaskedLanguageModelTask
from perceiver_tpu.serving import ServingEngine, materialize

task = MaskedLanguageModelTask(
    vocab_size=110, max_seq_len=32, num_latents=4,
    num_latent_channels=8, num_encoder_layers=1,
    num_encoder_self_attention_layers_per_block=1,
    num_encoder_cross_attention_heads=1,
    num_encoder_self_attention_heads=1,
    num_decoder_cross_attention_heads=1, loss_impl="dense")
engine = ServingEngine(task, batch_buckets=(1, 2),
                       seq_buckets=(16, 32), warmup=False,
                       exec_cache=sys.argv[1])
from perceiver_tpu.cache import register_compile_listener
events = []
register_compile_listener(events.append)
engine.warmup()
res = engine.dispatch({
    "input_ids": np.full((1, 10), 5, np.int32),
    "pad_mask": np.zeros((1, 10), bool)})
out = materialize(res, engine.graph)
m = engine.metrics
print(json.dumps({
    "compile_events": events,
    "engine_compiles": engine.compile_count,
    "buckets": sorted([b, s] for (b, s) in engine.compiled_buckets),
    "hits": m.get("serving_exec_cache_hits_total").value,
    "misses": m.get("serving_exec_cache_misses_total").value,
    "bytes_read": m.get("serving_exec_cache_bytes_total").value_of(
        direction="read"),
    "out0": np.asarray(out["filled_ids"]).tolist(),
}))
"""


def _run_warm_start_child(script_path, cache_dir):
    r = subprocess.run(
        [sys.executable, str(script_path), str(cache_dir)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_warm_start_full_grid_zero_compiles_across_processes(tmp_path):
    """Acceptance: a fresh process against a pre-populated cache warms
    the FULL bucket grid with zero XLA compiles (jax.monitoring), all
    buckets present, and bitwise-identical outputs."""
    script = tmp_path / "warm_child.py"
    script.write_text(_WARM_START_CHILD)
    cache_dir = tmp_path / "cache"

    cold = _run_warm_start_child(script, cache_dir)
    assert cold["misses"] == 4 and cold["engine_compiles"] == 4
    assert cold["compile_events"], "cold warmup must really compile"

    warm = _run_warm_start_child(script, cache_dir)
    assert warm["compile_events"] == [], (
        "warm-start warmup over the full bucket grid must perform "
        f"ZERO XLA compiles, saw {warm['compile_events']}")
    assert warm["engine_compiles"] == 0
    assert warm["hits"] == 4 and warm["misses"] == 0
    assert warm["bytes_read"] > 0
    assert warm["buckets"] == [[1, 16], [1, 32], [2, 16], [2, 32]]
    assert warm["out0"] == cold["out0"], \
        "deserialized executables must reproduce compiled outputs"
