"""Self-verification of the static-analysis subsystem (ISSUE 1).

Every graph pass must demonstrably FAIL on a seeded violation — a
gate that cannot catch its target defect is worse than no gate,
because it certifies trees it never checked. Each pass therefore gets
a tiny synthetic module that violates it (fp32 dot, host callback,
un-donated state, drifting compile key), a clean twin, and an
allowlist round-trip where applicable; the lint rules get seeded
source snippets. The headline-config regression pins
``bf16_flop_fraction == 1.0`` on the B=512/C=64 headline target, and
the slow full sweep runs what ``scripts/check.py --all`` gates at
merge.
"""

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from perceiver_tpu.analysis import (
    CANONICAL_TARGETS,
    DtypeAllow,
    PACKED_SERVING_TARGETS,
    SERVING_TARGETS,
    StepTarget,
    TransferAllow,
    cache_key_stability,
    donation_check,
    dtype_policy,
    hbm_budget,
    hlo,
    lint_source,
    load_hbm_budgets,
    lower_target,
    recompile_budget,
    run_graph_checks,
    transfer_guard,
    write_hbm_budgets,
)


def _lower_text(fn, *args):
    return fn.lower(*args).as_text()


# --- dtype_policy -----------------------------------------------------------


def _fp32_dot_text():
    @jax.jit
    def f(a, b):
        return a @ b

    x = jnp.ones((16, 32), jnp.float32)
    return _lower_text(f, x, x.T)


def test_dtype_policy_fails_on_fp32_dot():
    violations, summary = dtype_policy(_fp32_dot_text(), where="seeded")
    assert violations, "fp32 dot_general must violate dtype_policy"
    assert "f32" in violations[0].message
    assert summary["bf16_flop_fraction"] == 0.0


def test_dtype_policy_passes_bf16_dot():
    @jax.jit
    def f(a, b):
        return a @ b

    x = jnp.ones((16, 32), jnp.bfloat16)
    violations, summary = dtype_policy(_lower_text(f, x, x.T),
                                       where="clean",
                                       require_full_bf16=True)
    assert not violations
    assert summary["bf16_flop_fraction"] == 1.0


def test_dtype_policy_allowlist_consumes_budget():
    allow = (DtypeAllow(dtype="f32", max_count=1,
                        reason="seeded test exception"),)
    violations, _ = dtype_policy(_fp32_dot_text(), where="seeded",
                                 allowlist=allow)
    assert not violations
    # budget of 1 cannot cover two fp32 dots
    @jax.jit
    def g(a, b):
        return (a @ b) @ (a @ b).T

    x = jnp.ones((8, 8), jnp.float32)
    violations, _ = dtype_policy(_lower_text(g, x, x), where="seeded",
                                 allowlist=allow)
    assert violations


def test_dtype_policy_headline_requirement():
    violations, _ = dtype_policy(
        _fp32_dot_text(), where="seeded",
        allowlist=(DtypeAllow(dtype="f32", max_count=8,
                              reason="mask the per-dot findings"),),
        require_full_bf16=True)
    assert any("bf16_flop_fraction" in v.message for v in violations)


# --- transfer_guard ---------------------------------------------------------


def _callback_text():
    @jax.jit
    def f(x):
        jax.debug.print("x sum {s}", s=x.sum())
        return x * 2

    return _lower_text(f, jnp.ones((4,)))


def test_transfer_guard_fails_on_host_callback():
    violations = transfer_guard(_callback_text(), where="seeded")
    assert violations
    assert "callback" in violations[0].message


def test_transfer_guard_allowlist():
    text = _callback_text()
    markers = hlo.count_host_markers(text)
    assert markers, "seeded callback must be visible to the walker"
    allow = tuple(TransferAllow(marker=m, max_count=n,
                                reason="seeded test exception")
                  for m, n in markers.items())
    assert not transfer_guard(text, where="seeded", allowlist=allow)


def test_transfer_guard_passes_clean_module():
    @jax.jit
    def f(x):
        return x * 2

    assert not transfer_guard(_lower_text(f, jnp.ones((4,))),
                              where="clean")


# --- donation_check ---------------------------------------------------------


def _state_step(donate):
    dec = (partial(jax.jit, donate_argnums=(0,)) if donate else jax.jit)

    @dec
    def step(state, batch):
        new = jax.tree.map(lambda s: s + batch.sum(), state)
        return new

    state = {"w": jnp.ones((8, 8)), "b": jnp.ones((8,))}
    return _lower_text(step, state, jnp.ones((4,)))


def test_donation_check_fails_on_undonated_state():
    violations = donation_check(_state_step(donate=False),
                                where="seeded", expected_donated=2)
    assert violations
    assert "0/2" in violations[0].message


def test_donation_check_passes_donated_state():
    assert not donation_check(_state_step(donate=True), where="clean",
                              expected_donated=2)


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donation_check_fails_on_shape_drifted_state():
    # donated but unaliasable: the output state shape differs from the
    # input, so lowering cannot alias — exactly what forgetting to
    # keep state shapes stable across the step looks like
    @partial(jax.jit, donate_argnums=(0,))
    def step(state):
        return {"w": state["w"][:4]}

    text = _lower_text(step, {"w": jnp.ones((8, 8))})
    assert donation_check(text, where="seeded", expected_donated=1)


# --- recompile_budget -------------------------------------------------------


def _tiny_mlm():
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    return MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=16, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")


def _tiny_batch(batch=2, seq=16, vocab=110):
    import numpy as np

    rng = np.random.default_rng(0)
    return {
        "input_ids": jnp.asarray(
            rng.integers(3, vocab, (batch, seq)), jnp.int32),
        "pad_mask": jnp.zeros((batch, seq), bool),
    }


def test_recompile_budget_passes_stable_target():
    target = StepTarget(name="tiny_stable",
                        build=lambda: (_tiny_mlm(), _tiny_batch()))
    violations, fp = recompile_budget(target)
    assert not violations
    assert fp


def test_recompile_budget_fails_on_drifting_shapes():
    counter = itertools.count(2)
    target = StepTarget(
        name="tiny_drift",
        build=lambda: (_tiny_mlm(), _tiny_batch(batch=next(counter))))
    violations, _ = recompile_budget(target)
    assert any("different step signatures" in v.message
               for v in violations)


# --- cache_key_stability ----------------------------------------------------


def _fake_lowered(text, cached=False, name="seeded"):
    from perceiver_tpu.analysis.targets import LoweredStep

    target = StepTarget(name=name, build=lambda: (None, None))
    return LoweredStep(target=target, text=text, expected_donated=0,
                       task_hash=None, cached=cached)


def test_cache_key_stability_fails_on_body_drift():
    """Same @main signature, different body — the leakage class
    recompile_budget cannot see but that zeroes the exec-cache hit
    rate (a trace-time timestamp/RNG constant in the graph)."""
    sig = ("func.func public @main(%arg0: tensor<2x2xf32>) -> "
           "tensor<2x2xf32> {\n")
    a = _fake_lowered(sig + "  const 0.123\n}\n")
    b = _fake_lowered(sig + "  const 0.456\n}\n")
    target = a.target
    rc, _ = recompile_budget(target, first=a, second=b)
    assert not rc, "signature matches — recompile_budget is blind here"
    violations, _ = cache_key_stability(target, first=a, second=b)
    assert violations
    assert "zeroes the executable-cache hit rate" in \
        violations[0].message


def test_cache_key_stability_reports_cross_process_span():
    a = _fake_lowered("module { A }", cached=True)
    b = _fake_lowered("module { B }")
    violations, _ = cache_key_stability(a.target, first=a, second=b)
    assert "previous process" in violations[0].message


def test_cache_key_stability_passes_stable_target():
    target = StepTarget(name="tiny_stable",
                        build=lambda: (_tiny_mlm(), _tiny_batch()))
    violations, text_hash = cache_key_stability(target)
    assert not violations
    assert text_hash


def test_cache_key_stability_across_lowering_cache(tmp_path):
    """lower_target round-trips through a persistent lowering record
    and the stability pass compares record-vs-fresh cleanly — the
    warm check.py --graph path."""
    from perceiver_tpu.cache import ExecutableCache

    cache = ExecutableCache(str(tmp_path / "ec"))
    target = StepTarget(name="tiny_stable_cached",
                        build=lambda: (_tiny_mlm(), _tiny_batch()))
    fresh = lower_target(target, cache=cache)
    assert not fresh.cached and cache.stats.stores == 1
    recalled = lower_target(target, cache=cache)
    assert recalled.cached and recalled.text == fresh.text
    assert recalled.bytes_accessed == fresh.bytes_accessed
    assert recalled.expected_donated == fresh.expected_donated
    violations, _ = cache_key_stability(target, first=recalled)
    assert not violations
    rc, _ = recompile_budget(target, first=recalled)
    assert not rc


# --- hbm_budget -------------------------------------------------------------


def test_hbm_budget_fails_on_seeded_regression():
    # a step whose cost-analysis bytes exceed the pinned budget — the
    # exact shape of a re-materialized residual or fp32 copy landing
    budgets = {"seeded": {"budget_bytes": 1_000_000,
                          "pinned_bytes": 952_381, "pinned": "test"}}
    violations = hbm_budget(2_000_000.0, where="seeded", budgets=budgets)
    assert violations
    assert "exceeds the pinned budget" in violations[0].message
    assert "+110.0%" in violations[0].message


def test_hbm_budget_passes_within_budget():
    budgets = {"seeded": {"budget_bytes": 1_000_000,
                          "pinned_bytes": 952_381, "pinned": "test"}}
    assert not hbm_budget(999_999.0, where="seeded", budgets=budgets)


def test_hbm_budget_fails_on_missing_budget():
    # an unbudgeted canonical target must FAIL, not silently opt out
    # of the traffic gate (same for a deleted/unreadable manifest,
    # which loads as an empty dict)
    violations = hbm_budget(1.0, where="new_target", budgets={})
    assert violations
    assert "no byte budget pinned" in violations[0].message


def test_hbm_budget_fails_without_cost_analysis():
    # a backend exposing no lowering-time cost analysis cannot certify
    # the budget — that must be a loud violation, not a silent pass
    budgets = {"seeded": {"budget_bytes": 1_000_000,
                          "pinned_bytes": 952_381, "pinned": "test"}}
    violations = hbm_budget(None, where="seeded", budgets=budgets)
    assert violations
    assert "no cost analysis" in violations[0].message


def test_hbm_budget_manifest_roundtrip(tmp_path):
    path = str(tmp_path / "budgets.json")
    manifest = write_hbm_budgets({"a": 100.0, "b": 200.0}, path=path,
                                 note="test")
    loaded = load_hbm_budgets(path)
    assert loaded == manifest["targets"]
    assert loaded["a"]["pinned_bytes"] == 100
    assert loaded["a"]["budget_bytes"] == 105  # 5% headroom
    # the checked-in manifest budgets every canonical target
    pinned = load_hbm_budgets()
    assert {t.name for t in CANONICAL_TARGETS} <= set(pinned)


def test_hbm_budget_write_keeps_existing_pins(tmp_path):
    """The --pin-missing-hbm merge path: existing entries are copied
    through byte-identically, only the new target gets pinned — adding
    a serving target must never silently re-baseline the train pins."""
    path = str(tmp_path / "budgets.json")
    write_hbm_budgets({"old": 100.0}, path=path, note="r6")
    before = load_hbm_budgets(path)
    write_hbm_budgets({"new": 50.0}, path=path, note="r7", keep=before)
    after = load_hbm_budgets(path)
    assert after["old"] == before["old"]  # untouched, note still "r6"
    assert after["old"]["pinned"] == "r6"
    assert after["new"] == {"budget_bytes": 52, "pinned_bytes": 50,
                            "pinned": "r7"}


def test_hbm_budget_seeded_violation_through_runner(
        tmp_path, monkeypatch, lowered_target_cache):
    """End-to-end: shrink the checked-in budget for a real canonical
    target and the full runner must report a violation — proof the
    merge gate actually trips on a traffic regression."""
    import json as _json

    import perceiver_tpu.analysis.passes as passes_mod

    with open(passes_mod._HBM_MANIFEST) as f:
        manifest = _json.load(f)
    name = CANONICAL_TARGETS[0].name
    manifest["targets"][name]["budget_bytes"] = 1  # nothing fits in 1 B
    path = str(tmp_path / "budgets.json")
    with open(path, "w") as f:
        _json.dump(manifest, f)
    monkeypatch.setattr(passes_mod, "_HBM_MANIFEST", path)
    # recompile=False reads each lowering once — safe to serve from
    # the session cache (the recompile-closure pass is not in play)
    monkeypatch.setattr(passes_mod, "lower_target", lowered_target_cache)
    report = run_graph_checks([CANONICAL_TARGETS[0]], recompile=False)
    assert not report.ok
    assert any(v.check == "hbm_budget" and v.where == name
               for v in report.violations)


def test_headline_hbm_bytes_pinned_below_baseline():
    """The round-6 traffic work's acceptance number, pinned forever:
    the headline B=512/C=64 MLM step's cost-analysis bytes must stay
    ≥25% below the pre-PR baseline of 133.0 GB (the bf16 scan carries
    + attention recompute + packed masked-position decode win)."""
    pinned = load_hbm_budgets()["mlm_b512_c64_packed"]
    assert pinned["budget_bytes"] < 0.75 * 133.0e9


# --- serving targets (ISSUE 3) ----------------------------------------------


def _tiny_serve_target(name="tiny_serve", batch=2, seq=16):
    def build():
        import numpy as np

        task = _tiny_mlm()
        rng = np.random.default_rng(0)
        data = {
            "input_ids": jnp.asarray(
                rng.integers(3, 110, (batch, seq)), jnp.int32),
            "pad_mask": jnp.zeros((batch, seq), bool),
        }
        return task, data

    return StepTarget(name=name, build=build, kind="serve")


def test_serving_targets_registered_and_budgeted():
    """Every serving target rides CANONICAL_TARGETS (so check.py --all
    gates it) and has a pinned hbm budget — an unbudgeted serve graph
    would silently opt out of the traffic gate."""
    names = {t.name for t in SERVING_TARGETS}
    assert names == {"serve_mlm_b32_s512", "serve_text_clf_b32_s512",
                     "serve_img_clf_b32", "serve_seg_512x512_b1"}
    assert all(t.kind == "serve" for t in SERVING_TARGETS)
    packed_names = {t.name for t in PACKED_SERVING_TARGETS}
    assert packed_names == {"serve_mlm_packed_t8192_r32",
                            "serve_text_clf_packed_t8192_r32"}
    assert all(t.kind == "packed_serve" for t in PACKED_SERVING_TARGETS)
    names |= packed_names
    assert names <= {t.name for t in CANONICAL_TARGETS}
    assert names <= set(load_hbm_budgets())
    # the fast tier keeps all serve targets (forward-only = cheap)
    from perceiver_tpu.analysis import FAST_TARGETS
    assert names <= {t.name for t in FAST_TARGETS}


def test_serve_step_donation_contract_lowered():
    """The MLM serve graph donates exactly its request buffers, and
    lowering actually aliases them onto outputs (filled_ids/is_masked
    share shape+dtype by construction) — donation_check must pass with
    the serve step's own expected count."""
    from perceiver_tpu.analysis.targets import lower_target

    lowered = lower_target(_tiny_serve_target())
    assert lowered.expected_donated == 2  # input_ids + pad_mask
    assert not donation_check(lowered.text, where="tiny_serve",
                              expected_donated=lowered.expected_donated)
    # and the graph is callback-free + all-bf16 on the dot FLOPs
    assert not transfer_guard(lowered.text, where="tiny_serve")
    violations, summary = dtype_policy(lowered.text, where="tiny_serve",
                                       require_full_bf16=True)
    assert not violations
    assert summary["bf16_flop_fraction"] == 1.0


def test_serve_target_recompile_closure():
    """Independent rebuilds of a serve target lower byte-identically —
    the property that keeps the engine's AOT bucket set closed (any
    drift would be a per-restart recompile on the chip)."""
    violations, fp = recompile_budget(_tiny_serve_target())
    assert not violations
    assert fp


def test_serve_headline_is_mlm_bf16():
    serve_mlm = next(t for t in SERVING_TARGETS
                     if t.name == "serve_mlm_b32_s512")
    assert serve_mlm.headline
    assert serve_mlm.transfer_allow == ()  # no callbacks in serve graphs


# --- packed serving targets (ISSUE 9) ---------------------------------------


def _tiny_packed_serve_target(name="tiny_packed_serve"):
    def build():
        import numpy as np

        task = _tiny_mlm()
        lens = np.asarray([9, 3, 16, 0], np.int32)
        offs = np.zeros(4, np.int32)
        offs[1:] = np.cumsum(lens)[:-1]
        rng = np.random.default_rng(0)
        ids = rng.integers(3, 110, (32,)).astype(np.int32)
        data = {
            "packed_ids": jnp.asarray(ids),
            "row_offsets": jnp.asarray(offs),
            "lengths": jnp.asarray(lens),
        }
        return task, data

    return StepTarget(name=name, build=build, kind="packed_serve")


def test_packed_serve_step_donation_contract_lowered():
    """The packed MLM graph donates exactly ``packed_ids`` (it aliases
    ``filled_ids`` — same (T,) int32), and nothing else: the sidecar
    int arrays are tiny and donating them buys no aliasing."""
    lowered = lower_target(_tiny_packed_serve_target())
    assert lowered.expected_donated == 1  # packed_ids only
    assert not donation_check(lowered.text, where="tiny_packed_serve",
                              expected_donated=lowered.expected_donated)
    assert not transfer_guard(lowered.text, where="tiny_packed_serve")


def test_packed_serve_target_recompile_closure():
    """Independent rebuilds of the packed serve target lower
    byte-identically — the engine's packed (tokens, rows) bucket set
    stays closed across restarts, same contract as the rect path."""
    violations, fp = recompile_budget(_tiny_packed_serve_target())
    assert not violations
    assert fp


def test_packed_hbm_budget_seeded_violation_through_runner(
        tmp_path, monkeypatch, lowered_target_cache):
    """Satellite 5: shrink the checked-in budget for the REGISTERED
    packed serve target and the full runner must trip hbm_budget —
    proof the packed bytes win is an enforced merge gate, not a
    one-time measurement."""
    import json as _json

    import perceiver_tpu.analysis.passes as passes_mod

    target = PACKED_SERVING_TARGETS[0]
    with open(passes_mod._HBM_MANIFEST) as f:
        manifest = _json.load(f)
    manifest["targets"][target.name]["budget_bytes"] = 1
    path = str(tmp_path / "budgets.json")
    with open(path, "w") as f:
        _json.dump(manifest, f)
    monkeypatch.setattr(passes_mod, "_HBM_MANIFEST", path)
    monkeypatch.setattr(passes_mod, "lower_target", lowered_target_cache)
    report = run_graph_checks([target], recompile=False)
    assert not report.ok
    assert any(v.check == "hbm_budget" and v.where == target.name
               for v in report.violations)


def test_packed_serve_bytes_pinned_below_padded_rect():
    """The ISSUE 9 acceptance number, pinned as a merge gate: the
    packed serve graphs' cost-analysis bytes at the canonical shapes
    (8192 tokens / 32 rows vs the b32_s512 rectangles — the same 32
    requests) stay ≥25% below the padded equivalents. Measured at pin
    time: MLM 47.1%, text-clf 41.5% of the rect bytes."""
    pinned = load_hbm_budgets()
    pairs = [("serve_mlm_packed_t8192_r32", "serve_mlm_b32_s512"),
             ("serve_text_clf_packed_t8192_r32",
              "serve_text_clf_b32_s512")]
    for packed_name, rect_name in pairs:
        packed_bytes = pinned[packed_name]["pinned_bytes"]
        rect_bytes = pinned[rect_name]["pinned_bytes"]
        assert packed_bytes <= 0.75 * rect_bytes, (
            f"{packed_name} pinned at {packed_bytes} bytes is not ≥25% "
            f"below {rect_name} ({rect_bytes})")


# --- decode targets (ISSUE 14) ----------------------------------------------


def _tiny_decode_target(name="tiny_decode"):
    def build():
        from perceiver_tpu.serving.decode import DecodeGeometry

        task = _tiny_mlm()
        # mixed phase: row 0 prefills a 3-token chunk, row 1 decodes
        return task, {
            "geometry": DecodeGeometry(max_streams=2, num_pages=5,
                                       page_size=4, max_seq_len=16,
                                       max_chunk=4),
            "tokens": jnp.asarray([[7, 9, 11, 0], [9, 0, 0, 0]],
                                  jnp.int32),
            "qlens": jnp.asarray([3, 1], jnp.int32),
        }

    return StepTarget(name=name, build=build, kind="decode")


def test_decode_targets_registered_and_budgeted():
    """All decode targets — mixed-phase and the speculative k=4 verify
    step — ride CANONICAL_TARGETS (check.py --all) and carry pinned hbm
    budgets; the sharded variants are additionally pinned in
    shard_budgets.json. An unbudgeted decode step would silently opt
    the O(1)-memory claim out of the merge gate."""
    from perceiver_tpu.analysis import DECODE_TARGETS, FAST_TARGETS
    from perceiver_tpu.analysis.shardcheck import load_shard_budgets

    names = {t.name for t in DECODE_TARGETS}
    assert names == {"decode_mixed_mlm_r8_p64x16_q8",
                     "decode_spec_mlm_r8_p64x16_q8_k4",
                     "decode_multitenant_mlm_r8_p64x16_q8"}
    assert all(t.kind == "decode" for t in DECODE_TARGETS)
    # the multi-tenant target must be a signature twin of the plain
    # mixed step: tenancy is host-side state, identical lowered graph
    twins = {t.name: t.signature_twin for t in DECODE_TARGETS}
    assert (twins["decode_multitenant_mlm_r8_p64x16_q8"]
            == "decode_mixed_mlm_r8_p64x16_q8")
    canonical = {t.name for t in CANONICAL_TARGETS}
    assert names <= canonical
    spmd_names = {"decode_mixed_mlm_spmd_r8_p48x16_q8_dp2_tp2",
                  "decode_spec_mlm_spmd_r8_p48x16_q8_k4_dp2_tp2",
                  "decode_multitenant_mlm_spmd_r8_p48x16_q8_dp2_tp2"}
    assert spmd_names <= canonical
    assert names | spmd_names <= set(load_hbm_budgets())
    shard = load_shard_budgets()
    for spmd in spmd_names:
        assert spmd in shard and shard[spmd]["collectives"]
    # the unsharded steps are forward-only and compile-cheap: fast
    # tier; the mesh variants pay an XLA compile, so --all/--graph only
    fast = {t.name for t in FAST_TARGETS}
    assert names <= fast
    assert not (spmd_names & fast)


def test_decode_step_donation_contract_lowered():
    """The decode step donates exactly its carry — KV pools, lengths,
    page tables (4 leaves at one encoder layer) — and lowering aliases
    every leaf onto an output: the step's HBM high-water mark is ONE
    copy of the paged cache, the property that makes token N cost the
    same as token 1."""
    lowered = lower_target(_tiny_decode_target())
    assert lowered.expected_donated == 4  # k1, v1, lengths, page_tables
    assert not donation_check(lowered.text, where="tiny_decode",
                              expected_donated=lowered.expected_donated)
    assert not transfer_guard(lowered.text, where="tiny_decode")


def test_decode_target_recompile_closure():
    """Independent rebuilds of the decode target lower byte-identically
    — the engine compiles ONE step per pool geometry and replays it for
    every token, so any signature drift would be a mid-stream
    recompile (exactly what the zero-compile bench gate forbids)."""
    violations, fp = recompile_budget(_tiny_decode_target())
    assert not violations
    assert fp


def test_decode_hbm_budget_seeded_violation_through_runner(
        tmp_path, monkeypatch, lowered_target_cache):
    """Shrink the checked-in budget for the REGISTERED decode target
    and the full runner must trip hbm_budget — the O(1)-memory pin is
    an enforced merge gate, not a one-time measurement."""
    import json as _json

    import perceiver_tpu.analysis.passes as passes_mod
    from perceiver_tpu.analysis import DECODE_TARGETS

    target = DECODE_TARGETS[0]
    with open(passes_mod._HBM_MANIFEST) as f:
        manifest = _json.load(f)
    manifest["targets"][target.name]["budget_bytes"] = 1
    path = str(tmp_path / "budgets.json")
    with open(path, "w") as f:
        _json.dump(manifest, f)
    monkeypatch.setattr(passes_mod, "_HBM_MANIFEST", path)
    monkeypatch.setattr(passes_mod, "lower_target", lowered_target_cache)
    report = run_graph_checks([target], recompile=False)
    assert not report.ok
    assert any(v.check == "hbm_budget" and v.where == target.name
               for v in report.violations)


# --- speculative decode targets (ISSUE 19) ----------------------------------


def _tiny_spec_decode_target(name="tiny_spec_decode", spec_k=2):
    def build():
        from perceiver_tpu.serving.decode import DecodeGeometry

        task = _tiny_mlm()
        # mixed phase: row 0 prefills a full chunk, row 1 verifies a
        # k+1-lane speculative window (feedback + 2 drafted tokens)
        return task, {
            "geometry": DecodeGeometry(max_streams=2, num_pages=5,
                                       page_size=4, max_seq_len=16,
                                       max_chunk=4, spec_k=spec_k),
            "tokens": jnp.asarray([[7, 9, 11, 13], [9, 5, 3, 0]],
                                  jnp.int32),
            "qlens": jnp.asarray([4, 3], jnp.int32),
        }

    return StepTarget(name=name, build=build, kind="decode")


def test_spec_decode_step_donation_contract_lowered():
    """The speculative verify step keeps the EXACT donation contract of
    the plain decode step: window tiling widens latents/logits (pure
    activations) but the carry is still one paged cache — k1, v1,
    lengths, page_tables all alias in place. A second cache copy here
    would double decode HBM for every speculative stream."""
    lowered = lower_target(_tiny_spec_decode_target())
    assert lowered.expected_donated == 4  # k1, v1, lengths, page_tables
    assert not donation_check(lowered.text, where="tiny_spec_decode",
                              expected_donated=lowered.expected_donated)
    assert not transfer_guard(lowered.text, where="tiny_spec_decode")


def test_spec_decode_target_recompile_closure():
    """Independent rebuilds of the speculative step lower
    byte-identically — the engine compiles ONE verify executable per
    (geometry, spec_k) descriptor at admission time, and any signature
    drift would be a mid-traffic recompile (the zero-compile bench
    gate's failure mode)."""
    violations, fp = recompile_budget(_tiny_spec_decode_target())
    assert not violations
    assert fp


def test_spec_decode_descriptor_distinct_from_plain():
    """spec_k widens the exec-cache key: the k>0 descriptor must never
    collide with the plain decode entry (a collision would serve the
    1-lane executable to verify rows), and k=0 must keep the exact
    legacy descriptor so existing pins/caches stay valid."""
    from perceiver_tpu.serving.decode import DecodeGeometry

    plain = DecodeGeometry(max_streams=2, num_pages=5, page_size=4,
                           max_seq_len=16, max_chunk=4)
    spec = DecodeGeometry(max_streams=2, num_pages=5, page_size=4,
                          max_seq_len=16, max_chunk=4, spec_k=2)
    assert spec.descriptor != plain.descriptor
    assert spec.descriptor.endswith("_k2")
    assert "_k" not in plain.descriptor


def test_spec_decode_hbm_budget_seeded_violation_through_runner(
        tmp_path, monkeypatch, lowered_target_cache):
    """Shrink the checked-in budget for the REGISTERED speculative
    target and the full runner must trip hbm_budget — the k=4 verify
    step's memory pin is an enforced merge gate, not a one-time
    measurement."""
    import json as _json

    import perceiver_tpu.analysis.passes as passes_mod
    from perceiver_tpu.analysis import DECODE_TARGETS

    target = next(t for t in DECODE_TARGETS
                  if t.name == "decode_spec_mlm_r8_p64x16_q8_k4")
    with open(passes_mod._HBM_MANIFEST) as f:
        manifest = _json.load(f)
    manifest["targets"][target.name]["budget_bytes"] = 1
    path = str(tmp_path / "budgets.json")
    with open(path, "w") as f:
        _json.dump(manifest, f)
    monkeypatch.setattr(passes_mod, "_HBM_MANIFEST", path)
    monkeypatch.setattr(passes_mod, "lower_target", lowered_target_cache)
    report = run_graph_checks([target], recompile=False)
    assert not report.ok
    assert any(v.check == "hbm_budget" and v.where == target.name
               for v in report.violations)


# --- lint rules -------------------------------------------------------------


_JIT_ITEM = """
import jax

@jax.jit
def f(x):
    return x.sum().item()
"""

_JIT_FLOAT = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def f(x, n):
    return float(x) + n
"""

_JIT_NUMPY = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.asarray(x) * 2
"""

_JIT_TIME_RNG = """
import jax
import time
import numpy as np

@jax.jit
def f(x):
    t = time.time()
    return x * np.random.normal() + t
"""

_JIT_CALL_FORM = """
import jax

def step(state):
    return state.item()

run = jax.jit(step, donate_argnums=0)
"""

_HOST_SIDE_CLEAN = """
import time
import numpy as np

def host_loop(x):
    t = time.time()
    return float(np.asarray(x).sum()) + t
"""

_SHAPE_ACCESS_CLEAN = """
import jax

@jax.jit
def f(x):
    return x * int(x.shape[0])
"""


def _checks(src, path="<memory>"):
    return [v.check for v in lint_source(src, path)]


def test_lint_flags_item_in_jit():
    assert "jit-host-sync" in _checks(_JIT_ITEM)


def test_lint_flags_float_of_traced_param():
    assert "jit-host-sync" in _checks(_JIT_FLOAT)


def test_lint_flags_numpy_in_jit():
    assert "jit-host-sync" in _checks(_JIT_NUMPY)


def test_lint_flags_time_and_np_random_in_jit():
    checks = _checks(_JIT_TIME_RNG)
    assert checks.count("jit-python-rng-time") == 2


def test_lint_follows_jit_call_form():
    # jax.jit(fn, ...) marks fn traced even without a decorator
    assert "jit-host-sync" in _checks(_JIT_CALL_FORM)


def test_lint_ignores_host_side_code():
    assert not _checks(_HOST_SIDE_CLEAN)


def test_lint_allows_static_shape_access():
    assert not _checks(_SHAPE_ACCESS_CLEAN)


def test_lint_ops_numpy_mix_scoped_to_ops():
    src = "import numpy as np\nimport jax.numpy as jnp\n"
    assert "ops-numpy-mix" in _checks(src, "perceiver_tpu/ops/new.py")
    assert not _checks(src, "perceiver_tpu/data/new.py")
    np_only = "import numpy as np\n"
    assert not _checks(np_only, "perceiver_tpu/ops/fourier2.py")


_IMPL_UNVALIDATED = """
import dataclasses
from typing import Optional

@dataclasses.dataclass(frozen=True)
class Config:
    dropout: float = 0.0
    attention_impl: Optional[str] = None

    def __post_init__(self):
        # the reverted tasks/base.py shape: a feature guard using a
        # positive membership test, but no domain validation
        if self.dropout > 0.0 and self.attention_impl in ("flash",):
            raise ValueError("no dropout for flash")
"""

def test_lint_catches_missing_impl_validation():
    # the exact pre-fix tasks/base.py shape (ADVICE r5): feature guard
    # present, domain validation absent — must be flagged
    assert "impl-field-validation" in _checks(_IMPL_UNVALIDATED)


def test_lint_accepts_not_in_domain_validation():
    src = _IMPL_UNVALIDATED.replace(
        'raise ValueError("no dropout for flash")',
        'raise ValueError("no dropout for flash")\n'
        '        if self.attention_impl not in (None, "einsum"):\n'
        '            raise ValueError("bad impl")')
    assert "impl-field-validation" not in _checks(src)


def test_lint_suppression_marker():
    src = _JIT_ITEM.replace(".item()", ".item()  # graphcheck: ignore")
    assert not _checks(src)


_ENGINE_SYNC = """
import numpy as np
import jax

def dispatch(self, arrays):
    out = self._exe[bucket](self._params, *arrays)
    depth = out["count"].item()
    host = np.asarray(out["filled_ids"])
    jax.block_until_ready(out)
    got = jax.device_get(out)
    return host.tolist()
"""

_ENGINE_CLEAN = """
import numpy as np

def _pad_to_bucket(self, arrays, bucket):
    out = np.full((4, 16), 0, dtype=np.int32)
    out[: arrays.shape[0]] = arrays
    return out

def dispatch(self, arrays):
    return self._exe[bucket](self._params, self._pad_to_bucket(arrays))
"""

_ENGINE_PATH = "perceiver_tpu/serving/engine.py"


def test_lint_serving_host_sync_seeded():
    """Every sync shape the rule exists for: .item, np.asarray,
    block_until_ready, device_get, .tolist — all flagged, only inside
    serving/engine.py."""
    checks = _checks(_ENGINE_SYNC, _ENGINE_PATH)
    assert checks.count("serving-host-sync") == 5
    # identical source anywhere else is not the engine's contract
    assert "serving-host-sync" not in _checks(_ENGINE_SYNC,
                                              "perceiver_tpu/serving/api.py")


def test_lint_serving_host_sync_allows_host_padding():
    """np.full padding of HOST request arrays is the engine's job and
    must not be flagged — only conversions that force a device sync."""
    assert not _checks(_ENGINE_CLEAN, _ENGINE_PATH)


def test_lint_serving_engine_file_is_clean():
    """The real engine honors its own rule (the gate would fail the
    merge otherwise, but pin it directly too)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rel = "perceiver_tpu/serving/engine.py"
    with open(os.path.join(root, rel)) as f:
        assert not lint_source(f.read(), rel), rel


def test_lint_clean_on_fixed_tree_files():
    # the files this PR fixed must stay clean under the rules that
    # flagged them (regression for the ADVICE r5 finding)
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("perceiver_tpu/tasks/base.py",
                "perceiver_tpu/models/perceiver.py"):
        with open(os.path.join(root, rel)) as f:
            assert not lint_source(f.read(), rel), rel


# --- silent-swallow ---------------------------------------------------------

_SWALLOW = """
def load(path):
    try:
        return open(path).read()
    except Exception:
        pass
"""

_SWALLOW_BARE = """
def load(path):
    try:
        return open(path).read()
    except:
        return None
"""


def test_lint_silent_swallow_seeded():
    """Both shapes the rule exists for: except Exception: pass, and a
    bare except (flagged regardless of body)."""
    assert "silent-swallow" in _checks(_SWALLOW)
    assert "silent-swallow" in _checks(_SWALLOW_BARE)
    ellipsis = _SWALLOW.replace("pass", "...")
    assert "silent-swallow" in _checks(ellipsis)
    tupled = _SWALLOW.replace("except Exception:",
                              "except (ValueError, Exception):")
    assert "silent-swallow" in _checks(tupled)


def test_lint_silent_swallow_reason_comment_clears():
    reasoned = _SWALLOW.replace(
        "pass", "pass  # probing an optional path — absence is fine")
    assert "silent-swallow" not in _checks(reasoned)
    on_except = _SWALLOW.replace(
        "except Exception:",
        "except Exception:  # noqa: BLE001 — fall through and rebuild")
    assert "silent-swallow" not in _checks(on_except)
    suppressed = _SWALLOW.replace("pass", "pass  # graphcheck: ignore")
    assert "silent-swallow" not in _checks(suppressed)


def test_lint_silent_swallow_ignores_narrow_and_visible():
    narrow = _SWALLOW.replace("except Exception:", "except OSError:")
    assert "silent-swallow" not in _checks(narrow)
    visible = _SWALLOW.replace("pass", "return None")
    assert "silent-swallow" not in _checks(visible)


# --- uncached-compile -------------------------------------------------------

_RAW_COMPILE_CHAINED = """
import jax

def build(fn, args):
    return jax.jit(fn).lower(*args).compile()
"""

_RAW_COMPILE_TWO_STEP = """
import jax

def build(fn, args):
    lowered = jax.jit(fn).lower(*args)
    return lowered.compile()
"""

_RE_COMPILE_CLEAN = """
import re

PATTERN = re.compile(r"x+")

def scan(text):
    return re.compile("y").findall(text) + PATTERN.findall(text)
"""


def test_lint_uncached_compile_flags_chained_form():
    assert "uncached-compile" in _checks(_RAW_COMPILE_CHAINED)


def test_lint_uncached_compile_flags_two_step_form():
    assert "uncached-compile" in _checks(_RAW_COMPILE_TWO_STEP)


def test_lint_uncached_compile_exempts_cache_package():
    assert "uncached-compile" not in _checks(
        _RAW_COMPILE_CHAINED, "perceiver_tpu/cache/exec_cache.py")


def test_lint_uncached_compile_ignores_re_compile():
    assert not _checks(_RE_COMPILE_CLEAN)


def test_lint_uncached_compile_suppression():
    suppressed = _RAW_COMPILE_CHAINED.replace(
        ".compile()",
        ".compile()  # graphcheck: ignore — seeded diagnostic")
    assert "uncached-compile" not in _checks(suppressed)


# --- router-blocking-io ------------------------------------------------------

_FLEET_BLOCKING_RECV = """
def read_reply(sock):
    return sock.recv(4096)
"""

_FLEET_BARE_CONNECT = """
import socket

def connect(host, port):
    return socket.create_connection((host, port))
"""

_FLEET_PATH = "perceiver_tpu/fleet/new_transport.py"


def test_lint_router_blocking_io_seeded():
    assert "router-blocking-io" in _checks(_FLEET_BLOCKING_RECV, _FLEET_PATH)
    assert "router-blocking-io" in _checks(_FLEET_BARE_CONNECT, _FLEET_PATH)
    accept = _FLEET_BLOCKING_RECV.replace("recv(4096)", "accept()")
    assert "router-blocking-io" in _checks(accept, _FLEET_PATH)


def test_lint_router_blocking_io_deadline_clears():
    deadlined = _FLEET_BLOCKING_RECV.replace(
        "return sock.recv", "sock.settimeout(10.0)\n    return sock.recv")
    assert not _checks(deadlined, _FLEET_PATH)
    timed = _FLEET_BARE_CONNECT.replace(
        "(host, port))", "(host, port), timeout=5.0)")
    assert not _checks(timed, _FLEET_PATH)


def test_lint_router_blocking_io_scoped_to_fleet():
    # the rule polices the fleet's hot paths only; blocking sockets
    # elsewhere are some other module's business
    assert not _checks(_FLEET_BLOCKING_RECV, "perceiver_tpu/data/io.py")
    assert not _checks(_FLEET_BARE_CONNECT, "scripts/tooling.py")


def test_lint_router_blocking_io_suppression():
    suppressed = _FLEET_BLOCKING_RECV.replace(
        "sock.recv(4096)",
        "sock.recv(4096)  # graphcheck: ignore — deadline set by caller")
    assert "router-blocking-io" not in _checks(suppressed, _FLEET_PATH)


def test_lint_fleet_package_is_clean():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fleet = os.path.join(root, "perceiver_tpu", "fleet")
    for name in sorted(os.listdir(fleet)):
        if not name.endswith(".py"):
            continue
        rel = f"perceiver_tpu/fleet/{name}"
        with open(os.path.join(fleet, name)) as f:
            assert not lint_source(f.read(), rel), rel


# --- headline regression + full sweep ---------------------------------------


def test_headline_config_bf16_flop_fraction_is_one(lowered_target_cache):
    """B=512/C=64 packed MLM (the headline target): every dot FLOP in
    the lowered train step runs on bf16 operands — the round-4 audit's
    9.1%-at-fp32 regression, pinned forever."""
    target = CANONICAL_TARGETS[0]
    assert target.name == "mlm_b512_c64_packed" and target.headline
    lowered = lowered_target_cache(target)
    summary = hlo.dot_flop_summary(list(hlo.iter_dots(lowered.text)))
    assert summary["bf16_flop_fraction"] == 1.0
    violations, _ = dtype_policy(lowered.text, where=target.name,
                                 require_full_bf16=True)
    assert not violations
    # and its donation + transfer contracts hold
    assert not donation_check(lowered.text, where=target.name,
                              expected_donated=lowered.expected_donated)
    assert not transfer_guard(lowered.text, where=target.name,
                              allowlist=target.transfer_allow)


def test_full_graph_sweep_is_clean(monkeypatch, lowered_target_cache):
    """What ``scripts/check.py --graph`` gates at merge: every
    canonical target, all five passes including the double-lowering
    recompile check. Slow-marked (see conftest). The FIRST lowering
    per target comes from the session cache; the recompile pass's
    second lowering stays a real rebuild, so the closure check
    compares cache-vs-fresh — the cross-rebuild property it exists
    for — without paying every lowering twice."""
    import perceiver_tpu.analysis.passes as passes_mod
    from perceiver_tpu.analysis.targets import lower_target as real_lower

    first_seen = set()

    def once_cached(target, cache=None, **kwargs):
        if target.name not in first_seen:
            first_seen.add(target.name)
            return lowered_target_cache(target)
        return real_lower(target, **kwargs)

    monkeypatch.setattr(passes_mod, "lower_target", once_cached)
    report = run_graph_checks(CANONICAL_TARGETS, recompile=True)
    assert report.ok, report.format()
    assert set(report.checks_run) == {"dtype_policy", "transfer_guard",
                                      "donation_check",
                                      "recompile_budget", "hbm_budget",
                                      "cache_key_stability",
                                      "collective_budget",
                                      "replication_check",
                                      "per_shard_hbm_budget"}


def test_check_cli_exec_cache_second_run_warm():
    """``check.py --graph --fast --exec-cache DIR`` twice: the second
    run reuses every lowering record (misses=0), performs zero XLA
    compiles, and is measurably faster. Tier-1 — this is the CI face
    of the persistent-cache satellite."""
    import os
    import re
    import subprocess
    import sys
    import tempfile
    import time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as tmp:
        cmd = [sys.executable,
               os.path.join(root, "scripts", "check.py"),
               "--graph", "--fast", "--exec-cache",
               os.path.join(tmp, "ec")]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        t0 = time.perf_counter()
        r1 = subprocess.run(cmd, env=env, capture_output=True,
                            text=True, timeout=600)
        cold_s = time.perf_counter() - t0
        assert r1.returncode == 0, f"\n{r1.stdout}\n{r1.stderr}"
        t0 = time.perf_counter()
        r2 = subprocess.run(cmd, env=env, capture_output=True,
                            text=True, timeout=600)
        warm_s = time.perf_counter() - t0
        assert r2.returncode == 0, f"\n{r2.stdout}\n{r2.stderr}"

        def stats(stderr):
            m = re.search(r"exec-cache: hits=(\d+) misses=(\d+) "
                          r"stores=(\d+) xla_compiles=(\d+)", stderr)
            assert m, stderr
            return tuple(int(g) for g in m.groups())

        from perceiver_tpu.analysis import FAST_TARGETS

        n = len(FAST_TARGETS)
        assert stats(r1.stderr) == (0, n, n, stats(r1.stderr)[3])
        hits, misses, stores, compiles = stats(r2.stderr)
        assert (hits, misses, stores) == (n, 0, 0)
        assert compiles == 0, "warm check run must not compile"
        assert warm_s < 0.6 * cold_s, (
            f"warm run {warm_s:.1f}s not measurably faster than cold "
            f"{cold_s:.1f}s")


def test_full_lint_sweep_is_clean():
    """What ``scripts/check.py --lint`` gates at merge. Slow-marked."""
    import os

    from perceiver_tpu.analysis import default_lint_paths, lint_paths

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = lint_paths(default_lint_paths(root))
    assert report.ok, report.format()


# --- metrics-conventions -----------------------------------------------------

_METRIC_BAD_PREFIX = """
def build(registry):
    return registry.counter("request_count_total", "requests")
"""

_METRIC_COUNTER_NO_TOTAL = """
def build(registry):
    return registry.counter("serving_requests", "requests")
"""

_METRIC_GAUGE_WITH_TOTAL = """
def build(registry):
    return registry.gauge("fleet_size_total", "replicas")
"""

_METRIC_HISTOGRAM_CAMEL = """
def build(registry):
    return registry.histogram("serving_batchSize", "rows per call")
"""

_METRIC_CLEAN = """
def build(registry):
    registry.counter("serving_requests_total", "requests")
    registry.gauge("fleet_size", "replicas")
    registry.histogram("training_step_seconds", "step walltime")
"""


def test_lint_metrics_conventions_seeded():
    assert "metrics-conventions" in _checks(_METRIC_BAD_PREFIX)
    assert "metrics-conventions" in _checks(_METRIC_COUNTER_NO_TOTAL)
    assert "metrics-conventions" in _checks(_METRIC_GAUGE_WITH_TOTAL)
    assert "metrics-conventions" in _checks(_METRIC_HISTOGRAM_CAMEL)


def test_lint_metrics_conventions_clean_and_non_literal():
    assert "metrics-conventions" not in _checks(_METRIC_CLEAN)
    # computed names are out of scope for an AST pass
    computed = _METRIC_CLEAN.replace(
        '"serving_requests_total"', 'f"serving_{kind}_total"')
    assert "metrics-conventions" not in _checks(computed)
    # unrelated .counter() attribute calls with non-string args
    assert "metrics-conventions" not in _checks(
        "def f(x):\n    return x.counter(3)\n")


def test_lint_metrics_conventions_suppression_marker():
    suppressed = _METRIC_COUNTER_NO_TOTAL.replace(
        '"requests")', '"requests")  # graphcheck: ignore — legacy name')
    assert "metrics-conventions" not in _checks(suppressed)


# --- kv-alias (ISSUE 18: CoW discipline on the paged arena) ------------------

_KV_WRITE = """
def stash(kpool, page, slot, x):
    return kpool.at[page, slot].set(x)
"""

_KV_ADD = """
def accumulate(vpool, page, x):
    return vpool.at[page].add(x)
"""

_KV_CLEAN_DICT = """
def remember(seen, page):
    seen.add(page)
    cfg = {}
    cfg.setdefault("at", []).append(page)
"""


def test_lint_kv_alias_seeded():
    """A functional page write anywhere in serving/ outside the two
    CoW-aware modules bypasses ensure_private_page and corrupts every
    stream aliasing the page."""
    path = "perceiver_tpu/serving/other.py"
    assert "kv-alias" in _checks(_KV_WRITE, path)
    assert "kv-alias" in _checks(_KV_ADD, path)


def test_lint_kv_alias_exempt_modules_and_scope():
    # the two modules that uphold the CoW discipline are exempt
    assert "kv-alias" not in _checks(
        _KV_WRITE, "perceiver_tpu/serving/decode.py")
    assert "kv-alias" not in _checks(
        _KV_WRITE, "perceiver_tpu/serving/prefix_cache.py")
    # the rule is serving-scoped: model/ops code writes arrays freely
    assert "kv-alias" not in _checks(
        _KV_WRITE, "perceiver_tpu/ops/attention.py")
    # ordinary .add/.set calls without the .at[...] shape never trip
    assert "kv-alias" not in _checks(
        _KV_CLEAN_DICT, "perceiver_tpu/serving/other.py")


def test_lint_kv_alias_suppression_marker():
    suppressed = _KV_WRITE.replace(
        ".set(x)",
        ".set(x)  # graphcheck: ignore — scratch buffer, not the arena")
    assert "kv-alias" not in _checks(
        suppressed, "perceiver_tpu/serving/other.py")


# --- tenant-label-discipline (ISSUE 20: multi-tenant observability) ----------

_TENANT_LABELS_BARE = """
def record(counter):
    counter.labels(reason="tenant_quota").inc()
"""

_TENANT_EMIT_BARE = """
def record(log, stream_id):
    log.emit("stream_open", stream=stream_id)
"""

_TENANT_CLEAN = """
def record(counter, log, tenant, stream_id):
    counter.labels(tenant=tenant, reason="tenant_quota").inc()
    log.emit("stream_open", stream=stream_id, tenant=tenant)
    emit("tenant_shed", tenant=tenant, reason="tenant_quota")
"""


def test_lint_tenant_label_discipline_seeded():
    """An unlabeled series in a multi-tenant plane merges all tenants
    — noisy-neighbor starvation becomes invisible exactly when it
    matters. Both forms are in scope: metric .labels(...) sites and
    string-literal event emits (bare or attribute call)."""
    for path in ("perceiver_tpu/fleet/router.py",
                 "perceiver_tpu/serving/decode.py",
                 "perceiver_tpu/serving/batcher.py"):
        assert "tenant-label-discipline" in _checks(
            _TENANT_LABELS_BARE, path), path
        assert "tenant-label-discipline" in _checks(
            _TENANT_EMIT_BARE, path), path
    # bare emit(...) calls (module-level helper import) also count
    bare = 'def f(s):\n    emit("stream_close", stream=s)\n'
    assert "tenant-label-discipline" in _checks(
        bare, "perceiver_tpu/fleet/supervisor.py")


def test_lint_tenant_label_discipline_clean_and_scope():
    # a tenant= keyword on the call satisfies the rule
    assert "tenant-label-discipline" not in _checks(
        _TENANT_CLEAN, "perceiver_tpu/fleet/router.py")
    # scoped to the multi-tenant planes: the same sites are fine in
    # the single-tenant serving engine or the training loop
    assert "tenant-label-discipline" not in _checks(
        _TENANT_LABELS_BARE, "perceiver_tpu/serving/engine.py")
    assert "tenant-label-discipline" not in _checks(
        _TENANT_EMIT_BARE, "perceiver_tpu/training/loop.py")
    # computed event types are out of scope for an AST pass
    computed = _TENANT_EMIT_BARE.replace('"stream_open"', 'etype')
    assert "tenant-label-discipline" not in _checks(
        computed, "perceiver_tpu/fleet/router.py")


def test_lint_tenant_label_discipline_suppression_marker():
    suppressed = _TENANT_LABELS_BARE.replace(
        ".inc()",
        ".inc()  # graphcheck: ignore — aggregate series; tenant split"
        " is fleet_tenant_requests_total")
    assert "tenant-label-discipline" not in _checks(
        suppressed, "perceiver_tpu/fleet/router.py")
