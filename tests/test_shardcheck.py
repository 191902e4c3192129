"""Self-verification of shardcheck (ISSUE 10).

Same philosophy as test_graphcheck.py: every sharding-aware pass must
demonstrably FAIL on a seeded violation, because a gate that cannot
catch its target defect certifies trees it never checked. The
collective walker is exercised on synthetic optimized-HLO lines in
every replica-group syntax XLA prints (explicit, iota, iota+transpose,
source_target_pairs); the budget/replication/per-shard passes each get
a violating input, a clean twin, and — where applicable — an
allowlist round-trip. The slow end-to-end test lowers+compiles a tiny
dp2×tp2 MLM step and drives it through ``run_graph_checks`` against a
manifest pinned from its own measurement (clean) and an empty one
(fails), proving the wiring, not just the passes.
"""

import json

import pytest

from perceiver_tpu.analysis import (
    CANONICAL_TARGETS,
    FAST_TARGETS,
    ReplicationAllow,
    SHARDED_TARGETS,
    StepTarget,
    collective_budget,
    collective_inventory,
    hlo,
    lint_source,
    load_shard_budgets,
    lower_target,
    per_shard_hbm_budget,
    replication_check,
    run_graph_checks,
    run_shard_passes,
    write_shard_budgets,
)
from perceiver_tpu.analysis.shardcheck import DEFAULT_FLOOR_BYTES
from perceiver_tpu.analysis.targets import DP2_TP2, MeshSpec

# --- synthetic optimized HLO: one op per replica-group syntax ---------------
#
# mesh (2,2) = (data, model), iota device order [[0,1],[2,3]]:
#   data-axis groups  {0,2},{1,3}   model-axis groups {0,1},{2,3}

_HLO = """\
HloModule jit_train_step

ENTRY %main.42 {
  %all-reduce.1 = f32[256,256]{1,0} all-reduce(f32[256,256]{1,0} %x), channel_id=1, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  %all-gather.2 = bf16[64,128]{1,0} all-gather(bf16[64,64]{1,0} %y), channel_id=2, replica_groups=[2,2]<=[4], dimensions={1}
  %collective-permute.3 = f32[32]{0} collective-permute(f32[32]{0} %z), channel_id=3, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %all-reduce.4 = f32[8]{0} all-reduce(f32[8]{0} %w), channel_id=4, replica_groups={{0},{1},{2},{3}}, to_apply=%add
  %all-reduce-done.5 = f32[8]{0} all-reduce-done(f32[8]{0} %w2)
}
"""

_AR_BYTES = 256 * 256 * 4          # data axis
_AG_BYTES = 64 * 128 * 2           # model axis (result shape)
_CP_BYTES = 32 * 4                 # model axis (permute ring)


def _budget_entry(collectives, per_shard, mesh="data2_model2",
                  headroom=1.10):
    return {
        "mesh": mesh,
        "collectives": {
            axis: {"pinned_bytes": b, "budget_bytes": int(b * headroom)}
            for axis, b in collectives.items()},
        "per_shard": {"pinned_bytes": per_shard,
                      "budget_bytes": int(per_shard * headroom)},
    }


# --- collective walker ------------------------------------------------------


def test_iter_collectives_parses_every_group_syntax():
    cols = list(hlo.iter_collectives(_HLO))
    # the -done line must NOT parse as a collective
    assert [c["op"] for c in cols] == [
        "all-reduce", "all-gather", "collective-permute", "all-reduce"]
    assert cols[0]["bytes"] == _AR_BYTES
    assert cols[0]["groups"] == [(0, 2), (1, 3)]
    assert cols[1]["bytes"] == _AG_BYTES
    assert cols[1]["groups"] == [(0, 1), (2, 3)]
    assert cols[2]["groups"] == [(0, 1), (2, 3)]


def test_iota_transpose_groups():
    text = ("  %all-gather.9 = f32[16]{0} all-gather(f32[8]{0} %a), "
            "replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}\n")
    (col,) = hlo.iter_collectives(text)
    # iota(4).reshape(2,2).T.flatten() = [0,2,1,3] → groups {0,2},{1,3}
    assert col["groups"] == [(0, 2), (1, 3)]


def test_attribute_axis_on_dp2_tp2():
    shape, names = [2, 2], ["data", "model"]
    assert hlo.attribute_axis([(0, 2), (1, 3)], shape, names) == "data"
    assert hlo.attribute_axis([(0, 1), (2, 3)], shape, names) == "model"
    assert hlo.attribute_axis([(0, 1, 2, 3)], shape, names) \
        == "data+model"
    assert hlo.attribute_axis([(0, 3)], shape, names) == "other"


def test_collective_inventory_attributes_and_skips_singletons():
    inv = collective_inventory(_HLO, DP2_TP2)
    assert inv["collectives"] == {
        "data": _AR_BYTES, "model": _AG_BYTES + _CP_BYTES}
    assert inv["ops"]["data"] == {"all-reduce": 1}
    assert inv["ops"]["model"] == {"all-gather": 1,
                                   "collective-permute": 1}
    # the singleton-group all-reduce.4 moved no bytes and is absent


def test_sharding_factor():
    axes = {"data": 2, "model": 2}
    assert hlo.sharding_factor(None, axes) == 1
    assert hlo.sharding_factor("[{}, {}]", axes) == 1
    assert hlo.sharding_factor('[{"data"}, {"model"}]', axes) == 4
    assert hlo.sharding_factor('[{}, {"model", ?}]', axes) == 2
    assert hlo.sharding_factor('[{"data":(1)2}]', {"data": 4}) == 2


def test_main_args_reads_shardy_attrs():
    """The installed JAX prints shardings the Shardy way; the attr body
    nests braces, and aliasing rides in the same dict."""
    text = (
        'module @jit_f {\n'
        '  sdy.mesh @mesh = <["data"=2, "model"=2]>\n'
        '  func.func public @main(%arg0: tensor<64x128xf32> '
        '{sdy.sharding = #sdy.sharding<@mesh, [{}, {"model"}]>, '
        'tf.aliasing_output = 0 : i32}, %arg1: tensor<32x64xf32> '
        '{sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {}]>}) -> '
        '(tensor<64x128xf32> {jax.result_info = "result[0]", '
        'sdy.sharding = #sdy.sharding<@mesh, [{}, {"model"}]>}, '
        'tensor<f32> {jax.result_info = "result[1]", '
        'sdy.sharding = #sdy.sharding<@mesh, []>}) {\n  }\n}\n')
    assert hlo.mesh_axes(text) == {"data": 2, "model": 2}
    a0, a1 = hlo.main_args(text)
    assert a0 == {"type": "64x128xf32", "aliased": True,
                  "donor_only": False, "sharding": '[{}, {"model"}]'}
    assert a1["sharding"] == '[{"data"}, {}]' and not a1["aliased"]
    r0, r1 = hlo.main_results(text)
    assert r0 == {"type": "64x128xf32", "sharding": '[{}, {"model"}]'}
    assert r1 == {"type": "f32", "sharding": "[]"}


# --- collective_budget ------------------------------------------------------


def test_collective_budget_clean_within_budget():
    budgets = {"t": _budget_entry(
        {"data": _AR_BYTES, "model": _AG_BYTES + _CP_BYTES},
        per_shard=1)}
    vs, inv = collective_budget(_HLO, DP2_TP2, where="t",
                                budgets=budgets)
    assert not vs
    assert inv["collectives"]["data"] == _AR_BYTES


def test_collective_budget_fails_over_budget():
    budgets = {"t": _budget_entry(
        {"data": _AR_BYTES // 100, "model": _AG_BYTES + _CP_BYTES},
        per_shard=1)}
    vs, _ = collective_budget(_HLO, DP2_TP2, where="t", budgets=budgets)
    assert len(vs) == 1 and vs[0].check == "collective_budget"
    assert "'data'" in vs[0].message and "exceeds" in vs[0].message


def test_collective_budget_fails_on_unbudgeted_axis():
    budgets = {"t": _budget_entry({"data": _AR_BYTES}, per_shard=1)}
    vs, _ = collective_budget(_HLO, DP2_TP2, where="t", budgets=budgets)
    assert len(vs) == 1
    assert "unbudgeted mesh axis 'model'" in vs[0].message


def test_collective_budget_fails_without_manifest_entry():
    vs, _ = collective_budget(_HLO, DP2_TP2, where="t", budgets={})
    assert len(vs) == 1 and "no collective budget" in vs[0].message


def test_collective_budget_fails_without_compiled_text():
    vs, inv = collective_budget(None, DP2_TP2, where="t", budgets={})
    assert len(vs) == 1 and "no compiled HLO" in vs[0].message
    assert inv == {}


def test_collective_budget_fails_on_mesh_mismatch():
    budgets = {"t": _budget_entry(
        {"data": _AR_BYTES, "model": _AG_BYTES + _CP_BYTES},
        per_shard=1, mesh="data4_model1")}
    vs, _ = collective_budget(_HLO, DP2_TP2, where="t", budgets=budgets)
    assert len(vs) == 1 and "data4_model1" in vs[0].message


# --- replication_check ------------------------------------------------------

# 8192x64xf32 = 2 MB (above the 1 MiB floor); 256x64xf32 = 64 KB below
_REPLICATED_MAIN = (
    'module @jit_step {\n'
    '  sdy.mesh @mesh = <["data"=2, "model"=2]>\n'
    '  func.func public @main('
    '%arg0: tensor<8192x64xf32> '
    '{sdy.sharding = #sdy.sharding<@mesh, [{}, {}]>}, '
    '%arg1: tensor<8192x64xf32> '
    '{sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {"model"}]>}, '
    '%arg2: tensor<256x64xf32> '
    '{sdy.sharding = #sdy.sharding<@mesh, [{}, {}]>}) '
    '-> (tensor<8192x64xf32> '
    '{sdy.sharding = #sdy.sharding<@mesh, [{}, {}]>}) {\n'
    '  }\n'
    '}\n')


def test_replication_check_fails_on_replicated_large_tensor():
    vs = replication_check(_REPLICATED_MAIN, where="t")
    # %arg0 and the result replicate 2 MB; %arg1 is sharded, %arg2 is
    # under the floor
    assert len(vs) == 2
    assert all(v.check == "replication_check" for v in vs)
    assert "arg tensor<8192x64xf32>" in vs[0].message
    assert "result tensor<8192x64xf32>" in vs[1].message


def test_replication_check_allowlist_roundtrip():
    allow = (ReplicationAllow(type="8192x64xf32", max_count=2,
                              reason="read-only table, by design"),)
    assert not replication_check(_REPLICATED_MAIN, where="t",
                                 allowlist=allow)
    # max_count is a budget, not a blanket: one allowance covers one
    # tensor, the second replication still fails
    tight = (ReplicationAllow(type="8192x64xf32", max_count=1,
                              reason="only the arg"),)
    vs = replication_check(_REPLICATED_MAIN, where="t", allowlist=tight)
    assert len(vs) == 1


def test_replication_check_floor_excludes_small_tensors():
    # with the floor dropped, the 64 KB %arg2 is caught too
    vs = replication_check(_REPLICATED_MAIN, where="t", floor_bytes=1)
    assert len(vs) == 3


def test_replication_check_catches_midgraph_reshard():
    text = _REPLICATED_MAIN.replace(
        "  }\n",
        '    %2 = sdy.sharding_constraint %1 <@mesh, [{}, {}]> : '
        'tensor<512x1024xf32>\n  }\n')
    allow = (ReplicationAllow(type="8192x64xf32", max_count=2,
                              reason="boundary tensors excused"),)
    vs = replication_check(text, where="t", allowlist=allow)
    assert len(vs) == 1
    assert "mid-graph constraint tensor<512x1024xf32>" in vs[0].message


# --- per_shard_hbm_budget ---------------------------------------------------


def test_per_shard_budget_clean_and_over():
    budgets = {"t": _budget_entry({}, per_shard=1_000_000)}
    assert not per_shard_hbm_budget(4_000_000, DP2_TP2, where="t",
                                    budgets=budgets)
    vs = per_shard_hbm_budget(8_000_000, DP2_TP2, where="t",
                              budgets=budgets)
    assert len(vs) == 1 and vs[0].check == "per_shard_hbm_budget"
    assert "exceeds" in vs[0].message


def test_per_shard_budget_fails_without_pin_or_cost():
    vs = per_shard_hbm_budget(1.0, DP2_TP2, where="t", budgets={})
    assert len(vs) == 1 and "no per-shard byte budget" in vs[0].message
    budgets = {"t": _budget_entry({}, per_shard=1)}
    vs = per_shard_hbm_budget(None, DP2_TP2, where="t", budgets=budgets)
    assert len(vs) == 1 and "no cost analysis" in vs[0].message


# --- manifest round-trip ----------------------------------------------------


def test_write_load_shard_budgets_roundtrip(tmp_path):
    path = str(tmp_path / "shard_budgets.json")
    measured = {"t": {"mesh": "data2_model2",
                      "collectives": {"data": 1000, "model": 500},
                      "ops": {"data": {"all-reduce": 3}},
                      "per_shard": 2_000_000}}
    write_shard_budgets(measured, path=path, note="test")
    loaded = load_shard_budgets(path)
    entry = loaded["t"]
    assert entry["mesh"] == "data2_model2"
    assert entry["collectives"]["data"] == {
        "pinned_bytes": 1000, "budget_bytes": 1100}
    assert entry["per_shard"]["budget_bytes"] == 2_200_000
    assert entry["ops"] == {"data": {"all-reduce": 3}}
    # keep= copies existing pins through untouched (--pin-missing-shard)
    write_shard_budgets(
        {"u": {"mesh": "data2_model2", "collectives": {},
               "per_shard": 1}},
        path=path, note="test2", keep=loaded)
    again = load_shard_budgets(path)
    assert set(again) == {"t", "u"}
    assert again["t"] == entry
    # a deleted/corrupt manifest reads as empty, never as "clean"
    with open(path, "w") as f:
        f.write("not json")
    assert load_shard_budgets(path) == {}


# --- unsharded-pjit lint rule -----------------------------------------------

_UNSHARDED_SRC = '''
import jax
from functools import partial

@jax.jit
def bare(x):
    return x

@partial(jax.jit, donate_argnums=(0,))
def via_partial(x):
    return x

half = jax.jit(lambda x: x, in_shardings=None)
'''

_SHARDED_SRC = '''
import jax
from functools import partial

@partial(jax.jit, in_shardings=None, out_shardings=None,
         donate_argnums=(0,))
def step(x):
    return x

also = jax.jit(lambda x: x, in_shardings=None, out_shardings=None)
'''


def _pjit_violations(src, path):
    return [v for v in lint_source(src, path)
            if v.check == "unsharded-pjit"]


def test_unsharded_pjit_flags_all_three_forms():
    vs = _pjit_violations(_UNSHARDED_SRC,
                          "perceiver_tpu/parallel/fake.py")
    assert len(vs) == 3
    # the half-annotated call reports only what is missing
    assert any("out_shardings" in v.message
               and "in_shardings" not in v.message for v in vs)


def test_unsharded_pjit_scoped_to_spmd_modules():
    assert _pjit_violations(_UNSHARDED_SRC,
                            "perceiver_tpu/training/spmd.py")
    # same source outside the SPMD modules: propagation is the norm
    assert not _pjit_violations(_UNSHARDED_SRC,
                                "perceiver_tpu/models/fake.py")


def test_unsharded_pjit_clean_on_explicit_shardings():
    assert not _pjit_violations(_SHARDED_SRC,
                                "perceiver_tpu/parallel/fake.py")


def test_spmd_modules_lint_clean():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("perceiver_tpu/training/spmd.py",
                "perceiver_tpu/parallel/sharding.py",
                "perceiver_tpu/parallel/mesh.py"):
        with open(os.path.join(root, rel)) as f:
            assert not _pjit_violations(f.read(), rel), rel


# --- registration + MeshSpec ------------------------------------------------


def test_sharded_targets_registered_and_pinned():
    names = {t.name for t in SHARDED_TARGETS}
    assert len(names) >= 2
    assert {t.kind for t in SHARDED_TARGETS} == {"train", "serve",
                                                 "decode"}
    # ride the default sweep (check.py --all), but not the fast tier —
    # mesh targets pay an XLA compile the warm-cache contract excludes
    assert names <= {t.name for t in CANONICAL_TARGETS}
    assert not names & {t.name for t in FAST_TARGETS}
    assert all(t.mesh is not None for t in SHARDED_TARGETS)
    # the shipped manifest pins every sharded target on its mesh
    budgets = load_shard_budgets()
    for t in SHARDED_TARGETS:
        assert t.name in budgets, t.name
        assert budgets[t.name]["mesh"] == t.mesh.descriptor
        assert budgets[t.name]["collectives"], t.name


def test_mesh_spec_properties_and_build():
    mesh = MeshSpec(axes=(("data", 2), ("model", 2)))
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape == (2, 2)
    assert mesh.n_devices == 4
    assert mesh.descriptor == "data2_model2"
    built = mesh.build()
    assert built.devices.shape == (2, 2)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        MeshSpec(axes=(("data", 64),)).build()


# --- end-to-end: a tiny sharded step through the real pipeline --------------


def _tiny_spmd_target():
    from perceiver_tpu.analysis.targets import _build_mlm

    def build():
        return _build_mlm(batch=8, channels=16, seq_len=32, vocab=128,
                          loss_impl="packed")

    return StepTarget(name="tiny_mlm_spmd_dp2_tp2", build=build,
                      mesh=DP2_TP2)


def test_tiny_sharded_target_end_to_end(monkeypatch, tmp_path):
    """Lower+compile a tiny dp2×tp2 MLM train step, pin a manifest
    from its own measurement, and drive it through run_graph_checks:
    clean against its pins, failing against an empty manifest — the
    wiring proof, not just the passes. Slow-marked (one XLA compile)."""
    import perceiver_tpu.analysis.passes as passes_mod
    from perceiver_tpu.analysis import shardcheck

    target = _tiny_spmd_target()
    lowered = lower_target(target)
    assert lowered.compiled_text, "mesh target must carry compiled HLO"
    assert lowered.bytes_accessed

    inv = collective_inventory(lowered.compiled_text, target.mesh)
    # GSPMD must have inserted real collectives (at minimum the data-
    # axis gradient all-reduce) — an empty inventory means the step
    # silently stopped being SPMD
    assert inv["collectives"]

    path = str(tmp_path / "shard_budgets.json")
    write_shard_budgets({target.name: {
        "mesh": target.mesh.descriptor,
        "collectives": inv["collectives"],
        "ops": inv["ops"],
        "per_shard": lowered.bytes_accessed / target.mesh.n_devices,
    }}, path=path, note="test")
    budgets = load_shard_budgets(path)

    vs, _ = run_shard_passes(lowered, budgets=budgets)
    assert not vs, vs

    # seeded failures: an empty manifest and a zeroed budget both trip
    vs, _ = run_shard_passes(lowered, budgets={})
    assert {v.check for v in vs} == {"collective_budget",
                                    "per_shard_hbm_budget"}
    zeroed = json.loads(json.dumps(budgets))
    for axis in zeroed[target.name]["collectives"].values():
        axis["budget_bytes"] = 0
    zeroed[target.name]["per_shard"]["budget_bytes"] = 0
    vs, _ = run_shard_passes(lowered, budgets=zeroed)
    assert any(v.check == "collective_budget" and "exceeds"
               in v.message for v in vs)
    assert any(v.check == "per_shard_hbm_budget" for v in vs)

    # dropping the floor exposes the replicated small buffers (adamw
    # step counts etc.) the default floor rightly ignores
    assert replication_check(lowered.text, where=target.name,
                             floor_bytes=1)
    assert not replication_check(lowered.text, where=target.name,
                                 floor_bytes=DEFAULT_FLOOR_BYTES)

    # and the same lowering through the real driver: the three shard
    # passes run and gate
    monkeypatch.setattr(passes_mod, "lower_target",
                        lambda t, cache=None, **kw: lowered)
    monkeypatch.setattr(shardcheck, "load_shard_budgets",
                        lambda p=None: budgets)
    monkeypatch.setattr(
        passes_mod, "load_hbm_budgets",
        lambda p=None: {target.name: {
            "pinned_bytes": lowered.bytes_accessed,
            "budget_bytes": lowered.bytes_accessed * 1.05}})
    report = run_graph_checks([target], recompile=False)
    assert {"collective_budget", "replication_check",
            "per_shard_hbm_budget"} <= set(report.checks_run)
    assert report.ok, report.format()
    monkeypatch.setattr(shardcheck, "load_shard_budgets",
                        lambda p=None: {})
    assert not run_graph_checks([target], recompile=False).ok


# --- end-to-end: a tiny sharded DECODE step (ISSUE 14) ----------------------


def _tiny_decode_spmd_target(spec_k=0):
    import jax.numpy as jnp
    import numpy as np

    def build():
        from perceiver_tpu.serving.decode import DecodeGeometry
        from perceiver_tpu.tasks import MaskedLanguageModelTask

        # vocab 128 divides evenly over the model axis (tp2), streams
        # divide over data (dp2) — same divisibility rules as the
        # canonical decode_mixed_mlm_spmd target, at compile-cheap
        # shapes; mixed qlens exercise the unified prefill+decode step
        # (with spec_k, row 1 carries a k+1-lane verify window)
        task = MaskedLanguageModelTask(
            vocab_size=128, max_seq_len=32, num_latents=4,
            num_latent_channels=16, num_encoder_layers=2,
            num_encoder_self_attention_layers_per_block=1)
        rng = np.random.default_rng(0)
        return task, {
            "geometry": DecodeGeometry(max_streams=4, num_pages=9,
                                       page_size=4, max_seq_len=32,
                                       max_chunk=4, spec_k=spec_k),
            "tokens": jnp.asarray(rng.integers(3, 128, (4, 4)),
                                  jnp.int32),
            "qlens": jnp.asarray(
                [4, 1 + spec_k, 2, 1], jnp.int32),
            "attn_impl": "reference",
        }

    name = ("tiny_decode_spmd_dp2_tp2" if not spec_k
            else f"tiny_spec_decode_spmd_k{spec_k}_dp2_tp2")
    return StepTarget(name=name, build=build,
                      kind="decode", mesh=DP2_TP2)


def test_tiny_sharded_decode_target_end_to_end(tmp_path):
    """Lower+compile a tiny dp2×tp2 decode step, pin a manifest from
    its own measurement, and run the shard passes: clean against its
    pins, tripping against an emptied or zeroed manifest — the
    seeded-violation proof for the decode shard pin. The carry stays
    fully donated under explicit shardings (per-shard buffers alias in
    place), and the sub-floor KV pools may replicate freely."""
    from perceiver_tpu.analysis import donation_check

    target = _tiny_decode_spmd_target()
    lowered = lower_target(target)
    assert lowered.compiled_text, "mesh target must carry compiled HLO"
    assert lowered.expected_donated == 6  # k1 v1 kn vn lengths tables
    assert not donation_check(lowered.text, where=target.name,
                              expected_donated=lowered.expected_donated)
    # replicated pools sit below the 1 MiB floor by design
    assert not replication_check(lowered.text, where=target.name,
                                 floor_bytes=DEFAULT_FLOOR_BYTES)

    inv = collective_inventory(lowered.compiled_text, target.mesh)
    assert inv["collectives"], \
        "GSPMD inserted no collectives — the step stopped being SPMD"

    path = str(tmp_path / "shard_budgets.json")
    write_shard_budgets({target.name: {
        "mesh": target.mesh.descriptor,
        "collectives": inv["collectives"],
        "ops": inv["ops"],
        "per_shard": lowered.bytes_accessed / target.mesh.n_devices,
    }}, path=path, note="test")
    budgets = load_shard_budgets(path)

    vs, _ = run_shard_passes(lowered, budgets=budgets)
    assert not vs, vs
    # seeded failures: missing pin and zeroed budgets both trip
    vs, _ = run_shard_passes(lowered, budgets={})
    assert {v.check for v in vs} == {"collective_budget",
                                    "per_shard_hbm_budget"}
    zeroed = json.loads(json.dumps(budgets))
    for axis in zeroed[target.name]["collectives"].values():
        axis["budget_bytes"] = 0
    zeroed[target.name]["per_shard"]["budget_bytes"] = 0
    vs, _ = run_shard_passes(lowered, budgets=zeroed)
    assert any(v.check == "collective_budget" and "exceeds"
               in v.message for v in vs)
    assert any(v.check == "per_shard_hbm_budget" for v in vs)


# --- end-to-end: a tiny sharded SPECULATIVE decode step (ISSUE 19) ----------


def test_tiny_sharded_spec_decode_target_end_to_end(tmp_path):
    """The speculative verify step under dp2×tp2: window tiling folds
    the k+1 lanes into the kernel row axis, so GSPMD partitions the
    SAME program shape as plain decode — the carry stays fully donated
    (one paged cache per shard), collectives still appear, and its pin
    round-trips through shard_budgets with seeded violations tripping
    on an emptied or zeroed manifest."""
    from perceiver_tpu.analysis import donation_check

    target = _tiny_decode_spmd_target(spec_k=2)
    lowered = lower_target(target)
    assert lowered.compiled_text, "mesh target must carry compiled HLO"
    assert lowered.expected_donated == 6  # k1 v1 kn vn lengths tables
    assert not donation_check(lowered.text, where=target.name,
                              expected_donated=lowered.expected_donated)
    assert not replication_check(lowered.text, where=target.name,
                                 floor_bytes=DEFAULT_FLOOR_BYTES)

    inv = collective_inventory(lowered.compiled_text, target.mesh)
    assert inv["collectives"], \
        "GSPMD inserted no collectives — the step stopped being SPMD"

    path = str(tmp_path / "shard_budgets.json")
    write_shard_budgets({target.name: {
        "mesh": target.mesh.descriptor,
        "collectives": inv["collectives"],
        "ops": inv["ops"],
        "per_shard": lowered.bytes_accessed / target.mesh.n_devices,
    }}, path=path, note="test")
    budgets = load_shard_budgets(path)

    vs, _ = run_shard_passes(lowered, budgets=budgets)
    assert not vs, vs
    vs, _ = run_shard_passes(lowered, budgets={})
    assert {v.check for v in vs} == {"collective_budget",
                                    "per_shard_hbm_budget"}
    zeroed = json.loads(json.dumps(budgets))
    for axis in zeroed[target.name]["collectives"].values():
        axis["budget_bytes"] = 0
    zeroed[target.name]["per_shard"]["budget_bytes"] = 0
    vs, _ = run_shard_passes(lowered, budgets=zeroed)
    assert any(v.check == "collective_budget" and "exceeds"
               in v.message for v in vs)
    assert any(v.check == "per_shard_hbm_budget" for v in vs)
