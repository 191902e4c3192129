#!/usr/bin/env python
"""AOT memory check for the big BASELINE configs (VERDICT r1 #6).

Compiles (compile ONLY — no execution) the full train step of:

1. the 224×224 / 512-latent classifier preset (BASELINE configs[3],
   v5e-8 target) at its per-chip batch shard,
2. the v5p-16 Perceiver-LM MLM preset (1024×512 latents, 12 self-attn
   layers/block, seq 2048; BASELINE configs[4]) at its per-chip shard,
3. (``seg``) the 512×512 / 262,144-query segmentation config,
4. (``ouro``) the looped causal LM of the benchmark's ``ouro_train``
   (``benchmarks/configs/ouro_2p6b.json``: 8 layers x 4 passes at the
   published widths, 2 rows of 4096),

5. (``nemotron``) the hybrid state-space / mixture-of-experts LM of
   the benchmark's ``nemotron_train``
   (``benchmarks/configs/nemotron3_nano_30b.json``: 9 layers, 8 of 128
   experts held, 4 rows of 4096),

6. (``sdar``) the block-diffusion mixture-of-experts LM of the
   benchmark's ``sdar_train`` (``benchmarks/configs/sdar_30b_a3b.json``:
   6 layers, 16 of 128 experts held, 2 rows of 4096 data tokens, twice
   that through the stack),

7. (``qwen3next``) the linear-attention mixture-of-experts LM of the
   benchmark's ``qwen3next_train``
   (``benchmarks/configs/qwen3_next_80b_a3b.json``: 4 layers, three
   gated delta-rule mixers and one gated attention, 32 of 512 experts
   held, 4 rows of 4096),

8. (``kimi``) the Kimi Linear LM of the benchmark's ``kimi_linear_train``
   (``benchmarks/configs/kimi_linear_48b_a3b.json``: published layers 1
   to 5, four Kimi Delta Attention mixers and one latent attention, a
   leading dense MLP, 8 of 256 experts held, 4 rows of 4096),

9. (``glm``) the GLM-4.7-Flash LM of the benchmark's ``glm_flash_train``
   (``benchmarks/configs/glm_4p7_flash.json``: published layers 44 to
   47, four latent attentions with a query latent and rotary channels,
   8 of 64 experts held, the multi-token prediction module and its
   second reading of the head, 4 rows of 4096),

on whatever single device is available, and reports XLA's HBM usage
estimates (argument/output/temp/generated-code sizes). This validates
that remat + query chunking keep the per-chip footprint inside a
v5e/v5p chip's HBM before any pod time is spent.

``lm``, ``224``, ``ouro``, ``nemotron``, ``sdar``, ``qwen3next``, ``kimi`` and
``glm`` run ``remat: true``: beside
XLA's sizes they print which dear values the layers keep and the bytes reckoned
for them (``ops/remat.py``). Under ``MEMCHECK_TOPOLOGY`` the choices
that read the backend are made as the described chip would make them
(the fused attention core; the chip's memory, ``DESCRIBED_MEMORY``; in
use on it, the parameters and optimizer state the step is handed).

Usage: python scripts/aot_memcheck.py
           [224 | lm | seg | ouro | nemotron | sdar | qwen3next | kimi | glm
            | all] [rows]
       (``rows``: the per-chip batch of ``224`` / ``lm`` / ``ouro`` /
       ``nemotron`` / ``sdar`` / ``qwen3next`` / ``kimi`` / ``glm`` in
       place of the preset's; ``all`` leaves ``ouro``, ``nemotron``,
       ``sdar``, ``qwen3next``, ``kimi`` and ``glm`` out)
Env:   MEMCHECK_PLATFORM=cpu   (forces the CPU backend for smoke runs)
"""

import json
import os
import sys
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _mem_analysis(compiled):
    try:
        m = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001
        return {"error": f"memory_analysis unavailable: {e}"}
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(m, k, None)
        if v is not None:
            out[k.replace("_in_bytes", "_mb")] = round(v / 2**20, 1)
    # peak live ≈ args + temps (outputs alias donated args here)
    if "argument_size_mb" in out and "temp_size_mb" in out:
        out["approx_peak_mb"] = round(
            out["argument_size_mb"] + out["temp_size_mb"], 1)
    return out


# ``memory_stats()["bytes_limit"]`` of the chips a topology name can
# describe here (a v5e reports 16.91 GB; my chip run, PR 29)
DESCRIBED_MEMORY = {"TPU v5 lite": 16_909_336_064}


def _topology_sharding():
    """When MEMCHECK_TOPOLOGY is set (e.g. ``v5e:2x2``), AOT-compile
    against that real TPU target via the local libtpu instead of the
    host backend — memory numbers then come from the actual TPU
    compiler, not a CPU-backend estimate (VERDICT r3 missing #4)."""
    name = os.environ.get("MEMCHECK_TOPOLOGY")
    if not name:
        return None
    import jax
    from jax.experimental import topologies

    import perceiver_tpu.ops.attention as attention
    import perceiver_tpu.ops.moe as moe
    import perceiver_tpu.ops.pallas_head_rotary as head_rotary
    import perceiver_tpu.ops.pallas_short_conv as short_conv
    import perceiver_tpu.ops.remat as remat
    import perceiver_tpu.ops.ssm as ssm
    import perceiver_tpu.utils.platform as platform

    topo = topologies.get_topology_desc(name, platform="tpu")
    kind = topo.devices[0].device_kind
    print(f"[memcheck] target topology {name}: {kind}", file=sys.stderr,
          flush=True)
    # a described chip is no backend: what the program reads off the
    # backend is given as that chip would report it
    attention._backend = lambda: "tpu"
    moe._backend = lambda: "tpu"
    ssm._backend = lambda: "tpu"
    short_conv._backend = lambda: "tpu"
    head_rotary._backend = lambda: "tpu"
    # ... and its Pallas kernels are the chip's own, not the interpreter's
    # loops (a kernel that asks its backend directly: the delta rules')
    platform.default_interpret = lambda: False
    remat._memory_limit = lambda: DESCRIBED_MEMORY[kind]
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile_train_step(task, batch, label):
    import jax
    import optax

    from perceiver_tpu.ops.policy import Policy

    model = task.build()
    policy = Policy.bf16()
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    tx = optax.adamw(1e-3)
    opt_state = jax.eval_shape(tx.init, params)
    topo_sh = _topology_sharding()
    if topo_sh is not None:
        retarget = lambda t: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=topo_sh), t)
        params, opt_state = retarget(params), retarget(opt_state)
        import perceiver_tpu.ops.remat as remat
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves((params, opt_state)))
        remat._memory_held = lambda: state_bytes
        batch = retarget({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                          for k, v in batch.items()})

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch, rng):
        def loss_fn(p):
            loss, _ = task.loss_and_metrics(model, p, batch, rng=rng,
                                            deterministic=False,
                                            policy=policy)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=getattr(v, "sharding",
                                                       None))
              for k, v in batch.items()}
    rng_sds = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=topo_sh)
    from perceiver_tpu.ops.remat import format_remat_keeps, remat_keeps

    print(f"[{label}] lowering ...", file=sys.stderr, flush=True)
    with remat_keeps() as keeps:
        lowered = train_step.lower(params, opt_state, shapes, rng_sds)
    print(f"[{label}] compiling ...", file=sys.stderr, flush=True)
    compiled = lowered.compile()  # graphcheck: ignore — AOT memory diagnostic, compilation IS the measurement
    out = _mem_analysis(compiled)
    if keeps:
        out["remat_keeps"] = format_remat_keeps(keeps)
        out["remat_reckoned_mb"] = {
            name: round(n / 2**20, 1)
            for name, n in keeps[0]["bytes"].items()}
    return out


def check_224(per_chip_batch: int = 8):
    """224×224/512-latent classifier of
    scripts/configs/imagenet_scale_v5e8.yaml; v5e-8 runs dp8, so the
    per-chip shard is global_batch/8 (preset batch 64 → 8/chip, the
    benchmark's ``img_train``)."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import ImageClassifierTask

    task = ImageClassifierTask(
        image_shape=(224, 224, 3), num_classes=1000,
        num_frequency_bands=64, num_latents=512, num_latent_channels=512,
        num_encoder_layers=6, num_decoder_cross_attention_heads=1,
        remat=True)
    batch = {
        "image": jnp.zeros((per_chip_batch, 224, 224, 3), jnp.float32),
        "label": jnp.zeros((per_chip_batch,), jnp.int32),
    }
    return _compile_train_step(task, batch, "224")


def check_lm(per_chip_batch: int = 4):
    """v5p-16 Perceiver-LM preset per-chip shard (batch 64 → 4/chip):
    the mesh is dp4×sp2×tp2 (scripts/configs/perceiver_lm_v5p16.yaml);
    tensor-parallel weight shards aren't modeled single-chip, so this
    is the CONSERVATIVE (replicated-weights) bound. 24 rows are the
    benchmark's ``lm_train``."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=32000, max_seq_len=2048,
        num_latents=1024, num_latent_channels=512,
        num_encoder_self_attention_layers_per_block=12,
        num_encoder_cross_attention_heads=8,
        num_encoder_self_attention_heads=8,
        num_decoder_cross_attention_heads=8,
        remat=True, loss_impl="packed")
    batch = {
        "input_ids": jnp.zeros((per_chip_batch, 2048), jnp.int32),
        "pad_mask": jnp.zeros((per_chip_batch, 2048), bool),
    }
    return _compile_train_step(task, batch, "lm")


def check_seg(batch: int = 2, side: int = 512):
    """The 512×512 / 262,144-output-query LArTPC segmentation config
    (``run.py:72-112``) — the decoder query-chunking memory stress."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import SegmentationTask

    task = SegmentationTask(image_shape=(side, side, 1),
                            query_chunk_size=min(16384, side * side))
    batch_arrs = {
        "image": jnp.zeros((batch, side, side, 1), jnp.float32),
        "label": jnp.zeros((batch, side, side), jnp.int32),
    }
    return _compile_train_step(task, batch_arrs, f"seg{side}_b{batch}")


def _benchmark_model(name: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def check_ouro(per_chip_batch: int = 2):
    """The benchmark's ``ouro_2p6b`` as ``ouro_train`` runs it: the
    ``model`` group of its configuration file, full rows."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import CausalLMTask

    model = _benchmark_model("ouro_2p6b")
    batch = {"input_ids": jnp.zeros((per_chip_batch, model["max_seq_len"]),
                                    jnp.int32)}
    return _compile_train_step(CausalLMTask(**model), batch, "ouro")


def check_nemotron(per_chip_batch: int = 4,
                   config: str = "nemotron3_nano_30b",
                   label: str = "nemotron"):
    """The benchmark's ``nemotron3_nano_30b`` as ``nemotron_train`` runs
    it (or ``qwen3_next_80b_a3b`` as ``qwen3next_train`` does, or
    ``kimi_linear_48b_a3b`` as ``kimi_linear_train``, or
    ``glm_4p7_flash`` as ``glm_flash_train``: the same task, another
    pattern): the ``model`` group of its configuration file, full rows,
    each expert layer's share named by the batch, a prediction module's
    last."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import HybridLMTask

    model = _benchmark_model(config)
    batch = {"input_ids": jnp.zeros((per_chip_batch, model["max_seq_len"]),
                                    jnp.int32),
             "first_experts": jnp.zeros(
                 (per_chip_batch, model["hybrid_override_pattern"].count("E")
                  + model.get("num_nextn_predict_layers", 0)), jnp.int32)}
    return _compile_train_step(HybridLMTask(**model), batch, label)


def check_sdar(per_chip_batch: int = 2):
    """The benchmark's ``sdar_30b_a3b`` as ``sdar_train`` runs it: the
    ``model`` group of its configuration file, full rows (the step
    doubles them), each expert layer's share named by the batch."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import BlockDiffusionLMTask

    model = _benchmark_model("sdar_30b_a3b")
    batch = {"input_ids": jnp.zeros((per_chip_batch, model["max_seq_len"]),
                                    jnp.int32),
             "first_experts": jnp.zeros(
                 (per_chip_batch, model["num_hidden_layers"]), jnp.int32)}
    return _compile_train_step(BlockDiffusionLMTask(**model), batch, "sdar")


def main():
    import jax

    want = os.environ.get("MEMCHECK_PLATFORM")
    if want:
        jax.config.update("jax_platforms", want)
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    rows = {"per_chip_batch": int(sys.argv[2])} if len(sys.argv) > 2 else {}

    out = {"device": str(jax.devices()[0]),
           "topology": os.environ.get("MEMCHECK_TOPOLOGY")}
    if which in ("224", "all"):
        out["classifier_224"] = check_224(**rows)
    if which in ("lm", "all"):
        out["perceiver_lm_v5p16_shard"] = check_lm(**rows)
    if which in ("seg", "all"):
        out["seg_512_262k_queries"] = check_seg()
    if which == "ouro":
        out["ouro_2p6b_8_layers"] = check_ouro(**rows)
    if which == "nemotron":
        out["nemotron3_nano_30b_9_layers"] = check_nemotron(**rows)
    if which == "sdar":
        out["sdar_30b_a3b_6_layers"] = check_sdar(**rows)
    if which == "qwen3next":
        out["qwen3_next_80b_a3b_4_layers"] = check_nemotron(
            config="qwen3_next_80b_a3b", label="qwen3next", **rows)
    if which == "kimi":
        out["kimi_linear_48b_a3b_5_layers"] = check_nemotron(
            config="kimi_linear_48b_a3b", label="kimi", **rows)
    if which == "glm":
        out["glm_4p7_flash_4_layers_and_mtp"] = check_nemotron(
            config="glm_4p7_flash", label="glm", **rows)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
