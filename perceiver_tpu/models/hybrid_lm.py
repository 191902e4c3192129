"""A hybrid state-space / mixture-of-experts language model: a decoder
stack whose layers are of seven kinds in a published order (the
``nemotron_h`` family, NVIDIA Nemotron-H, arXiv:2504.03624, and
Nemotron 3 Nano; ``hybrid_override_pattern``)::

    h0 = E[ids]
    for each character of the pattern:  h = h + mixer(rms(h))
    logits = rms_f(h) Wh                                  (head untied)

``M`` is a Mamba-2 mixer (``ops/ssm.py``), ``E`` an expert layer
(``ops/moe.py``), ``L`` a gated delta-rule mixer, ``K`` a Kimi Delta
Attention mixer, ``A`` latent attention, ``D`` a dense gated MLP (all
below; a multi-token prediction module may follow the stack), ``*``
causal attention with grouped queries and, in
``nemotron_h``, **no rotary embedding** (the Mamba layers carry
position). A layer is one RMSNorm with a scale, one mixer and a
residual: there is no separate MLP after ``M`` or ``*`` unless the
pattern names one (``D``). No linear layer has a bias.

A Qwen3-MoE decoder layer (``h += attn(rms(h)); h += moe(rms(h))``, the
layer of SDAR) is two of these, ``*E``: its attention has rotary
positions (``rope_theta``) and an RMSNorm over each head's channels of
the projected queries and keys (``qk_norm``), its router is a softmax
with the top-k renormalised (``router_scoring``, ``norm_topk_prob``),
its experts are gated with three matrices (``gated_experts``) and it
has no shared expert (``moe_shared_expert_intermediate_size`` 0). A
call may run a row as **block diffusion** trains it
(``block_diffusion=(L, B)``: ``L`` noised positions beside their ``L``
clean ones, ``ops.attention.block_diffusion_mask``'s rules in the
causal triangle's place, position ``j mod L`` for index ``j``).

A ``qwen3_next`` decoder layer (Qwen3-Next) is two of these as well,
``LE`` or ``*E`` (a published period of four is ``LELELE*E``): ``L`` is
a gated delta-rule mixer (``ops/delta_rule.py``: linear attention,
``linear_*``); its ``*`` projects each head's query beside a gate and
multiplies the core's output by the gate's sigmoid
(``attn_output_gate``), turns the first ``partial_rotary_factor`` of a
head's channels only, and every RMSNorm of the stack (the layers', the
final one, the q/k norms) is zero-centred, ``x / rms(x) * (1 + w)``
(``zero_centered_norms``); its ``E`` adds a shared expert that is gated
with three matrices under a sigmoid gate of one column
(``shared_expert_kind``).

A ``kimi_linear`` decoder layer (Kimi Linear, arXiv:2510.26692) is two
as well, ``KD``, ``KE`` or ``AE`` (its published layers 1 to 5 are
``KDKEKEAEKE``): ``K`` is Kimi Delta Attention (``ops/delta_rule.py``'s
``kda_mixer_*``, ``kda_*``: the delta rule with a decay that is a vector
a key channel, from a low-rank projection, and a sigmoid-gated norm);
``A`` is latent attention (MLA, ``mla_*`` below: keys and values from
one normed latent of ``kv_lora_rank`` channels, ``qk_rope_head_dim``
key channels shared by all heads, score heads of
``qk_nope_head_dim + qk_rope_head_dim`` beside value heads of
``v_head_dim``, and **no rotary embedding on any channel**:
``mla_use_nope``, the KDA layers carry position; training runs it
expanded to multi-head attention); ``D`` is the dense gated MLP of the
leading layers (``first_k_dense_replace``; ``ops/mlp.gated_mlp_*`` at
``intermediate_size``, scope ``mlp``); its ``E`` has a sigmoid router
whose renormalised top-k is scaled, gated experts and a shared expert
gated with three matrices and no gate column (``shared_expert_kind``
``glu``).

A ``glm4_moe_lite`` decoder layer (GLM-4.7-Flash; DeepSeek-V3's layer,
arXiv:2412.19437) is ``AE`` throughout: its ``A`` takes its queries from
a normed latent too (``q_lora_rank``: ``q = rms(a W_qa) W_qb``) and
carries **decoupled rotary positions** (``rope_theta``: the
``qk_rope_head_dim`` shared key channels, turned once before they are
handed to the heads, and each query head's last ``qk_rope_head_dim``
channels turn with position; the ``nope`` channels and the values do
not), with score heads as wide as value heads (192 + 64 | 256: the
fused kernels take the call unpadded). After the stack it has a
**multi-token prediction module** (``num_nextn_predict_layers`` 1,
``params["mtp"]``; no letter of the pattern): the stack's final-normed
state beside the embedding of the next id, each normed, through
``eh_proj`` into one more ``A`` + ``E`` pair of the module's own and a
norm, read by the stack's own head (``prediction_states``; the task
adds its loss at a weight). The embedding table and the head are the
stack's: their gradients are the sums of both readings'.

The attention layer runs on the cores every causal call takes
(``ops.attention.mha_apply``): its ``num_kv_heads`` key/value heads are
repeated to the query heads before the call (query head ``i`` reads
key/value head ``i // (heads / kv_heads)``), so the fused kernels see
the call they know.

The expert layers hold ``held_experts`` of the ``num_experts`` the
router sees, from ``first_expert`` on: a chip's share under expert
parallelism; all of them where ``held_experts`` is None. A call may say
which share each expert layer holds (``first_experts``, one first
expert an expert layer, values of the step and not of its program) in
``first_expert``'s place.

The layers differ, so they are unrolled (no scan over stacked
parameters). With ``remat`` each layer is a ``jax.checkpoint`` of its
own whose save list is the names ``ops/remat.choose_keeps`` keeps of
``HYBRID_REMAT_NAMES``, reckoned over all the layers from the shapes
each kind reports, against what the device has left.

Training never materialises the ``(B, S, V)`` logits: the task takes
``hidden_states`` and reads the head through
``ops.fused_ce.fused_linear_nll``. ``apply`` gives the dense logits for
tests, prediction and small sizes.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops import remat
from perceiver_tpu.ops.attention import (
    data_shards,
    mha_apply,
    tally_latent_call,
    untallied,
)
from perceiver_tpu.ops.delta_rule import (
    delta_mixer_apply,
    delta_mixer_init,
    kda_mixer_apply,
    kda_mixer_init,
)
from perceiver_tpu.ops.fourier import rope_tables
from perceiver_tpu.ops.initializers import trunc_normal_clamped
from perceiver_tpu.ops.linear import linear_apply, linear_init
from perceiver_tpu.ops.mlp import gated_mlp_apply, gated_mlp_init
from perceiver_tpu.ops.moe import moe_apply, moe_init
from perceiver_tpu.ops.norm import rms_norm_apply, rms_norm_init
from perceiver_tpu.ops.pallas_head_rotary import head_norm_rotary
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.ssm import ssm_mixer_apply, ssm_mixer_init
from perceiver_tpu.ops.tally import Tally

_INIT_STD = 0.02
LAYER_KINDS = {"M": "ssm", "E": "moe", "*": "attn", "L": "delta",
               "K": "kda", "A": "mla", "D": "mlp"}
# the layers of a multi-token prediction module, in order
MTP_KINDS = "AE"
#: trace-time tally of the prediction modules a step's loss reads, by
#: the words of the ``[step_load]`` line: depth, loss weight, what is
#: shared with the stack
prediction_modules = Tally()


def gqa_init(key, dim: int, num_heads: int, num_kv_heads: int,
             head_dim: int, qk_norm: bool = False,
             output_gate: bool = False, zero_centered: bool = False):
    """``output_gate``: the query projection twice as wide, a head's
    query beside its gate; ``zero_centered``: the q/k norms'."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    params = {
        "q": linear_init(kq, dim, num_heads * head_dim
                         * (2 if output_gate else 1), bias=False),
        "k": linear_init(kk, dim, num_kv_heads * head_dim, bias=False),
        "v": linear_init(kv, dim, num_kv_heads * head_dim, bias=False),
        "out": linear_init(ko, num_heads * head_dim, dim, bias=False),
    }
    if qk_norm:   # one scale of head_dim each, for all the heads
        params.update(
            q_norm=rms_norm_init(head_dim, zero_centered=zero_centered),
            k_norm=rms_norm_init(head_dim, zero_centered=zero_centered))
    return params


def repeat_kv(x, num_kv_heads: int, num_heads: int):
    """(B, S, kv_heads x D) -> (B, S, heads x D): each key/value head
    side by side for the query heads that read it."""
    rows, seq, width = x.shape
    heads = x.reshape(rows, seq, num_kv_heads, 1, width // num_kv_heads)
    return jnp.broadcast_to(
        heads, (rows, seq, num_kv_heads, num_heads // num_kv_heads,
                width // num_kv_heads)).reshape(rows, seq, -1)


def gqa_apply(params, a, *, num_heads: int, num_kv_heads: int,
              policy: Policy = DEFAULT_POLICY, impl: Optional[str] = None):
    """Causal attention with grouped queries, no position embedding."""
    return rotary_gqa_apply(params, a, num_heads=num_heads,
                            num_kv_heads=num_kv_heads, policy=policy,
                            impl=impl)


def rotary_gqa_apply(params, a, *, num_heads: int, num_kv_heads: int,
                     policy: Policy = DEFAULT_POLICY,
                     impl: Optional[str] = None, rope=None,
                     norm_eps: float = 1e-6, block_diffusion=None,
                     output_gate: bool = False):
    """Attention with grouped queries under the causal mask, or under
    the block-diffusion mask of ``block_diffusion=(L, B)``. Where the
    tree holds ``q_norm`` / ``k_norm``, queries and keys take an RMSNorm
    over each head's channels; ``rope`` (tables with a row a position of
    ``a``) rotates them after it. The keys are normed and rotated on
    their own ``num_kv_heads`` heads, before they are repeated.
    ``output_gate`` as ``mha_apply``'s."""
    with device_scope("attn_proj"):
        k = linear_apply(params["k"], a, policy=policy)
        if "k_norm" in params or rope is not None:
            k = head_norm_rotary(k, num_kv_heads, norm=params.get("k_norm"),
                                 eps=norm_eps, rope=rope, policy=policy)
        k = repeat_kv(k, num_kv_heads, num_heads)
        v = repeat_kv(linear_apply(params["v"], a, policy=policy),
                      num_kv_heads, num_heads)
    return mha_apply(params, a, None, None, num_heads=num_heads,
                     kv_heads=(k, v), causal=block_diffusion is None,
                     block_diffusion=block_diffusion, rope=rope,
                     norm_eps=norm_eps, policy=policy, impl=impl,
                     output_gate=output_gate)


def mla_init(key, dim: int, num_heads: int, *, kv_lora_rank: int,
             qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
             q_lora_rank: int = 0):
    """Latent attention: ``q`` a head's ``nope + rope`` channels or,
    with a query latent (``q_lora_rank``), ``q_a`` to the latent,
    ``q_a_norm`` its RMSNorm and ``q_b`` from it to the same channels;
    ``kv_a`` the latent beside the shared key channels, ``kv_norm`` the
    latent's RMSNorm, ``kv_b`` a head's ``nope`` key channels beside its
    ``v_head_dim`` value channels, ``out`` from the value heads."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    q_width = num_heads * (qk_nope_head_dim + qk_rope_head_dim)
    if q_lora_rank:
        kqa, kqb = jax.random.split(kq)
        queries = {
            "q_a": linear_init(kqa, dim, q_lora_rank, bias=False),
            "q_a_norm": rms_norm_init(q_lora_rank),
            "q_b": linear_init(kqb, q_lora_rank, q_width, bias=False),
        }
    else:
        queries = {"q": linear_init(kq, dim, q_width, bias=False)}
    return {
        **queries,
        "kv_a": linear_init(ka, dim, kv_lora_rank + qk_rope_head_dim,
                            bias=False),
        "kv_norm": rms_norm_init(kv_lora_rank),
        "kv_b": linear_init(kb, kv_lora_rank, num_heads * (
            qk_nope_head_dim + v_head_dim), bias=False),
        "out": linear_init(ko, num_heads * v_head_dim, dim, bias=False),
    }


def _mla_queries(params, a, num_heads: int, qk_nope_head_dim: int, rope,
                 norm_eps: float, policy: Policy):
    """The queries (B, S, H x (nope + rope)) of a latent attention
    whose tree holds a query latent or whose rope channels turn:
    ``q = rms(a W_qa; w_q) W_qb`` (or ``a W_q``), then each head's last
    ``rope`` channels rotated by ``rope``'s tables."""
    if "q_a" in params:
        q = linear_apply(params["q_b"], rms_norm_apply(
            params["q_a_norm"],
            linear_apply(params["q_a"], a, policy=policy), norm_eps, policy),
            policy=policy)
    else:
        q = linear_apply(params["q"], a, policy=policy)
    if rope is not None:
        q = head_norm_rotary(q, num_heads, rope=rope,
                             offset=qk_nope_head_dim, policy=policy)
    return remat.dear(q, "qkv")


@device_scope("mla_mixer")
def mla_apply(params, a, *, num_heads: int, kv_lora_rank: int,
              qk_nope_head_dim: int, norm_eps: float = 1e-6,
              policy: Policy = DEFAULT_POLICY, impl: Optional[str] = None,
              rope=None):
    """Causal latent attention, expanded: ``[c | k_s] = a W_kva``,
    ``[k_n | v] = rms(c) W_kvb`` a head, ``k_h = [k_n,h | k_s]`` (the
    shared channels the same for every head), ``q`` a head's channels
    in the same order, ``a W_q`` or, where the tree holds a query
    latent (``q_a``), ``rms(a W_qa) W_qb``. ``rope`` (tables
    ``(cos, sin)`` of ``qk_rope_head_dim`` columns, a row a position):
    decoupled rotary positions, the shared key channels turned once,
    before they are handed to the heads, and each query head's last
    ``rope`` channels; the ``nope`` channels and the values never turn.
    None: no position embedding on any channel. The core is
    ``mha_apply``'s, whose score heads and value heads may differ in
    width; the scale is ``1 / sqrt(nope + rope)``."""
    rows, seq, _ = a.shape
    with device_scope("attn_proj"):
        latent, shared = jnp.split(
            linear_apply(params["kv_a"], a, policy=policy), [kv_lora_rank],
            axis=-1)
        if rope is not None:
            # (one head of ``rope`` channels, half a vector of lanes
            # wide in the published shapes: XLA's, by ``fits``)
            shared = head_norm_rotary(shared, 1, rope=rope, policy=policy)
        kv = linear_apply(
            params["kv_b"],
            rms_norm_apply(params["kv_norm"], latent, norm_eps, policy),
            policy=policy).reshape(rows, seq, num_heads, -1)
        k = jnp.concatenate([
            kv[..., :qk_nope_head_dim],
            jnp.broadcast_to(shared[:, :, None, :],
                             (rows, seq, num_heads, shared.shape[-1]))], -1)
        v = kv[..., qk_nope_head_dim:]
        q_heads = None
        if "q_a" in params or rope is not None:
            q_heads = _mla_queries(params, a, num_heads, qk_nope_head_dim,
                                   rope, norm_eps, policy)
    tally_latent_call(
        f"{qk_nope_head_dim}+{shared.shape[-1]}"
        f"{'r' if rope is not None else ''}|{v.shape[-1]}"
        + (" query latent" if "q_a" in params else ""))
    return mha_apply(params, a, None, None, num_heads=num_heads,
                     kv_heads=(k.reshape(rows, seq, -1),
                               v.reshape(rows, seq, -1)),
                     q_heads=q_heads, causal=True, policy=policy, impl=impl)


@dataclasses.dataclass(frozen=True, kw_only=True)
class HybridLM:
    vocab_size: int
    hidden_size: int
    pattern: str                     # one of LAYER_KINDS a layer
    max_seq_len: int
    # M (a pattern without M needs none of them)
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    n_groups: int = 1
    ssm_state_size: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    # L (a pattern without L needs none of them): value head j reads
    # key head j // (value heads / key heads)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    delta_chunk_size: int = 64
    # K (a pattern without K needs none of them): heads of one width
    # for q, k and v alike; the chunk is delta_chunk_size
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel_size: int = 4
    # A (a pattern without A needs none of them): num_attention_heads
    # heads, score heads of nope + rope beside value heads of v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the query latent's rank; 0: q in one product. With rope_theta the
    # qk_rope_head_dim shared key channels and each query head's turn
    q_lora_rank: int = 0
    # D: the dense gated MLP's width (0: no such layer)
    intermediate_size: int = 0
    # *
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # rotary positions at this base (of * and of A's rope channels);
    # None: no position embedding
    rope_theta: Optional[float] = None
    # the share of a * head's channels, from the first, that the rotary
    # tables turn
    partial_rotary_factor: float = 1.0
    # an RMSNorm over each head's channels of the projected q and k
    qk_norm: bool = False
    # a head's query projected beside a gate; the core's output times
    # the gate's sigmoid before the output projection
    attn_output_gate: bool = False
    # E
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    # 0: no shared expert
    moe_shared_expert_intermediate_size: int = 0
    # relu2; gated: three matrices under a sigmoid gate of one column;
    # glu: the three matrices alone (ops/moe.SHARED_KINDS)
    shared_expert_kind: str = "relu2"
    routed_scaling_factor: float = 1.0
    router_scoring: str = "sigmoid"  # or softmax (ops/moe.SCORINGS)
    norm_topk_prob: bool = True      # the chosen scores over their sum
    # (silu(a Wg) * (a Wu)) Wd, three matrices; else relu(a Wu)^2 Wd
    gated_experts: bool = False
    # the experts held here, from first_expert on; None: all of them
    held_experts: Optional[int] = None
    first_expert: int = 0
    norm_eps: float = 1e-5
    # every RMSNorm of the stack as x / rms(x) * (1 + w), w from 0
    zero_centered_norms: bool = False
    # recompute every layer on the backward pass, but for the dear
    # values that fit the device (ops/remat.py)
    remat: bool = False
    # multi-token prediction modules after the stack (params["mtp"]:
    # one A and one E layer of the kinds above behind two norms and a
    # projection); 0: none. Depth 1 is what there is
    num_nextn_predict_layers: int = 0

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(LAYER_KINDS):
            raise ValueError(
                f"pattern {self.pattern!r}: one of {sorted(LAYER_KINDS)} a "
                "layer (M Mamba-2, E experts, * attention, L gated "
                "delta rule, K Kimi Delta Attention, A latent attention, "
                "D dense gated MLP)")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.mamba_num_heads % self.n_groups:
            raise ValueError("query heads divide over the key/value heads, "
                             "Mamba heads over the groups")
        if "L" in self.pattern and not (
                self.linear_num_key_heads and self.linear_key_head_dim
                and self.linear_value_head_dim
                and self.linear_num_value_heads
                and not self.linear_num_value_heads
                % self.linear_num_key_heads):
            raise ValueError(
                "a pattern with L needs linear_num_key_heads, "
                "linear_key_head_dim, linear_value_head_dim and "
                "linear_num_value_heads, a multiple of the key heads")
        if "K" in self.pattern and not (
                self.kda_num_heads and self.kda_head_dim):
            raise ValueError("a pattern with K needs kda_num_heads and "
                             "kda_head_dim")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"{self.num_nextn_predict_layers} prediction modules: "
                "one, or none")
        latent = "A" in self.pattern or self.num_nextn_predict_layers
        if latent and not (self.kv_lora_rank and self.qk_nope_head_dim
                           and self.v_head_dim):
            raise ValueError(
                "a pattern with A needs kv_lora_rank, qk_nope_head_dim and "
                "v_head_dim, and so does a prediction module")
        if latent and self.rope_theta is not None and (
                not self.qk_rope_head_dim or self.qk_rope_head_dim % 2):
            raise ValueError(
                f"rotary positions pair A's qk_rope_head_dim channels: "
                f"{self.qk_rope_head_dim} of them")
        if "D" in self.pattern and not self.intermediate_size:
            raise ValueError("a pattern with D needs intermediate_size")
        if "M" in self.pattern and not (
                self.mamba_num_heads and self.mamba_head_dim
                and self.ssm_state_size):
            raise ValueError("a pattern with M needs mamba_num_heads, "
                             "mamba_head_dim and ssm_state_size")
        held = self.num_held_experts
        if not 0 <= self.first_expert <= self.n_routed_experts - held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held} "
                f"are not among the router's {self.n_routed_experts}")

    @property
    def num_held_experts(self) -> int:
        return (self.n_routed_experts if self.held_experts is None
                else self.held_experts)

    def layer_names(self):
        """``00_ssm``, ``01_moe``, ...: the parameter tree's keys, in
        the pattern's order."""
        return [f"{i:02d}_{LAYER_KINDS[kind]}"
                for i, kind in enumerate(self.pattern)]

    def _mixer_init(self, key, kind: str):
        c = self.hidden_size
        if kind == "M":
            return ssm_mixer_init(
                key, c, num_heads=self.mamba_num_heads,
                head_dim=self.mamba_head_dim, n_groups=self.n_groups,
                state_size=self.ssm_state_size,
                conv_kernel=self.conv_kernel)
        if kind == "L":
            return delta_mixer_init(
                key, c, num_key_heads=self.linear_num_key_heads,
                num_value_heads=self.linear_num_value_heads,
                key_head_dim=self.linear_key_head_dim,
                value_head_dim=self.linear_value_head_dim,
                conv_kernel=self.linear_conv_kernel_dim)
        if kind == "K":
            return kda_mixer_init(
                key, c, num_heads=self.kda_num_heads,
                head_dim=self.kda_head_dim,
                conv_kernel=self.kda_conv_kernel_size)
        if kind == "A":
            return mla_init(
                key, c, self.num_attention_heads,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, q_lora_rank=self.q_lora_rank)
        if kind == "D":
            return gated_mlp_init(key, c, self.intermediate_size)
        if kind == "E":
            return moe_init(
                key, c, num_experts=self.n_routed_experts,
                held_experts=self.num_held_experts,
                expert_hidden=self.moe_intermediate_size,
                shared_hidden=self.moe_shared_expert_intermediate_size,
                gated=self.gated_experts,
                shared_kind=self.shared_expert_kind)
        return gqa_init(key, c, self.num_attention_heads,
                        self.num_key_value_heads, self.head_dim,
                        self.qk_norm, self.attn_output_gate,
                        self.zero_centered_norms)

    def _norm_init(self):
        return rms_norm_init(self.hidden_size,
                             zero_centered=self.zero_centered_norms)

    def init(self, key):
        ke, kl, kh = jax.random.split(key, 3)
        c = self.hidden_size
        keys = jax.random.split(kl, len(self.pattern))

        def layer(k, kind):
            return {"norm": self._norm_init(),
                    "mixer": self._mixer_init(k, kind)}

        params = {
            "embed": {"embed": trunc_normal_clamped(
                ke, (self.vocab_size, c), _INIT_STD)},
            "layers": {
                name: layer(k, kind)
                for name, kind, k in zip(self.layer_names(), self.pattern,
                                         keys)},
            "norm": self._norm_init(),
            "head": {"w": trunc_normal_clamped(
                kh, (c, self.vocab_size), _INIT_STD)},
        }
        if self.num_nextn_predict_layers:
            # a key of its own: the rest of the tree is the one a model
            # without the module draws
            kp, ka, kx = jax.random.split(jax.random.fold_in(key, 1), 3)
            params["mtp"] = {
                "enorm": self._norm_init(), "hnorm": self._norm_init(),
                "eh_proj": linear_init(kp, 2 * c, c, bias=False),
                **{LAYER_KINDS[kind]: layer(k, kind)
                   for kind, k in zip(MTP_KINDS, (ka, kx))},
                "norm": self._norm_init(),
            }
        return params

    def _layer(self, kind: str, policy: Policy, rope=None,
               block_diffusion=None):
        """``(layer_params, h, first) -> (h, load)`` of one kind;
        ``first`` is an expert layer's first held expert (None:
        ``first_expert``) and ``load`` its assignments a held expert,
        both None elsewhere. ``rope`` is the kind's own tables (a ``*``
        layer's, ``rotary_gqa_apply``; an ``A`` layer's rope channels',
        ``mla_apply``), ``block_diffusion`` the ``*`` layers'."""
        def layer(p, h, first=None):
            a = rms_norm_apply(p["norm"], h, self.norm_eps, policy)
            load = None
            if kind == "M":
                out = ssm_mixer_apply(
                    p["mixer"], a, num_heads=self.mamba_num_heads,
                    head_dim=self.mamba_head_dim, n_groups=self.n_groups,
                    state_size=self.ssm_state_size,
                    chunk_size=self.chunk_size, eps=self.norm_eps,
                    policy=policy)
            elif kind == "L":
                out = delta_mixer_apply(
                    p["mixer"], a, num_key_heads=self.linear_num_key_heads,
                    num_value_heads=self.linear_num_value_heads,
                    key_head_dim=self.linear_key_head_dim,
                    value_head_dim=self.linear_value_head_dim,
                    chunk_size=self.delta_chunk_size, eps=self.norm_eps,
                    policy=policy)
            elif kind == "K":
                out = kda_mixer_apply(
                    p["mixer"], a, num_heads=self.kda_num_heads,
                    head_dim=self.kda_head_dim,
                    chunk_size=self.delta_chunk_size, eps=self.norm_eps,
                    policy=policy)
            elif kind == "A":
                out = mla_apply(
                    p["mixer"], a, num_heads=self.num_attention_heads,
                    kv_lora_rank=self.kv_lora_rank,
                    qk_nope_head_dim=self.qk_nope_head_dim,
                    norm_eps=self.norm_eps, policy=policy, rope=rope)
            elif kind == "D":
                out = gated_mlp_apply(p["mixer"], a, policy)
            elif kind == "E":
                out, load = moe_apply(
                    p["mixer"], a, top_k=self.num_experts_per_tok,
                    first_expert=(self.first_expert if first is None
                                  else first),
                    scaling=self.routed_scaling_factor,
                    scoring=self.router_scoring,
                    renormalize=self.norm_topk_prob, policy=policy)
            else:
                out = rotary_gqa_apply(
                    p["mixer"], a, num_heads=self.num_attention_heads,
                    num_kv_heads=self.num_key_value_heads, policy=policy,
                    rope=rope, norm_eps=self.norm_eps,
                    block_diffusion=block_diffusion,
                    output_gate=self.attn_output_gate)
            return h + out, load

        return layer

    def _remat_keeps(self, layers, h):
        """The names every layer's checkpoint saves: one layer of each
        kind traced for its shapes alone says what its names would
        hold; ``choose_keeps`` takes the sum over ``layers`` (``(kind,
        layer, its parameters)`` an application: the stack's and a
        prediction module's) on one device against what the device has
        left."""
        held = dict.fromkeys(remat.HYBRID_REMAT_NAMES, 0)
        first = {kind: (layer, p) for kind, layer, p in reversed(layers)}
        count = collections.Counter(kind for kind, _, _ in layers)
        with untallied():
            for kind, (layer, p) in first.items():
                named = remat.named_bytes(
                    lambda p, x: layer(p, x)[0], p, h)
                for n in held:
                    held[n] += count[kind] * named[n]
        shards = data_shards(h)
        return remat.choose_keeps(
            {n: v // shards for n, v in held.items()},
            len(layers) * h.size * h.dtype.itemsize // shards,
            names=remat.HYBRID_REMAT_NAMES)

    def hidden_states(self, params, input_ids, *, first_experts=None,
                      policy: Policy = DEFAULT_POLICY, block_diffusion=None):
        """``(the final normed state (B, S, C) in the compute dtype,
        loads)``; ``loads`` (expert layers, held) int32: the
        assignments each held expert computed, a row an expert layer.
        ``first_experts`` (expert layers,) int32: each expert layer's
        first held expert, in ``first_expert``'s place.
        ``block_diffusion`` ``(L, B)``: ``input_ids`` are rows of ``2 L``
        positions, the noised copy beside the clean one, under the
        block-diffusion mask; index ``j`` has position ``j mod L``. A
        prediction module is not run."""
        h, _, loads = self._states(params, input_ids, None, first_experts,
                                   policy, block_diffusion)
        return h, loads

    def prediction_states(self, params, input_ids, next_ids, *,
                          first_experts=None,
                          policy: Policy = DEFAULT_POLICY):
        """``(h, z, loads)``: ``hidden_states``' state, and the
        prediction module's beside it (DeepSeek-V3, arXiv:2412.19437,
        section 2.2, depth 1)::

            u_i = [rms(E[next_ids_i]; w_e) | rms(h_i; w_h)] W_eh
            y = u + A(rms(u));  y = y + E_xp(rms(y));  z = rms(y; w_o)

        ``E`` the stack's own embedding table, ``A`` and ``E_xp`` a
        latent-attention and an expert layer of the stack's kinds with
        the module's weights (``params["mtp"]``), position ``i``'s
        rotary tables, and ``z`` read by the stack's own head:
        ``z_i`` predicts the id after ``next_ids_i``. ``loads`` and
        ``first_experts`` count the module's expert layer last."""
        if not self.num_nextn_predict_layers:
            raise ValueError("this model has no prediction module")
        return self._states(params, input_ids, next_ids, first_experts,
                            policy, None)

    def _states(self, params, input_ids, next_ids, first_experts,
                policy: Policy, block_diffusion):
        seq = positions = input_ids.shape[1]
        if block_diffusion is not None:
            positions = block_diffusion[0]
            if seq != 2 * positions:
                raise ValueError(f"{seq} positions for a block-diffusion "
                                 f"row of 2 x {positions}")
        if positions > self.max_seq_len:
            raise ValueError(f"{positions} positions, max_seq_len "
                             f"{self.max_seq_len}")
        tables = {}     # a kind's rotary tables, where it has any
        if self.rope_theta is not None and "*" in self.pattern:
            rope = rope_tables(
                positions, int(self.head_dim * self.partial_rotary_factor),
                self.rope_theta)
            if block_diffusion is not None:
                rope = tuple(np.concatenate([t, t]) for t in rope)
            # one array a table, which every layer is handed: a numpy
            # table is written into the step's text once a use (24 times
            # 4 MB in a six-layer step of 8,192 positions)
            tables["*"] = tuple(jnp.asarray(t) for t in rope)
        if self.rope_theta is not None and (
                "A" in self.pattern or next_ids is not None):
            tables["A"] = tuple(jnp.asarray(t) for t in rope_tables(
                positions, self.qk_rope_head_dim, self.rope_theta))
        with device_scope("input_adapter"):
            h = policy.cast_compute(params["embed"]["embed"][input_ids])
        layers = [(kind, self._layer(kind, policy, tables.get(kind),
                                     block_diffusion), params["layers"][name])
                  for name, kind in zip(self.layer_names(), self.pattern)]
        module = [] if next_ids is None else [
            (kind, self._layer(kind, policy, tables.get(kind)),
             params["mtp"][LAYER_KINDS[kind]]) for kind in MTP_KINDS]
        loads = []
        firsts = iter(() if first_experts is None else first_experts)

        def run(layers, h):
            for kind, layer, p in layers:
                if self.remat:
                    layer = jax.checkpoint(layer, policy=policy_fn)
                h, load = layer(p, h,
                                next(firsts, None) if kind == "E" else None)
                if load is not None:
                    loads.append(load)
            return h

        with device_scope("hybrid_stack"):
            if self.remat:
                policy_fn = jax.checkpoint_policies.save_only_these_names(
                    *self._remat_keeps(layers + module, h))
            h = rms_norm_apply(params["norm"], run(layers, h),
                               self.norm_eps, policy)
        z = None
        if module:
            with device_scope("mtp"):
                p = params["mtp"]
                e = policy.cast_compute(params["embed"]["embed"][next_ids])
                u = linear_apply(p["eh_proj"], jnp.concatenate([
                    rms_norm_apply(p["enorm"], e, self.norm_eps, policy),
                    rms_norm_apply(p["hnorm"], h, self.norm_eps, policy)],
                    -1), policy=policy)
                z = rms_norm_apply(p["norm"], run(module, u), self.norm_eps,
                                   policy)
        return h, z, (jnp.stack(loads) if loads else
                      jnp.zeros((0, self.num_held_experts), jnp.int32))

    def apply(self, params, input_ids, *, first_experts=None,
              policy: Policy = DEFAULT_POLICY, block_diffusion=None):
        """Dense logits ``(B, S, V)`` float32."""
        h, _ = self.hidden_states(params, input_ids,
                                  first_experts=first_experts, policy=policy,
                                  block_diffusion=block_diffusion)
        with device_scope("loss"):
            return linear_apply(params["head"], h,
                                policy=policy).astype(jnp.float32)
