"""A looped language model: one stack of causal decoder layers applied
``total_ut_steps`` times with the same weights, the head reading the
state after every pass, and a learned exit gate that turns the passes'
readings into one loss (Zhu et al., "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741; the Ouro family).

One layer, on a row ``h`` of ``S`` positions (sandwich norms: four
RMSNorms a layer, no biases anywhere in it)::

    a = rms1(h);  q, k, v = a Wq, a Wk, a Wv
    q, k = rope(q), rope(k)
    o = softmax(q k^T / sqrt(D) + causal) v
    h = h + rms2(o Wo)
    m = rms3(h);  h = h + rms4((silu(m Wg) * (m Wu)) Wd)

The loop, the **same** parameters in every pass::

    h0 = E[ids]
    for t = 1..T:  ht = rms_f(Layers(h(t-1)))   # the normed state feeds
                   logits_t = ht Wh              # the next pass
                   lam_t = sigmoid(ht wg + bg)
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j)
    p_T = prod_{j<T}(1 - lam_j)

The layers' parameters are stacked on a leading axis and scanned (one
compiled layer body, as the Perceiver encoder's ``selfs``); the passes
are a second scan over the same tree, so the gradient of every layer's
weights is the sum over the passes and the optimizer holds one copy.
With ``remat`` every layer application is recomputed on the backward
pass from what the forward saved of it: its input, ``T x L`` states of
``(B, S, C)`` in the compute dtype (and each pass's state before the
final norm), and the dear values it names (``ops/remat.py``: the fused
core's float32 output and log-sum-exp first, then the projections'
product, then the MLP's two) as far as they fit the device beside what
it already holds, chosen by the reckoning the Perceiver encoder uses
(``LoopedLM._remat_keeps``). A kept value is not computed again; the
rest of a layer (norms, rope, residual sums, the products not kept)
is.

``remat`` is a hand-written backward pass (``_loop_stack``), not
``jax.checkpoint`` under autodiff, for the memory's sake: the
transpose of a scan inside a scan keeps the stacked float32 gradient
of the layers several times over (the outer scan's running sum, the
inner scan's stacked output, their sum), and float32 parameters that a
while loop carries are copied for it when the step donates them: 1.1
GB of temporaries a layer at the published widths, where one copy of
the gradient is 0.2, and the step no longer fits the chip it was sized
for. The backward here walks the ``T x L`` applications in reverse and
adds each one's gradient into one stacked accumulator in place; the
loops carry the matrices in the compute dtype, cast once. Only an
application's input and its kept values go through the stacks: the
vjp of an application is built on the backward pass
(``remat.vjp_handing``), where its parameters are a slice of the
stack that is there anyway (built on the forward pass, it would count
them among its residuals, a copy a pass and a layer).

Training never materialises the ``(T, B, S, V)`` logits: the task
takes ``hidden_states`` and reads the head through
``ops.fused_ce.fused_linear_nll``. ``apply`` gives the dense logits
for tests, prediction and small sizes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops import remat
from perceiver_tpu.ops.attention import (
    data_shards,
    mha_apply,
    mha_init,
    untallied,
)
from perceiver_tpu.ops.fourier import rope_tables
from perceiver_tpu.ops.initializers import trunc_normal_clamped
from perceiver_tpu.ops.linear import linear_apply
from perceiver_tpu.ops.mlp import gated_mlp_apply, gated_mlp_init
from perceiver_tpu.ops.norm import rms_norm_apply, rms_norm_init
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy

_INIT_STD = 0.02
# the cores a causal call can take (ops/attention.mha_apply)
CAUSAL_ATTENTION_IMPLS = (None, "einsum", "flash")


def decoder_layer_init(key, dim: int, num_heads: int, hidden: int):
    ka, km = jax.random.split(key)
    return {
        "attn_norm_in": rms_norm_init(dim),
        "attn": mha_init(ka, dim, num_heads, bias=False),
        "attn_norm_out": rms_norm_init(dim),
        "mlp_norm_in": rms_norm_init(dim),
        "mlp": gated_mlp_init(km, dim, hidden),
        "mlp_norm_out": rms_norm_init(dim),
    }


@device_scope("decoder_layer")
def decoder_layer_apply(params, h, *, num_heads: int, rope, eps: float,
                        policy: Policy = DEFAULT_POLICY,
                        impl: Optional[str] = None):
    a = rms_norm_apply(params["attn_norm_in"], h, eps, policy)
    o = mha_apply(params["attn"], a, a, a, num_heads=num_heads,
                  causal=True, rope=rope, policy=policy, impl=impl)
    h = h + rms_norm_apply(params["attn_norm_out"], o, eps, policy)
    m = rms_norm_apply(params["mlp_norm_in"], h, eps, policy)
    m = gated_mlp_apply(params["mlp"], m, policy=policy)
    return h + rms_norm_apply(params["mlp_norm_out"], m, eps, policy)


# --- the passes, with a backward pass that adds in place --------------------


def _cast_matrices(policy: Policy, layers):
    """The stacked projection matrices in the compute dtype, cast once
    outside both loops (``linear_apply``'s own cast is then a no-op);
    the stacked norm scales stay as they are: RMSNorm reads them in
    fp32."""
    return jax.tree.map(
        lambda x: policy.cast_param(x) if x.ndim >= 3 else x, layers)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _loop_stack(layer_fn, norm_fn, passes, policy, kept, layers, norm, h):
    """``passes`` times ``norm_fn(norm, scan(layer_fn over layers))``,
    each pass fed the one before: the normed state of every pass,
    ``(passes, *h.shape)``. ``layer_fn(layer_params, h)`` and
    ``norm_fn(norm_params, h)`` close over nothing traced. ``kept``:
    the names (``ops/remat.py``) whose values the backward is handed
    beside each application's input; it changes no value."""
    return _loop_stack_fwd(layer_fn, norm_fn, passes, policy, kept, layers,
                           norm, h)[0]


def _loop_stack_fwd(layer_fn, norm_fn, passes, policy, kept, layers, norm,
                    h):
    # the float32 parameters are read here and nowhere else: a donated
    # buffer that a while loop carries is copied for it
    compute = _cast_matrices(policy, layers)
    num_layers = jax.tree.leaves(compute)[0].shape[0]
    # What the backward is handed of every application, its input and
    # its kept values: one stack a value over all ``passes x layers``
    # applications, carried through both loops and written by slice.
    # Left to the loops' own stacked outputs a value is copied again
    # and again (a layer's into its pass's stack, a pass's stack into
    # the stack of all, and back the same way): 27 ms a step for
    # 2.2 GB where this takes 14 (PERF.md, PR 32).
    with untallied():
        saved = jax.eval_shape(
            lambda p, x: (x, remat.taking(kept, layer_fn, p, x)[1]),
            remat.layer_shapes(compute), h)
    stacks = jax.tree.map(
        lambda a: jnp.zeros((passes * num_layers, *a.shape), a.dtype), saved)

    def one_pass(carry, t):
        def layer(carry, xs):
            h, stacks = carry
            index, layer_params = xs
            out, values = remat.taking(kept, layer_fn, layer_params, h)
            stacks = jax.tree.map(
                lambda stack, x: jax.lax.dynamic_update_index_in_dim(
                    stack, x, t * num_layers + index, 0),
                stacks, (h, values))
            return (out, stacks), None

        (x, stacks), _ = jax.lax.scan(
            layer, carry, (jnp.arange(num_layers), compute))
        out = norm_fn(norm, x)
        return (out, stacks), (out, x)

    (_, stacks), (states, before_norm) = jax.lax.scan(
        one_pass, (h, stacks), jnp.arange(passes))
    return states, (compute, norm, stacks, before_norm)


def _loop_stack_bwd(layer_fn, norm_fn, passes, policy, kept, res,
                    ct_states):
    del kept    # what was kept is in the stacks
    compute, norm, stacks, before_norm = res
    num_layers = jax.tree.leaves(compute)[0].shape[0]

    def pass_bwd(carry, xs):
        g_layers, g_norm, ct_next = carry
        t, ct_state, before_norm_t = xs

        def layer_bwd(carry, xs):
            g_layers, ct = carry
            index, layer_params = xs
            h_in, values = jax.tree.map(
                lambda stack: jax.lax.dynamic_index_in_dim(
                    stack, t * num_layers + index, 0, keepdims=False),
                stacks)
            g, ct = remat.vjp_handing(values, layer_fn, layer_params,
                                      h_in)(ct)
            # one stacked fp32 accumulator, added into in place: a
            # slice read, added to and written back (``acc.at[index]
            # .add`` lowers to a scatter that passes over the whole
            # stack every time)
            g_layers = jax.tree.map(
                lambda acc, x: jax.lax.dynamic_update_index_in_dim(
                    acc, jax.lax.dynamic_index_in_dim(
                        acc, index, 0, keepdims=False)
                    + x.astype(acc.dtype), index, 0),
                g_layers, g)
            return (g_layers, ct), None

        # a pass's state is read by the head and the gate and feeds
        # the next pass
        _, vjp = jax.vjp(norm_fn, norm, before_norm_t)
        g, ct = vjp(ct_state + ct_next)
        g_norm = jax.tree.map(jnp.add, g_norm, g)
        (g_layers, ct), _ = jax.lax.scan(
            layer_bwd, (g_layers, ct), (jnp.arange(num_layers), compute),
            reverse=True)
        return (g_layers, g_norm, ct), None

    def zeros(tree):
        return jax.tree.map(lambda x: jnp.zeros(x.shape, policy.param_dtype),
                            tree)

    (g_layers, g_norm, ct_h), _ = jax.lax.scan(
        pass_bwd, (zeros(compute), zeros(norm),
                   jnp.zeros_like(ct_states[0])),
        (jnp.arange(passes), ct_states, before_norm), reverse=True)
    return g_layers, g_norm, ct_h


_loop_stack.defvjp(_loop_stack_fwd, _loop_stack_bwd)


def _plain_loop_stack(layer_fn, norm_fn, passes, layers, norm, h):
    """The same loop under plain autodiff (``remat`` off): every
    activation of every application is kept."""
    def one_pass(h, _):
        h, _ = jax.lax.scan(lambda h, p: (layer_fn(p, h), None), h, layers)
        h = norm_fn(norm, h)
        return h, h

    return jax.lax.scan(one_pass, h, None, length=passes)[1]


@device_scope("exit_gate")
def exit_distribution(gate_logits):
    """The exit distribution over the passes from the gate's logits
    ``(T, ...)``: ``p_t = lam_t prod_{j<t}(1 - lam_j)``, the last pass
    taking what is left. fp32, in logs: ``log lam = log_sigmoid(z)``,
    ``log(1 - lam) = log_sigmoid(-z)``. Returns ``(p, log p)``, each
    ``(T, ...)``; ``p`` sums to 1 over the passes."""
    z = gate_logits.astype(jnp.float32)
    log_stay = jax.nn.log_sigmoid(-z)
    before = jnp.cumsum(log_stay, axis=0) - log_stay   # sum over j < t
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(z[:-1]) + before[:-1], before[-1:]], axis=0)
    return jnp.exp(log_p), log_p


@dataclasses.dataclass(frozen=True)
class LoopedLM:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    head_dim: int
    intermediate_size: int
    max_seq_len: int
    total_ut_steps: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # recompute every layer application on the backward pass, but for
    # the dear values that fit the device (ops/remat.py)
    remat: bool = False
    # None picks the attention core per call site; "einsum"/"flash"
    # force one (ops/attention.py)
    attention_impl: Optional[str] = None

    def __post_init__(self):
        if self.num_heads * self.head_dim != self.hidden_size:
            # the published family has heads x head_dim == hidden; the
            # projections here are square
            raise ValueError(
                f"{self.num_heads} heads of {self.head_dim} are not the "
                f"hidden size {self.hidden_size}")
        if self.attention_impl not in CAUSAL_ATTENTION_IMPLS:
            raise ValueError(
                f"attention_impl {self.attention_impl!r} is not one of "
                f"{CAUSAL_ATTENTION_IMPLS}: causal attention runs on the "
                "fused or the materialized core")

    def init(self, key):
        ke, kl, kh, kg = jax.random.split(key, 4)
        c = self.hidden_size
        layers = jax.vmap(lambda k: decoder_layer_init(
            k, c, self.num_heads, self.intermediate_size))(
                jax.random.split(kl, self.num_layers))
        return {
            "embed": {"embed": trunc_normal_clamped(
                ke, (self.vocab_size, c), _INIT_STD)},
            "layers": layers,
            "norm": rms_norm_init(c),
            "head": {"w": trunc_normal_clamped(
                kh, (c, self.vocab_size), _INIT_STD)},
            "gate": {"w": trunc_normal_clamped(kg, (c, 1), _INIT_STD),
                     "b": jnp.zeros((1,), jnp.float32)},
        }

    def _remat_keeps(self, layer, layers, h):
        """The names whose values the backward is handed, as
        ``PerceiverEncoder._remat_policy`` chooses its save list: one
        layer application differentiated for its shapes alone says what
        its names would hold; ``choose_keeps`` takes the bytes of all
        ``total_ut_steps x num_layers`` applications on one device
        against what the device has left."""
        with untallied():
            named = remat.named_bytes(layer, remat.layer_shapes(layers), h)
        count, shards = self.total_ut_steps * self.num_layers, data_shards(h)
        held = {name: count * named[name] // shards
                for name in remat.REMAT_NAMES}
        return remat.choose_keeps(
            held, count * h.size * h.dtype.itemsize // shards)

    def hidden_states(self, params, input_ids, *,
                      policy: Policy = DEFAULT_POLICY):
        """The normed state after every pass, ``(T, B, S, C)`` in the
        compute dtype."""
        seq = input_ids.shape[1]
        if seq > self.max_seq_len:
            raise ValueError(f"{seq} positions, max_seq_len "
                             f"{self.max_seq_len}")
        rope = rope_tables(seq, self.head_dim, float(self.rope_theta))
        with device_scope("input_adapter"):
            h = policy.cast_compute(params["embed"]["embed"][input_ids])

        def layer(layer_params, h):
            return decoder_layer_apply(
                layer_params, h, num_heads=self.num_heads, rope=rope,
                eps=self.rms_norm_eps, policy=policy,
                impl=self.attention_impl)

        def final_norm(norm_params, h):
            return rms_norm_apply(norm_params, h, self.rms_norm_eps, policy)

        with device_scope("loop_stack"):
            if not self.remat:
                return _plain_loop_stack(
                    layer, final_norm, self.total_ut_steps, params["layers"],
                    params["norm"], h)
            kept = self._remat_keeps(layer, params["layers"], h)
            return _loop_stack(layer, final_norm, self.total_ut_steps,
                               policy, kept, params["layers"],
                               params["norm"], h)

    @device_scope("exit_gate")
    def gate_logits(self, params, states):
        """``ht wg + bg`` for every pass, ``(T, B, S)`` fp32."""
        w = params["gate"]["w"].astype(states.dtype)
        z = jnp.einsum("tbsc,co->tbso", states, w,
                       preferred_element_type=jnp.float32)[..., 0]
        return z + params["gate"]["b"].astype(jnp.float32)

    def apply(self, params, input_ids, *,
              policy: Policy = DEFAULT_POLICY):
        """Dense reading of every pass: ``(logits (T, B, S, V) fp32,
        exit probabilities (T, B, S) fp32)``."""
        states = self.hidden_states(params, input_ids, policy=policy)
        with device_scope("exit_loss"):
            logits = linear_apply(params["head"], states, policy=policy)
        p, _ = exit_distribution(self.gate_logits(params, states))
        return logits.astype(jnp.float32), p
