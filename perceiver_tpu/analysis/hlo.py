"""StableHLO text walker: the shared parsing layer for the graph passes.

Everything operates on ``jitted.lower(...).as_text()`` — the
pre-optimization StableHLO module, which is platform-independent
(tracing/lowering needs no chip) and stable enough to gate on: matmul
operand dtypes, host-transfer custom calls, and input/output aliasing
are all decided at this level, before XLA's backend passes run.

Parsing is line-oriented regex, not an MLIR parser: the module text is
machine-generated with one op per line, and the three things the
passes need (dot shapes/dtypes, custom-call targets, the ``@main``
signature) are regular. If a jax upgrade changes the printing, the
self-verifying fixtures in ``tests/test_graphcheck.py`` fail loudly —
the failure mode is a test break, never a silently-passing gate.
"""

from __future__ import annotations

import hashlib
import re
from typing import Dict, Iterator, List, Optional, Tuple

# stablehlo.dot_general with optional batching_dims, capturing the
# contracting dims and the full (operands) -> result type signature
_DOT = re.compile(
    r"stablehlo\.dot_general.*?"
    r"contracting_dims = \[([0-9, ]*)\] x \[([0-9, ]*)\].*?"
    r": \(tensor<([^>]+)>, tensor<([^>]+)>\) -> tensor<([^>]+)>")

_CONV = re.compile(
    r"stablehlo\.convolution.*?"
    r": \(tensor<([^>]+)>, tensor<([^>]+)>\) -> tensor<([^>]+)>")

_CUSTOM_CALL = re.compile(r"stablehlo\.custom_call @([A-Za-z0-9_.]+)")

# Shardings print the Shardy way: one ``sdy.mesh @mesh = <["data"=2,
# "model"=2]>`` per module, and per tensor ``sdy.sharding =
# #sdy.sharding<@mesh, [{}, {"model"}]>`` — one ``{axes}`` per dim. The
# attr body nests braces and quotes, so it is cut out by depth
# (``_attrs_at``), never by a ``[^}]*`` regex, which would drop
# ``tf.aliasing_output`` on every sharded module.
_ARG = re.compile(r"%arg\d+: tensor<([^>]+)>(?: loc\([^)]*\))?")
_RESULT = re.compile(r"tensor<([^>]+)>")
_SDY_MESH = re.compile(r"sdy\.mesh @[\w.]+ = <\[([^\]]*)\]")
_SDY_MESH_AXIS = re.compile(r'"([^"]+)"=(\d+)')
_SDY_SHARDING = re.compile(r"#sdy\.sharding<@[\w.]+, (\[[^\]]*\])")
_SDY_AXIS = re.compile(r'"([^"]+)"(?::\(\d+\)(\d+))?')

# Ops that move data across the host↔device boundary, or host-compute
# offload markers. Python host callbacks (jax.debug.print, io_callback,
# pure_callback) all lower to custom calls named *callback*.
HOST_TRANSFER_MARKERS = (
    "stablehlo.infeed",
    "stablehlo.outfeed",
    "stablehlo.send",
    "stablehlo.recv",
    '_xla_compute_type = "host"',
)
_CALLBACK_RE = re.compile(r"custom_call @(\S*callback\S*)\(")


def parse_tensor(t: str) -> Tuple[List[int], str]:
    """``"512x64xbf16"`` → ``([512, 64], "bf16")``; scalars have []."""
    *dims, dtype = t.split("x")
    return [int(d) for d in dims], dtype


# byte widths of the element types the walkers price; anything exotic
# (future fp8 variants etc.) falls back to 4 so a new dtype can only
# OVER-count — budgets fail loudly instead of silently under-counting
_DTYPE_BYTES = {
    "pred": 1, "i1": 1, "s8": 1, "u8": 1, "i8": 1, "ui8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "i32": 4, "ui32": 4, "f32": 4,
    "s64": 8, "u64": 8, "i64": 8, "ui64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}


def tensor_bytes(t: str) -> int:
    """Byte size of a tensor type string (``"512x64xbf16"`` → 65536)."""
    dims, dtype = parse_tensor(t)
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES.get(dtype, 4)


def iter_dots(text: str) -> Iterator[dict]:
    """Yield one record per ``dot_general``: operand/result shapes,
    contraction depth K, operand dtype, and FLOPs (2·|out|·K)."""
    for m in _DOT.finditer(text):
        lhs_c = [int(x) for x in m.group(1).split(",") if x.strip()]
        lhs_dims, lhs_dt = parse_tensor(m.group(3))
        rhs_dims, rhs_dt = parse_tensor(m.group(4))
        out_dims, out_dt = parse_tensor(m.group(5))
        k = 1
        for d in lhs_c:
            k *= lhs_dims[d]
        out_elems = 1
        for d in out_dims:
            out_elems *= d
        yield {
            "op": "dot_general",
            "lhs": lhs_dims, "rhs": rhs_dims, "out": out_dims,
            "k": k, "dtype": lhs_dt, "rhs_dtype": rhs_dt,
            "out_dtype": out_dt,
            "flops": 2.0 * out_elems * k,
            "sig": f"({m.group(3)}, {m.group(4)}) -> {m.group(5)}",
        }


def iter_convs(text: str) -> Iterator[dict]:
    """Yield one record per ``convolution`` (dtype audit only — FLOP
    attribution for convs stays with XLA's cost analysis)."""
    for m in _CONV.finditer(text):
        lhs_dims, lhs_dt = parse_tensor(m.group(1))
        yield {
            "op": "convolution",
            "lhs": lhs_dims, "dtype": lhs_dt, "flops": None,
            "sig": f"({m.group(1)}, {m.group(2)}) -> {m.group(3)}",
        }


def dot_flop_summary(dots: List[dict], mxu_depth: int = 128) -> dict:
    """FLOP-weighted aggregates over ``iter_dots`` records: the MXU
    K-padding ceiling model and the bf16/fp32 FLOP split (the numbers
    ``dtype_policy`` gates on)."""
    total = sum(d["flops"] for d in dots) or 1.0
    ceiling = sum(d["flops"] * min(d["k"], mxu_depth) / mxu_depth
                  for d in dots) / total
    bf16 = sum(d["flops"] for d in dots if "bf16" in d["dtype"]) / total
    top = sorted(dots, key=lambda d: -d["flops"])[:8]
    return {
        "n_dot_general": len(dots),
        "total_dot_tflops_per_step": round(total / 1e12, 3),
        "flop_weighted_k_ceiling": round(ceiling, 4),
        "bf16_flop_fraction": round(bf16, 4),
        "top_dots": [{"lhs": d["lhs"], "out": d["out"], "k": d["k"],
                      "dtype": d["dtype"],
                      "flop_share": round(d["flops"] / total, 4)}
                     for d in top],
    }


def main_signature(text: str) -> str:
    """The ``func.func public @main(...)`` line — inputs, per-arg
    attributes (donation aliasing), and result types."""
    idx = text.find("@main(")
    if idx < 0:
        raise ValueError("lowered module has no public @main function")
    return text[idx:text.index("\n", idx)]


def _attrs_at(sig: str, i: int) -> str:
    """The body of the ``{...}`` attribute dict that starts at
    ``sig[i:]`` (after one space), or "" where there is none. Quoted
    spans are opaque; braces nest."""
    if not sig.startswith(" {", i):
        return ""
    depth, j, quoted = 0, i + 1, False
    while j < len(sig):
        c = sig[j]
        if c == '"':
            quoted = not quoted
        elif not quoted:
            if c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
                if depth == 0:
                    return sig[i + 2:j]
        j += 1
    return sig[i + 2:]


def _sharding_of(attrs: str) -> Optional[str]:
    m = _SDY_SHARDING.search(attrs)
    return m.group(1) if m else None


def main_args(text: str) -> List[dict]:
    """Per-argument records from the @main signature: tensor type,
    whether lowering aliased it onto an output (actual donation — the
    ``tf.aliasing_output`` attr jax emits for donated, shape-matched
    buffers; ``jax.buffer_donor`` marks donated-but-unmatched), and
    the per-dim axis list of its ``sdy.sharding`` (None when absent)."""
    sig = main_signature(text)
    # only the input side: results also print as tensor<...> {attrs}
    sig = sig.split(" -> ")[0]
    args = []
    for m in _ARG.finditer(sig):
        attrs = _attrs_at(sig, m.end())
        args.append({
            "type": m.group(1),
            "aliased": "tf.aliasing_output" in attrs,
            "donor_only": "jax.buffer_donor" in attrs,
            "sharding": _sharding_of(attrs),
        })
    return args


def main_results(text: str) -> List[dict]:
    """Per-result records from the @main signature: tensor type and
    the ``sdy.sharding`` dims sharded modules carry (None otherwise)."""
    sig = main_signature(text)
    _, _, results = sig.partition(" -> ")
    return [{"type": m.group(1),
             "sharding": _sharding_of(_attrs_at(results, m.end()))}
            for m in _RESULT.finditer(results)]


def mesh_axes(text: str) -> Dict[str, int]:
    """Axis sizes of the module's ``sdy.mesh`` ({} when unsharded)."""
    m = _SDY_MESH.search(text)
    if not m:
        return {}
    return {name: int(n) for name, n in _SDY_MESH_AXIS.findall(m.group(1))}


def sharding_factor(sharding: Optional[str], axes: Dict[str, int]) -> int:
    """Number of distinct shards a sharding's dims list splits a
    tensor into: 1 means fully replicated (every device holds the whole
    tensor). Absent or ``[{}, {}]`` → 1; ``[{"data"}, {"model"}]`` on a
    2×2 mesh → 4; a sub-axis ``"data":(1)2`` counts its own size."""
    factor = 1
    for name, sub in _SDY_AXIS.findall(sharding or ""):
        factor *= int(sub) if sub else axes[name]
    return factor


# ---------------------------------------------------------------------------
# Compiled-HLO collective walker.
#
# GSPMD inserts collectives during SPMD partitioning, which runs at
# COMPILE time — the pre-optimization StableHLO of a pjit program has
# sharding annotations but zero collective ops. The collective passes
# therefore parse ``lowered.compile().as_text()`` (optimized HLO text),
# which prints one op per line in the classic HLO syntax:
#
#   %all-reduce.1 = f32[256,256]{1,0} all-reduce(%x), channel_id=1,
#       replica_groups={{0,2},{1,3}}, use_global_device_ids=true, ...
#
# Replica groups come in two formats: explicit ``{{0,2},{1,3}}`` and
# iota ``[G,S]<=[dims]`` (optionally with a ``T(perm)`` transpose),
# meaning iota(prod(dims)) reshaped to ``dims``, transposed by
# ``perm``, flattened, and reshaped to G groups of S. collective-permute
# has ``source_target_pairs`` instead; its groups are the connected
# components of that edge list.

_HLO_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<ty>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?:-start)?\((?P<rest>.*)$",
    re.MULTILINE)
_HLO_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_REPLICA_EXPLICIT = re.compile(r"replica_groups=\{(\{[0-9,{}]*\})\}")
_REPLICA_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_SOURCE_TARGET = re.compile(r"source_target_pairs=\{([0-9,{}]*)\}")
_GROUP_BODY = re.compile(r"\{([0-9,]*)\}")


def _hlo_shape_bytes(ty: str) -> int:
    """Total bytes of an optimized-HLO result type; tuple types (async
    collectives, multi-operand all-to-all) sum their elements."""
    total = 0
    for m in _HLO_SHAPE.finditer(ty):
        n = _DTYPE_BYTES.get(m.group(1), 4)
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _iota_groups(g: int, s: int, dims: List[int],
                 perm: Optional[List[int]]) -> List[Tuple[int, ...]]:
    n = 1
    for d in dims:
        n *= d
    flat = list(range(n))
    if perm:
        # reshape to dims, transpose by perm, flatten
        strides = [1] * len(dims)
        for i in range(len(dims) - 2, -1, -1):
            strides[i] = strides[i + 1] * dims[i + 1]
        out = []
        idx = [0] * len(dims)
        pdims = [dims[p] for p in perm]
        def rec(depth, base_idx):
            if depth == len(pdims):
                off = sum(base_idx[perm[i]] * strides[perm[i]]
                          for i in range(len(perm)))
                out.append(flat[off])
                return
            for v in range(pdims[depth]):
                base_idx[perm[depth]] = v
                rec(depth + 1, base_idx)
        rec(0, idx)
        flat = out
    return [tuple(sorted(flat[i * s:(i + 1) * s])) for i in range(g)]


def _permute_groups(pairs_body: str) -> List[Tuple[int, ...]]:
    """Connected components of a collective-permute edge list."""
    parent: Dict[int, int] = {}
    def find(x: int) -> int:
        parent.setdefault(x, x)
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x
    for m in _GROUP_BODY.finditer(pairs_body):
        ids = [int(v) for v in m.group(1).split(",") if v]
        if len(ids) == 2:
            parent[find(ids[0])] = find(ids[1])
    comps: Dict[int, List[int]] = {}
    for x in parent:
        comps.setdefault(find(x), []).append(x)
    return [tuple(sorted(v)) for v in comps.values()]


def iter_collectives(compiled_text: str) -> Iterator[dict]:
    """Yield one record per collective op in optimized HLO text:
    ``{"op", "bytes", "groups", "line"}``. ``bytes`` is the result-type
    byte size (tuple elements summed); ``groups`` is a list of sorted
    device-id tuples (empty when the op prints no groups — a
    single-partition degenerate)."""
    for m in _HLO_COLLECTIVE.finditer(compiled_text):
        rest = m.group("rest")
        groups: List[Tuple[int, ...]] = []
        ex = _REPLICA_EXPLICIT.search(rest)
        it = _REPLICA_IOTA.search(rest)
        st = _SOURCE_TARGET.search(rest)
        if ex:
            groups = [tuple(sorted(int(v) for v in g.group(1).split(",")
                                   if v))
                      for g in _GROUP_BODY.finditer(ex.group(1))]
        elif it:
            g, s = int(it.group(1)), int(it.group(2))
            dims = [int(d) for d in it.group(3).split(",")]
            perm = ([int(p) for p in it.group(4).split(",")]
                    if it.group(4) else None)
            groups = _iota_groups(g, s, dims, perm)
        elif st:
            groups = _permute_groups(st.group(1))
        yield {
            "op": m.group("op"),
            "bytes": _hlo_shape_bytes(m.group("ty")),
            "groups": groups,
            "line": m.group(0).strip()[:200],
        }


def _axis_groups(shape: List[int], axes: List[int]) -> frozenset:
    """Replica groups of a collective over the given mesh-axis subset,
    assuming iota device order (how ``make_mesh`` lays devices out):
    fix the other axes, vary ``axes``."""
    strides = [1] * len(shape)
    for i in range(len(shape) - 2, -1, -1):
        strides[i] = strides[i + 1] * shape[i + 1]
    fixed = [i for i in range(len(shape)) if i not in axes]
    groups: List[Tuple[int, ...]] = []

    def rec_fixed(idx: int, base: int) -> None:
        if idx == len(fixed):
            group: List[int] = []

            def rec_var(jdx: int, off: int) -> None:
                if jdx == len(axes):
                    group.append(base + off)
                    return
                a = axes[jdx]
                for v in range(shape[a]):
                    rec_var(jdx + 1, off + v * strides[a])

            rec_var(0, 0)
            groups.append(tuple(sorted(group)))
            return
        i = fixed[idx]
        for v in range(shape[i]):
            rec_fixed(idx + 1, base + v * strides[i])

    rec_fixed(0, 0)
    return frozenset(groups)


def attribute_axis(groups: List[Tuple[int, ...]], mesh_shape: List[int],
                   axis_names: List[str]) -> str:
    """Label a collective's replica groups with the smallest mesh-axis
    subset whose iota-order groups match exactly: ``"data"``,
    ``"model"``, ``"data+model"``, … — or ``"other"`` when no subset
    reproduces the groups (a manual collective or a permute ring that
    does not follow mesh axes)."""
    from itertools import combinations

    key = frozenset(tuple(sorted(g)) for g in groups)
    for r in range(1, len(mesh_shape) + 1):
        for combo in combinations(range(len(mesh_shape)), r):
            if _axis_groups(mesh_shape, list(combo)) == key:
                return "+".join(axis_names[i] for i in combo)
    return "other"


def count_host_markers(text: str) -> Dict[str, int]:
    """Occurrences of each host-transfer marker in the module text.
    Callback custom calls are counted under their call-target name."""
    counts: Dict[str, int] = {}
    for marker in HOST_TRANSFER_MARKERS:
        n = text.count(marker)
        if n:
            counts[marker] = n
    for m in _CALLBACK_RE.finditer(text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def custom_call_targets(text: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for m in _CUSTOM_CALL.finditer(text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def module_fingerprint(text: str) -> str:
    """Stable fingerprint of the module's compilation-cache-relevant
    interface: the @main input/result signature (shapes + dtypes +
    donation layout). Two lowerings of "the same" step that disagree
    here WILL be two compile-cache entries on the chip."""
    return hashlib.sha256(main_signature(text).encode()).hexdigest()[:16]


def text_hash(text: str) -> str:
    """Hash of the FULL module text — the persistent executable
    cache's key material (``perceiver_tpu/cache``). Stricter than
    ``module_fingerprint``: trace-time leakage into the graph *body*
    (a timestamp constant, a host-RNG draw, an id() in a name) changes
    this hash while leaving the @main signature intact — and silently
    zeroes the cache hit rate."""
    return hashlib.sha256(text.encode()).hexdigest()
