"""Source-level (AST) linter with JAX-specific rules.

The graph passes catch what made it into the lowered module; these
rules catch what never should have been written — host syncs and
Python-time effects inside traced code, numpy/jax.numpy mixing in ops
code, and enum-like config fields without config-time validation.

Rules (names are the ``check`` field of emitted violations):

``jit-host-sync``
    Inside jit-traced functions: ``.item()`` calls, ``float()``/
    ``int()``/``bool()`` applied to traced function parameters, and
    ``np.*`` calls (which force the tracer to concretize — a trace
    error at best, a silent host round-trip at worst).

``jit-python-rng-time``
    ``time.*``, ``random.*``, ``np.random.*``, ``datetime.*.now`` calls
    inside jit-traced functions: they run once at trace time and
    freeze into the compiled graph as constants.

``ops-numpy-mix``
    A module under ``perceiver_tpu/ops/`` importing both ``numpy`` and
    ``jax.numpy`` at top level. Host-side precompute belongs in
    np-only modules (see ``ops/fourier.py``); traced code in jnp-only
    modules — one module doing both is where np-on-traced-values bugs
    breed.

``impl-field-validation``
    A dataclass field named ``*_impl`` (the repo's string-enum
    convention) whose defining class has no domain validation in
    ``__post_init__``. The canonical form is
    ``if self.<field> not in <valid set>: raise`` — a positive ``in``
    test conjoined with other conditions (e.g. the dropout-support
    guards) is a feature check, not domain validation, and does not
    count. An unvalidated value fails deep inside a jit trace instead
    of at config time (ADVICE r5 on ``tasks/base.py``).

``uncached-compile``
    A raw AOT compile — ``.lower(...).compile()`` chained, or
    ``x.compile()`` where ``x`` was assigned from a ``.lower(...)``
    call — anywhere outside ``perceiver_tpu/cache/``. Every AOT
    compile is supposed to flow through the persistent executable
    cache (``perceiver_tpu.cache.aot_compile``/``compile_lowered``)
    so warm starts can deserialize instead of recompiling; a raw
    compile silently opts its call site out. Diagnostics that
    intentionally measure compilation suppress per line with a
    reason.

``silent-swallow``
    Broad exception handlers that discard the failure: a bare
    ``except:`` (it also eats ``KeyboardInterrupt``/``SystemExit``),
    or an ``except Exception``/``except BaseException`` whose body is
    only ``pass``/``...``. Silently swallowed errors are how a
    production system loses data without logging a byte
    (docs/RESILIENCE.md) — every such handler must either narrow the
    exception type, handle it visibly, or carry a reason comment on
    the ``except``/``pass`` line explaining why discarding is correct.

``serving-host-sync``
    Device synchronization inside ``serving/engine.py``: ``.item()``,
    ``.tolist()``, ``.block_until_ready()``, ``jax.device_get``, and
    numpy conversion calls (``np.asarray``/``np.array``/``np.copy``/
    ``np.ascontiguousarray``) anywhere in the engine module. The
    engine's dispatch path must stay sync-free so dispatches pipeline
    like train steps; materializing results — and timing them —
    belongs to the consumer layer (``serving/api.py``, the batcher).
    Scoped to the whole engine module on purpose: a sync in a helper
    called from dispatch stalls the pipeline exactly the same way.

``unsharded-pjit``
    A ``jax.jit``/``pjit`` call or decorator inside the SPMD code
    paths (modules under ``perceiver_tpu/parallel/`` and
    ``perceiver_tpu/training/spmd.py``) that omits explicit
    ``in_shardings`` or ``out_shardings``. Silent sharding propagation
    is how replication sneaks in: GSPMD happily materializes an
    unconstrained operand fully replicated, and nothing fails until a
    real slice runs out of HBM — declare the layout at every pjit
    boundary and let ``replication_check`` verify what lowering did
    with it. Single-device jits that truly have no layout (rare in
    these modules) suppress per line with a reason.

``metrics-conventions``
    Prometheus naming discipline at every metric registration site —
    a ``.counter("name", ...)``/``.gauge(...)``/``.histogram(...)``
    call with a string-literal name. Names must be snake_case with a
    plane prefix (``serving_``/``training_``/``fleet_``) so one fleet
    exposition can merge replica, router, and trainer series without
    collisions; counters must end ``_total`` (the exposition suffix
    convention scrapers and recording rules key on) and gauges/
    histograms must not (``_total`` on a non-counter misleads every
    rate() written against it). Misnamed metrics don't fail at
    registration — they fail months later in dashboards that filter
    on the suffix.

``router-blocking-io``
    Blocking socket I/O without a deadline inside the fleet's
    router/replica hot paths (modules under ``perceiver_tpu/fleet/``):
    a ``.recv``/``.recv_into``/``.recvfrom``/``.accept`` call whose
    receiver never gets a ``.settimeout(...)`` in the same module, or
    a ``socket.create_connection`` without a ``timeout`` argument. A
    bare blocking read turns one stalled replica into a hung router
    thread — the failover contract (retry-on-sibling under a deadline,
    docs/SERVING.md "Fleet") requires every socket operation to be
    able to time out.

``distributed-blocking-io``
    The multi-host discipline (modules under
    ``perceiver_tpu/distributed/``): the router rule's socket checks,
    PLUS argument-less barrier-style waits — ``.wait()`` / ``.join()``
    / ``.get()`` / ``.acquire()`` with no positional argument and no
    ``timeout=`` keyword. A process group's failure mode is the
    unbounded collective wait (a dead member wedges every survivor),
    so every rendezvous, queue pop, thread join, and lock acquire in
    the distributed layer must carry an explicit deadline the group
    supervisor can act on (docs/RESILIENCE.md "Multi-host"). Calls
    with any positional argument pass (``d.get(key)``,
    ``done.wait(5)``); a genuinely-unbounded wait that is safe
    suppresses per line with a reason. The same check name also
    covers Condition hygiene in ``serving/`` and ``fleet/``: a
    ``.wait()`` with no timeout on an attribute assigned from
    ``threading.Condition(...)`` is flagged there too — a missed
    notify (e.g. a producer dying between append and notify) wedges
    the waiter forever, so every condition wait must be a
    predicate loop with a bounded wait.

``blocking-under-lock``
    Blocking work while a lock is held, in the concurrent host-side
    packages (``serving/``, ``fleet/``, ``distributed/``): inside a
    ``with <something named *lock*>:`` frame (or a ``with`` on a
    ``threading.Condition`` attribute, which acquires its lock), flag
    ``time.sleep``, ``pickle.dumps/loads/dump/load``,
    ``subprocess.run/Popen/check_*/call``, socket operations
    (``send``/``sendall``/``recv*``/``accept``/``connect``), builtin
    ``open()``, and the fleet framing wrappers ``send_msg`` /
    ``recv_msg``. Work done under a lock serializes every thread that
    touches that lock — a slow pickle under the router lock stalls
    all routing, and socket IO under a lock is the PR-5 breaker
    deadlock shape one hop away. Move the blocking work outside the
    critical section (snapshot under the lock, do IO after release),
    or suppress per line with a reason when holding the lock IS the
    protocol (e.g. one-in-flight-per-connection RPC framing).

``kv-alias``
    A direct functional page write — ``X.at[...].set(...)`` / ``.add``
    / any other ``.at`` update method — in a module under
    ``perceiver_tpu/serving/`` other than ``serving/decode.py`` or
    ``serving/prefix_cache.py``. With content-addressed prefix caching
    (ISSUE 18) a KV page in the paged arena may be aliased by many
    streams and by the prefix index; the copy-on-write discipline
    (``ensure_private_page`` before any write) lives entirely in those
    two modules, and a page write anywhere else in the serving layer
    bypasses it — silently corrupting every other stream sharing the
    page. Genuinely non-arena ``.at`` updates in serving code suppress
    per line with a reason.

``tenant-label-discipline``
    Metric label sites (``.labels(...)``) and typed event emissions
    (``emit("...", ...)``) in the multi-tenant planes — ``fleet/``,
    ``serving/decode.py``, ``serving/batcher.py`` — without a
    ``tenant=`` keyword. Noisy-neighbor isolation is only *provable*
    if every observability series in the shared-pool path attributes
    its samples to a tenant (docs/OBSERVABILITY.md "Tenant labels");
    an unlabeled series silently merges all tenants and hides exactly
    the starvation the quotas exist to prevent. Series that are
    genuinely tenant-free (per-replica breaker gauges, aggregate
    outcome counters that a tenant-split sibling series covers)
    suppress per line with a reason naming the covering series.

Tracing detection is local and conservative: functions decorated with
``jax.jit`` / ``partial(jax.jit, ...)``, functions passed to a
``jax.jit(...)`` call anywhere in the module, and everything nested
inside them. Cross-module propagation (a jitted caller invoking a
helper from another file) is out of scope — the graph passes cover
that end via the lowered module itself.

Suppress any finding by putting ``graphcheck: ignore`` in a comment on
the offending line.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set

from perceiver_tpu.analysis.report import Report, Violation

SUPPRESS_MARKER = "graphcheck: ignore"

_TIME_CALLS = {"time", "perf_counter", "monotonic", "time_ns",
               "perf_counter_ns", "monotonic_ns", "process_time"}
# attribute accesses that read static metadata, not traced values
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize"}


def _is_jit_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "jit"
    if isinstance(node, ast.Attribute):
        return node.attr == "jit"
    return False


def _is_partial_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "partial"
    if isinstance(node, ast.Attribute):
        return node.attr == "partial"
    return False


def _is_jit_decorator(dec: ast.AST) -> bool:
    if _is_jit_expr(dec):
        return True
    if isinstance(dec, ast.Call):
        if _is_jit_expr(dec.func):
            return True
        if _is_partial_expr(dec.func):
            return any(_is_jit_expr(a) for a in dec.args)
    return False


def _attr_root(node: ast.AST) -> Optional[str]:
    """``np.random.normal`` → ``"np"``; bare names → the name."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_chain(node: ast.AST) -> List[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return list(reversed(parts))


class _Imports(ast.NodeVisitor):
    """Module alias map for the handful of modules the rules care
    about. ``top_level`` records what the module imports at its top
    scope (for the ops mixing rule)."""

    def __init__(self):
        self.numpy: Set[str] = set()
        self.jnp: Set[str] = set()
        self.time: Set[str] = set()
        self.random: Set[str] = set()
        self.datetime: Set[str] = set()
        self.top_level: Set[str] = set()
        self._depth = 0

    def visit_FunctionDef(self, node):
        self._depth += 1
        self.generic_visit(node)
        self._depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def _record(self, module: str, alias: str) -> None:
        bucket = {"numpy": self.numpy, "jax.numpy": self.jnp,
                  "time": self.time, "random": self.random,
                  "datetime": self.datetime}.get(module)
        if bucket is not None:
            bucket.add(alias)
            if self._depth == 0:
                self.top_level.add(module)

    def visit_Import(self, node):
        for a in node.names:
            self._record(a.name, a.asname or a.name.split(".")[0])

    def visit_ImportFrom(self, node):
        if node.module == "jax":
            for a in node.names:
                if a.name == "numpy":
                    self._record("jax.numpy", a.asname or "numpy")


def _jit_called_names(tree: ast.AST) -> Set[str]:
    """Function names passed to a ``jax.jit(fn, ...)``-style call."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_expr(node.func) \
                and node.args and isinstance(node.args[0], ast.Name):
            names.add(node.args[0].id)
    return names


def _traced_param_names(node: ast.AST) -> Iterable[str]:
    a = node.args
    for arg in (a.posonlyargs + a.args + a.kwonlyargs
                + ([a.vararg] if a.vararg else [])
                + ([a.kwarg] if a.kwarg else [])):
        if arg.arg != "self":
            yield arg.arg


def _names_outside_static_attrs(node: ast.AST) -> Set[str]:
    """Names referenced in ``node``, skipping subtrees hanging off
    static-metadata attributes (``x.shape[0]`` reads no traced data)."""
    found: Set[str] = set()

    def walk(n):
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
            return
        if isinstance(n, ast.Name):
            found.add(n.id)
        for child in ast.iter_child_nodes(n):
            walk(child)

    walk(node)
    return found


class _TracedChecker:
    """Applies the traced-context rules inside one jit-traced function
    (and its nested defs, whose params are traced too)."""

    def __init__(self, imports: _Imports, path: str):
        self.imports = imports
        self.path = path
        self.violations: List[Violation] = []

    def _add(self, check: str, node: ast.AST, message: str) -> None:
        self.violations.append(Violation(
            check=check, where=f"{self.path}:{node.lineno}",
            message=message))

    def check(self, fn: ast.AST) -> List[Violation]:
        self._walk(fn, set(_traced_param_names(fn)))
        return self.violations

    def _walk(self, node: ast.AST, params: Set[str]) -> None:
        for child in ast.iter_child_nodes(node):
            child_params = params
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child_params = params | set(_traced_param_names(child))
            if isinstance(child, ast.Call):
                self._check_call(child, params)
            self._walk(child, child_params)

    def _check_call(self, call: ast.Call, params: Set[str]) -> None:
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not call.args:
            self._add("jit-host-sync", call,
                      ".item() inside a jit-traced function — a "
                      "device→host sync that fails under trace; thread "
                      "the value out of the jitted computation instead")
            return
        if isinstance(func, ast.Name) and func.id in ("float", "int",
                                                      "bool") \
                and call.args:
            touched = _names_outside_static_attrs(call.args[0]) & params
            if touched:
                self._add("jit-host-sync", call,
                          f"{func.id}() applied to traced value(s) "
                          f"{sorted(touched)} inside a jit-traced "
                          "function — concretization error under "
                          "trace; use jnp casts/ops instead")
            return
        root = _attr_root(func)
        if root is None:
            return
        chain = _attr_chain(func)
        if root in self.imports.numpy:
            if len(chain) >= 3 and chain[1] == "random":
                self._add("jit-python-rng-time", call,
                          f"{'.'.join(chain)}() inside a jit-traced "
                          "function — host RNG runs once at trace time "
                          "and freezes; use jax.random with a threaded "
                          "key")
            else:
                self._add("jit-host-sync", call,
                          f"{'.'.join(chain)}() inside a jit-traced "
                          "function — numpy concretizes traced values; "
                          "use the jax.numpy equivalent")
            return
        if root in self.imports.time and chain[-1] in _TIME_CALLS:
            self._add("jit-python-rng-time", call,
                      f"{'.'.join(chain)}() inside a jit-traced "
                      "function — evaluated once at trace time, then "
                      "constant; time outside the jitted step")
            return
        if root in self.imports.random:
            self._add("jit-python-rng-time", call,
                      f"{'.'.join(chain)}() inside a jit-traced "
                      "function — Python RNG runs at trace time and "
                      "freezes; use jax.random with a threaded key")
            return
        if root in self.imports.datetime and chain[-1] in ("now",
                                                           "utcnow",
                                                           "today"):
            self._add("jit-python-rng-time", call,
                      f"{'.'.join(chain)}() inside a jit-traced "
                      "function — trace-time constant; stamp outside "
                      "the jitted step")


def _is_dataclass_decorator(dec: ast.AST) -> bool:
    target = dec.func if isinstance(dec, ast.Call) else dec
    if isinstance(target, ast.Name):
        return target.id == "dataclass"
    if isinstance(target, ast.Attribute):
        return target.attr == "dataclass"
    return False


def _check_impl_fields(cls: ast.ClassDef, path: str) -> List[Violation]:
    fields = [(stmt.target.id, stmt.lineno) for stmt in cls.body
              if isinstance(stmt, ast.AnnAssign)
              and isinstance(stmt.target, ast.Name)
              and stmt.target.id.endswith("_impl")]
    if not fields:
        return []
    post = next((stmt for stmt in cls.body
                 if isinstance(stmt, ast.FunctionDef)
                 and stmt.name == "__post_init__"), None)
    validated: Set[str] = set()
    if post is not None:
        # only the `self.<field> not in <valid set>` form counts: a
        # positive `in` test is how the feature guards are phrased
        # (e.g. "dropout unsupported for impl in (...)"), which must
        # not satisfy the domain-validation requirement
        for node in ast.walk(post):
            if isinstance(node, ast.Compare) and any(
                    isinstance(op, ast.NotIn) for op in node.ops):
                left = node.left
                if isinstance(left, ast.Attribute) \
                        and isinstance(left.value, ast.Name) \
                        and left.value.id == "self":
                    validated.add(left.attr)
    out = []
    for name, lineno in fields:
        if name not in validated:
            out.append(Violation(
                check="impl-field-validation", where=f"{path}:{lineno}",
                message=f"dataclass {cls.name}.{name} is an enum-like "
                        "impl field with no membership validation in "
                        f"{cls.name}.__post_init__ — an invalid value "
                        "only fails deep inside a jit trace; validate "
                        "at config time"))
    return out


def _check_uncached_compiles(tree: ast.AST, path: str) -> List[Violation]:
    """``uncached-compile``: raw ``.lower().compile()`` outside the
    cache package (see module docstring). Matches the chained form and
    the two-statement form (``lowered = f.lower(...); lowered.
    compile()``) via a module-wide name scan — conservative enough
    that ``re.compile`` and friends never match (the receiver must be
    a lowering)."""
    lowered_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and isinstance(node.value.func, ast.Attribute) \
                and node.value.func.attr == "lower":
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    lowered_names.add(tgt.id)
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "compile"):
            continue
        recv = node.func.value
        chained = (isinstance(recv, ast.Call)
                   and isinstance(recv.func, ast.Attribute)
                   and recv.func.attr == "lower")
        named = isinstance(recv, ast.Name) and recv.id in lowered_names
        if chained or named:
            out.append(Violation(
                check="uncached-compile", where=f"{path}:{node.lineno}",
                message="raw .lower().compile() outside "
                        "perceiver_tpu/cache/ — route AOT compiles "
                        "through perceiver_tpu.cache (aot_compile / "
                        "compile_lowered) so warm starts deserialize "
                        "instead of recompiling, or suppress with "
                        "'graphcheck: ignore' and a reason"))
    return out


_BROAD_EXCEPTIONS = {"Exception", "BaseException"}


def _has_reason_comment(lines: List[str], lineno: int) -> bool:
    """A non-empty ``#`` comment on the 1-based line counts as the
    required reason (naive scan is fine: the flagged lines hold only
    ``except ...:`` / ``pass`` / ``...``, never ``#`` in a string)."""
    try:
        line = lines[lineno - 1]
    except IndexError:
        return False
    head, sep, comment = line.partition("#")
    return bool(sep) and bool(comment.strip())


def _is_broad_type(node: Optional[ast.AST]) -> bool:
    if node is None:
        return True  # bare except
    if isinstance(node, ast.Tuple):
        return any(_is_broad_type(e) for e in node.elts)
    if isinstance(node, ast.Name):
        return node.id in _BROAD_EXCEPTIONS
    if isinstance(node, ast.Attribute):
        return node.attr in _BROAD_EXCEPTIONS
    return False


def _check_silent_swallow(tree: ast.AST, lines: List[str],
                          path: str) -> List[Violation]:
    """``silent-swallow``: see module docstring. A bare ``except:`` is
    flagged regardless of body; a broad typed handler only when its
    body is pure ``pass``/``...``. A reason comment on the ``except``
    line or any body line clears it."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        bare = node.type is None
        swallows = all(
            isinstance(stmt, ast.Pass)
            or (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)
                and stmt.value.value is Ellipsis)
            for stmt in node.body)
        if not (bare or (_is_broad_type(node.type) and swallows)):
            continue
        check_lines = [node.lineno] + [s.lineno for s in node.body]
        if any(_has_reason_comment(lines, ln) for ln in check_lines):
            continue
        what = ("bare except:" if bare
                else "except Exception: pass")
        out.append(Violation(
            check="silent-swallow", where=f"{path}:{node.lineno}",
            message=f"{what} silently discards the failure — narrow "
                    "the exception type, handle it visibly, or add a "
                    "reason comment on the except/pass line (or "
                    "'graphcheck: ignore') explaining why discarding "
                    "is correct"))
    return out


# serving/engine.py: the sync-free dispatch contract (docs/SERVING.md)
_ENGINE_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_NUMPY_CONVERSIONS = {"asarray", "array", "copy", "ascontiguousarray"}


def _check_engine_syncs(tree: ast.AST, imports: _Imports,
                        path: str) -> List[Violation]:
    """``serving-host-sync``: no device→host synchronization anywhere
    in the serving engine module (see module docstring)."""
    out: List[Violation] = []

    def add(node, what, hint):
        out.append(Violation(
            check="serving-host-sync", where=f"{path}:{node.lineno}",
            message=f"{what} in serving/engine.py — the engine "
                    "dispatch path must never synchronize on device "
                    f"values; {hint}"))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) \
                and func.attr in _ENGINE_SYNC_ATTRS:
            add(node, f".{func.attr}()",
                "materialize results in serving/api.py instead")
            continue
        chain = _attr_chain(func)
        if chain and chain[-1] == "device_get":
            add(node, "device_get()",
                "hand device arrays to the consumer layer instead")
            continue
        root = _attr_root(func)
        if root in imports.numpy and len(chain) == 2 \
                and chain[1] in _NUMPY_CONVERSIONS:
            add(node, f"{'.'.join(chain)}() on a potential device array",
                "numpy conversion forces a transfer — convert in "
                "serving/api.materialize")
    return out


# fleet/: every blocking socket op needs a reachable deadline
_BLOCKING_RECV_ATTRS = {"recv", "recv_into", "recvfrom", "accept"}


def _receiver_key(func: ast.AST) -> Optional[str]:
    """``self._sock.recv`` → ``"self._sock"`` (the dotted receiver the
    method is called on), None for non-name receivers."""
    chain = _attr_chain(func)
    return ".".join(chain[:-1]) if len(chain) >= 2 else None


def _check_router_blocking_io(tree: ast.AST, path: str) -> List[Violation]:
    """``router-blocking-io``: see the module docstring. The receiver
    match is name-based and module-wide — one ``settimeout`` anywhere
    on the same dotted receiver clears its reads, which is exactly the
    discipline ``fleet/rpc.py`` follows (re-assert the timeout before
    every framed read)."""
    out: List[Violation] = []
    with_timeout: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "settimeout":
            key = _receiver_key(node.func)
            if key is not None:
                with_timeout.add(key)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) \
                and func.attr in _BLOCKING_RECV_ATTRS:
            key = _receiver_key(func)
            if key is not None and key not in with_timeout:
                out.append(Violation(
                    check="router-blocking-io",
                    where=f"{path}:{node.lineno}",
                    message=f"blocking {key}.{func.attr}() without a "
                            f"settimeout on {key!r} anywhere in the "
                            "module — a stalled peer would hang this "
                            "fleet hot path forever; set a deadline "
                            "so the router can eject and retry on a "
                            "sibling"))
            continue
        chain = _attr_chain(func)
        if chain and chain[-1] == "create_connection":
            has_timeout = any(kw.arg == "timeout"
                              for kw in node.keywords) \
                or len(node.args) >= 2
            if not has_timeout:
                out.append(Violation(
                    check="router-blocking-io",
                    where=f"{path}:{node.lineno}",
                    message="socket.create_connection without a "
                            "timeout blocks indefinitely on an "
                            "unresponsive replica — pass timeout= so "
                            "connect attempts respect the fleet's "
                            "failover deadline"))
    return out


# distributed/: socket discipline + no argument-less barrier waits
_BARRIER_WAIT_ATTRS = {"wait", "join", "get", "acquire"}


def _check_distributed_blocking_io(tree: ast.AST,
                                   path: str) -> List[Violation]:
    """``distributed-blocking-io``: see the module docstring. Socket
    checks mirror ``router-blocking-io`` (same receiver-key match);
    the barrier-wait check is purely syntactic — no positional args
    and no ``timeout=`` keyword means the call can block forever."""
    out: List[Violation] = []
    for v in _check_router_blocking_io(tree, path):
        out.append(Violation(
            check="distributed-blocking-io", where=v.where,
            message=v.message.replace(
                "fleet hot path", "distributed code path")))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _BARRIER_WAIT_ATTRS):
            continue
        if node.args or any(kw.arg == "timeout"
                            for kw in node.keywords):
            continue
        key = _receiver_key(node.func) or "<expr>"
        out.append(Violation(
            check="distributed-blocking-io",
            where=f"{path}:{node.lineno}",
            message=f"argument-less {key}.{node.func.attr}() in a "
                    "distributed module can block forever — a dead "
                    "group member must surface as a typed timeout the "
                    "supervisor can re-form on, never a wedged "
                    "barrier; pass a timeout (or suppress with "
                    "'graphcheck: ignore' and a reason)"))
    return out


# serving/+fleet/+distributed/: no blocking work under a held lock
_LOCKISH_NAME_RE = re.compile(r"lock|mutex", re.IGNORECASE)
_PICKLE_CALLS = {"dumps", "loads", "dump", "load"}
_SUBPROCESS_CALLS = {"run", "Popen", "check_output", "check_call",
                     "call"}
_SOCKET_BLOCKING_ATTRS = {"sendall", "send", "recv", "recv_into",
                          "recvfrom", "accept", "connect"}
_FRAMING_CALLS = {"send_msg", "recv_msg"}


def _condition_attrs(tree: ast.AST) -> Set[str]:
    """Final names assigned from a ``threading.Condition(...)`` call
    anywhere in the module (``self._not_empty = threading.Condition(
    self._lock)`` → ``"_not_empty"``). Module-wide on purpose: a
    subclass method using a base-class Condition still resolves."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        chain = _attr_chain(node.value.func)
        if not chain or chain[-1] != "Condition":
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Attribute):
                out.add(tgt.attr)
            elif isinstance(tgt, ast.Name):
                out.add(tgt.id)
    return out


def _check_blocking_under_lock(tree: ast.AST,
                               path: str) -> List[Violation]:
    """``blocking-under-lock``: see the module docstring. A lock frame
    is a ``with`` whose context expression's final name matches
    ``lock``/``mutex`` (case-insensitive) or is a known Condition
    attribute; nested function bodies reset the held set (they run
    later, on whatever thread calls them)."""
    cond_attrs = _condition_attrs(tree)
    out: List[Violation] = []

    def lockish(expr: ast.AST) -> Optional[str]:
        chain = _attr_chain(expr)
        if not chain:
            return None
        final = chain[-1]
        if _LOCKISH_NAME_RE.search(final) or final in cond_attrs:
            return ".".join(chain)
        return None

    def classify(call: ast.Call) -> Optional[str]:
        func = call.func
        if isinstance(func, ast.Name):
            if func.id == "open":
                return "open() file IO"
            if func.id in _FRAMING_CALLS:
                return f"{func.id}() framed socket IO"
            return None
        chain = _attr_chain(func)
        if not chain or not isinstance(func, ast.Attribute):
            return None
        root, final = chain[0], chain[-1]
        if final in _FRAMING_CALLS:
            return f"{'.'.join(chain)}() framed socket IO"
        if root == "time" and final == "sleep":
            return "time.sleep()"
        if root == "pickle" and final in _PICKLE_CALLS:
            return f"pickle.{final}() serialization"
        if root == "subprocess" and final in _SUBPROCESS_CALLS:
            return f"subprocess.{final}()"
        if final in _SOCKET_BLOCKING_ATTRS and len(chain) >= 2:
            return f"{'.'.join(chain)}() socket IO"
        return None

    def walk(node: ast.AST, held) -> None:
        for child in ast.iter_child_nodes(node):
            child_held = held
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda)):
                child_held = ()
            elif isinstance(child, (ast.With, ast.AsyncWith)):
                locks = tuple(
                    (name, child.lineno) for item in child.items
                    for name in (lockish(item.context_expr),)
                    if name is not None)
                child_held = held + locks
            elif isinstance(child, ast.Call) and held:
                what = classify(child)
                if what is not None:
                    lock_name, lock_line = held[-1]
                    out.append(Violation(
                        check="blocking-under-lock",
                        where=f"{path}:{child.lineno}",
                        message=f"{what} while holding {lock_name} "
                                f"(acquired line {lock_line}) — "
                                "blocking work under a lock "
                                "serializes every thread on that "
                                "lock and is one callback away from "
                                "the breaker-deadlock shape "
                                "(docs/RESILIENCE.md); snapshot "
                                "under the lock and do the blocking "
                                "work after release, or suppress "
                                "with 'graphcheck: ignore' and a "
                                "reason if holding the lock is the "
                                "protocol"))
            walk(child, child_held)

    walk(tree, ())
    return out


def _check_condition_waits(tree: ast.AST, path: str) -> List[Violation]:
    """Condition hygiene (emitted as ``distributed-blocking-io``; see
    module docstring): ``.wait()`` with no positional argument and no
    ``timeout=`` on an attribute assigned from
    ``threading.Condition(...)``."""
    cond_attrs = _condition_attrs(tree)
    out: List[Violation] = []
    if not cond_attrs:
        return out
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "wait"):
            continue
        chain = _attr_chain(node.func)
        if len(chain) < 2 or chain[-2] not in cond_attrs:
            continue
        if node.args or any(kw.arg == "timeout"
                            for kw in node.keywords):
            continue
        cond = ".".join(chain[:-1])
        out.append(Violation(
            check="distributed-blocking-io",
            where=f"{path}:{node.lineno}",
            message=f"{cond}.wait() with no timeout — a missed "
                    "notify (producer dying between append and "
                    "notify) wedges this waiter forever; wait in a "
                    "predicate loop with a bounded timeout so the "
                    "thread can re-check shutdown flags "
                    "(docs/RESILIENCE.md), or suppress with "
                    "'graphcheck: ignore' and a reason"))
    return out


# metric registration sites: one naming convention for all planes
_METRIC_KINDS = {"counter", "gauge", "histogram"}
_METRIC_NAME_RE = re.compile(r"^(serving|training|fleet)_[a-z0-9_]+$")


def _check_metrics_conventions(tree: ast.AST,
                               path: str) -> List[Violation]:
    """``metrics-conventions``: see the module docstring. Only
    string-literal first arguments are checked — a computed name is a
    different smell, but not one an AST pass can validate."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_KINDS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        kind, name = node.func.attr, node.args[0].value
        problems = []
        if not _METRIC_NAME_RE.match(name):
            problems.append(
                "must be snake_case with a serving_/training_/fleet_ "
                "plane prefix")
        if kind == "counter" and not name.endswith("_total"):
            problems.append("counters must end in _total")
        if kind != "counter" and name.endswith("_total"):
            problems.append(f"{kind}s must not end in _total "
                            "(reserved for counters)")
        for problem in problems:
            out.append(Violation(
                check="metrics-conventions",
                where=f"{path}:{node.lineno}",
                message=f"metric {name!r} registered via .{kind}() — "
                        f"{problem}; one naming scheme keeps the "
                        "merged fleet exposition collision-free and "
                        "rate()-able (docs/OBSERVABILITY.md)"))
    return out


def _is_pjit_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "pjit"
    if isinstance(node, ast.Attribute):
        return node.attr == "pjit"
    return False


_SHARDING_KWARGS = {"in_shardings", "out_shardings"}


def _check_unsharded_pjit(tree: ast.AST, path: str) -> List[Violation]:
    """``unsharded-pjit``: jit/pjit in the SPMD modules without
    explicit in_shardings AND out_shardings (see module docstring).
    Covers the call form, the ``@partial(jax.jit, ...)`` decorator,
    and the bare ``@jax.jit`` decorator."""
    out: List[Violation] = []

    def flag(lineno: int, missing) -> None:
        out.append(Violation(
            check="unsharded-pjit", where=f"{path}:{lineno}",
            message=f"jit/pjit without explicit {'/'.join(missing)} "
                    "in an SPMD module — silent sharding propagation "
                    "is how replication sneaks in; declare the layout "
                    "at the pjit boundary (parallel/sharding.py specs) "
                    "or suppress with 'graphcheck: ignore' and a "
                    "reason"))

    for node in ast.walk(tree):
        kws = None
        if isinstance(node, ast.Call):
            if _is_jit_expr(node.func) or _is_pjit_expr(node.func):
                kws = node.keywords
            elif _is_partial_expr(node.func) and any(
                    _is_jit_expr(a) or _is_pjit_expr(a)
                    for a in node.args):
                kws = node.keywords
        if kws is None:
            continue
        missing = sorted(_SHARDING_KWARGS
                         - {kw.arg for kw in kws if kw.arg})
        if missing:
            flag(node.lineno, missing)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                # bare @jax.jit — the Call forms were handled above
                if not isinstance(dec, ast.Call) and (
                        _is_jit_expr(dec) or _is_pjit_expr(dec)):
                    flag(dec.lineno, sorted(_SHARDING_KWARGS))
    return out


# serving/: CoW discipline — page writes only in the two CoW-aware
# modules (decode.py enforces ensure_private_page; prefix_cache.py
# defines it)
_AT_UPDATE_METHODS = {"set", "add", "subtract", "multiply", "divide",
                      "power", "min", "max", "apply"}
_KV_ALIAS_EXEMPT = ("serving/decode.py", "serving/prefix_cache.py")


def _check_kv_alias(tree: ast.AST, path: str) -> List[Violation]:
    """``kv-alias``: see the module docstring. The match is the exact
    JAX functional-update shape — a call on an attribute of an
    ``.at[...]`` subscript — so ordinary dict/list ``.add``/``.set``
    calls never trip it."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _AT_UPDATE_METHODS):
            continue
        sub = node.func.value
        if not (isinstance(sub, ast.Subscript)
                and isinstance(sub.value, ast.Attribute)
                and sub.value.attr == "at"):
            continue
        out.append(Violation(
            check="kv-alias",
            where=f"{path}:{node.lineno}",
            message=f".at[...].{node.func.attr}(...) page write outside "
                    "the CoW-aware modules — KV pages may be aliased by "
                    "the prefix index and other streams (refcount > 1), "
                    "and only serving/decode.py (via "
                    "ensure_private_page) and serving/prefix_cache.py "
                    "uphold the copy-on-write discipline; route the "
                    "write through the engine, or mark the line "
                    "'graphcheck: ignore' with a reason if the target "
                    "is provably not the paged arena"))
    return out


# multi-tenant observability: every label/emit site in these planes
# must attribute to a tenant (or carry a reasoned suppression)
_TENANT_LABEL_FILES = ("serving/decode.py", "serving/batcher.py")


def _check_tenant_label_discipline(tree: ast.AST,
                                   path: str) -> List[Violation]:
    """``tenant-label-discipline``: see the module docstring. Matches
    ``<anything>.labels(...)`` and ``emit("<type>", ...)`` /
    ``<anything>.emit("<type>", ...)`` calls; only string-literal
    event types are checked (computed types are a different smell)."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        is_labels = isinstance(func, ast.Attribute) \
            and func.attr == "labels"
        is_emit = ((isinstance(func, ast.Attribute)
                    and func.attr == "emit")
                   or (isinstance(func, ast.Name) and func.id == "emit"))
        if not (is_labels or is_emit):
            continue
        if is_emit and not (node.args
                            and isinstance(node.args[0], ast.Constant)
                            and isinstance(node.args[0].value, str)):
            continue
        if any(kw.arg == "tenant" for kw in node.keywords):
            continue
        what = ("metric .labels(...) site" if is_labels
                else f"event emit({node.args[0].value!r}, ...)")
        out.append(Violation(
            check="tenant-label-discipline",
            where=f"{path}:{node.lineno}",
            message=f"{what} without a tenant= label in a multi-tenant "
                    "plane — unlabeled series merge all tenants and "
                    "hide noisy-neighbor starvation "
                    "(docs/OBSERVABILITY.md 'Tenant labels'); add the "
                    "tenant label, or mark the line 'graphcheck: "
                    "ignore' with a reason naming the tenant-split "
                    "series that covers it"))
    return out


def lint_source(src: str, path: str = "<memory>") -> List[Violation]:
    """Lint one module's source. ``path`` is used for reporting and
    for the ops-scoped rule (a path containing ``/ops/``)."""
    tree = ast.parse(src, filename=path)
    imports = _Imports()
    imports.visit(tree)
    violations: List[Violation] = []
    violations.extend(_check_silent_swallow(tree, src.splitlines(), path))
    violations.extend(_check_metrics_conventions(tree, path))

    norm = path.replace(os.sep, "/")
    if norm.endswith("serving/engine.py"):
        violations.extend(_check_engine_syncs(tree, imports, path))
    if "perceiver_tpu/fleet/" in norm:
        violations.extend(_check_router_blocking_io(tree, path))
    if "perceiver_tpu/distributed/" in norm:
        violations.extend(_check_distributed_blocking_io(tree, path))
    if ("perceiver_tpu/serving/" in norm
            or "perceiver_tpu/fleet/" in norm
            or "perceiver_tpu/distributed/" in norm):
        violations.extend(_check_blocking_under_lock(tree, path))
    if "perceiver_tpu/serving/" in norm \
            or "perceiver_tpu/fleet/" in norm:
        violations.extend(_check_condition_waits(tree, path))
    if "perceiver_tpu/serving/" in norm and not norm.endswith(
            _KV_ALIAS_EXEMPT):
        violations.extend(_check_kv_alias(tree, path))
    if "perceiver_tpu/fleet/" in norm \
            or norm.endswith(_TENANT_LABEL_FILES):
        violations.extend(_check_tenant_label_discipline(tree, path))
    if "perceiver_tpu/parallel/" in norm \
            or norm.endswith("perceiver_tpu/training/spmd.py"):
        violations.extend(_check_unsharded_pjit(tree, path))
    if "perceiver_tpu/cache/" not in norm:
        violations.extend(_check_uncached_compiles(tree, path))
    if "/ops/" in norm and {"numpy", "jax.numpy"} <= imports.top_level:
        lineno = next((n.lineno for n in tree.body
                       if isinstance(n, (ast.Import, ast.ImportFrom))), 1)
        violations.append(Violation(
            check="ops-numpy-mix", where=f"{path}:{lineno}",
            message="ops module imports both numpy and jax.numpy at "
                    "top level — keep host-side precompute in np-only "
                    "modules (ops/fourier.py pattern) and traced code "
                    "jnp-only, or mark the line 'graphcheck: ignore' "
                    "with a reason"))

    jit_names = _jit_called_names(tree)
    traced_roots = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in jit_names or any(
                    _is_jit_decorator(d) for d in node.decorator_list):
                traced_roots.append(node)
    # drop roots nested inside another root (checked once, outermost)
    covered = set()
    for root in traced_roots:
        for sub in ast.walk(root):
            if sub is not root and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                covered.add(sub)
    for root in traced_roots:
        if root not in covered:
            violations.extend(
                _TracedChecker(imports, path).check(root))

    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and any(
                _is_dataclass_decorator(d) for d in node.decorator_list):
            violations.extend(_check_impl_fields(node, path))

    # per-line suppression
    lines = src.splitlines()
    kept = []
    for v in violations:
        try:
            lineno = int(v.where.rsplit(":", 1)[1])
            if SUPPRESS_MARKER in lines[lineno - 1]:
                continue
        except (IndexError, ValueError):
            pass
        kept.append(v)
    return kept


ALL_RULES = ("jit-host-sync", "jit-python-rng-time", "ops-numpy-mix",
             "impl-field-validation", "serving-host-sync",
             "uncached-compile", "silent-swallow", "router-blocking-io",
             "distributed-blocking-io", "unsharded-pjit",
             "metrics-conventions", "blocking-under-lock", "kv-alias",
             "tenant-label-discipline")


def lint_paths(paths: Iterable[str]) -> Report:
    """Lint every ``.py`` file under the given files/directories."""
    report = Report()
    for rule in ALL_RULES:
        report.ran(rule)
    for path in _expand(paths):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        try:
            report.extend(lint_source(src, path))
        except SyntaxError as e:
            report.add(Violation(
                check="lint-parse", where=f"{path}:{e.lineno or 0}",
                message=f"could not parse: {e.msg}"))
    return report


def _expand(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    return out


_REPO_LINT_DEFAULTS = ("perceiver_tpu", "scripts", "run.py")


def default_lint_paths(repo_root: str) -> List[str]:
    """The tree ``scripts/check.py`` lints by default: the package,
    the scripts, and the entry points. Tests are excluded on purpose —
    they host-sync deliberately to assert on device values."""
    return [os.path.join(repo_root, p) for p in _REPO_LINT_DEFAULTS
            if os.path.exists(os.path.join(repo_root, p))]
