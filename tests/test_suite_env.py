"""The tests' own environment (``tests/conftest.py``): what a child
process inherits of it, that the two LLVM options ``jit_once`` gives a
program leave XLA's own passes as they were, and that time is not won
by marking cases slow."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp

import conftest


def test_children_inherit_the_tests_flags():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import json, os, sys; assert 'jax' not in sys.modules; "
         "print(json.dumps({k: os.environ.get(k) for k in "
         "('XLA_FLAGS', 'JAX_ENABLE_COMPILATION_CACHE', "
         "'JAX_COMPILATION_CACHE_DIR')}))"],
        env=dict(os.environ), cwd=conftest.REPO_ROOT, capture_output=True,
        text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout)
    flags = got["XLA_FLAGS"].split()
    assert "--xla_force_host_platform_device_count=8" in flags
    assert got["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert got["JAX_COMPILATION_CACHE_DIR"] is None
    assert len(jax.devices()) == 8
    # the cheap compile is a program's (``conftest.jit_once``), never the
    # process's: a child that runs long keeps LLVM's optimisation
    assert not any(name in got["XLA_FLAGS"] for name in conftest.RUNS_ONCE)


def test_hlo_passes_still_run_under_the_tests_flags():
    """``jit_once``'s two options switch off LLVM-level work only: the
    optimized HLO (source locations aside), its fusions and the sizes
    buffer assignment reports, which ``analysis/`` and the budget
    manifests read, are those of a plain ``jax.jit``."""
    def loss(w, x):
        return jax.nn.softmax(jnp.tanh(x @ w)).var()

    def optimized(jit):
        compiled = jit(jax.value_and_grad(loss)).lower(
            jnp.ones((128, 128)), jnp.ones((64, 128))).compile()
        text = compiled.as_text()
        # the computations alone: the tables before them say where the
        # caller stood
        text = re.sub(r", metadata=\{[^}]*\}", "", text[text.index("\n%"):])
        memory = compiled.memory_analysis()
        return text, (memory.argument_size_in_bytes,
                      memory.output_size_in_bytes,
                      memory.temp_size_in_bytes)

    assert conftest.RUNS_ONCE == {
        "xla_backend_optimization_level": 0,
        "xla_llvm_disable_expensive_passes": True}     # these two, no other
    cheap_text, cheap_bytes = optimized(conftest.jit_once)
    plain_text, plain_bytes = optimized(jax.jit)
    assert len(re.findall(r"^\s*\S+ = \S+ fusion\(", cheap_text, re.M)) > 5
    assert cheap_text == plain_text
    assert cheap_bytes == plain_bytes and all(cheap_bytes)


def test_the_slow_list_does_not_grow():
    """Time is not to be won by marking: the list is at most what it
    was when the suite's compile work was cut (PR 47)."""
    assert len(conftest._SLOW) <= 42
