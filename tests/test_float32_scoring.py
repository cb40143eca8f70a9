"""float32 scoring that is the definition's, bit for bit.

The fitness exponential is defined in float64 and rounded once to
float32 (structs/funcs.py ``_pow10``).  With ``jax_enable_x64`` off —
the dtype a chip deploys — the kernels carry the free share and the
exponential as two float32s (ops/twofloat.py) and must land on that
same float32; with it on, the float64 trace is what it has always been.
The tier-1 session runs x64 on, so every case here that needs it off
switches it off for its own scope (``jax.enable_x64(False)``, or a child
process where a served pipeline's threads need it).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest, repo_root
from nomad_tpu.ops import twofloat
from nomad_tpu.ops.score import _fit_exponentials, _pow10
from nomad_tpu.structs.funcs import pow10_np


def _definition(after, cap):
    """float64 free share, float64 power, rounded once to float32."""
    share = 1.0 - after.astype(np.float64) / cap.astype(np.float64)
    return pow10_np(share).astype(np.float32)


def _two_float(after, cap):
    with jax.enable_x64(False):
        got = jax.jit(twofloat.pow10_free)(
            jnp.asarray(after, jnp.float32), jnp.asarray(cap, jnp.float32)
        )
        assert got.dtype == jnp.float32
        return np.asarray(got)


def _every_pair(config_name, resource):
    """Every (used, capacity) a fleet of this configuration can hold for
    one resource: each capacity less its reservation, and every whole
    number of units up to it (a superset of the sums of its alloc and
    ask sizes)."""
    fleet = Manifest().config(config_name)["fleet"]
    caps, reserved = {
        "cpu": (fleet["node_cpu"], fleet["reserved_cpu"]),
        "mem": (fleet["node_memory_mb"], fleet["reserved_memory_mb"]),
    }[resource]
    after, cap = [], []
    for c in caps:
        after.append(np.arange(0, c - reserved + 1, dtype=np.int64))
        cap.append(np.full(c - reserved + 1, c - reserved, dtype=np.int64))
    return np.concatenate(after), np.concatenate(cap)


@pytest.mark.parametrize("resource", ["cpu", "mem"])
@pytest.mark.parametrize("config_name", ["binpack-10k", "spread-5k"])
def test_two_float_is_the_definition_on_every_pair_a_fleet_can_hold(
    config_name, resource
):
    after, cap = _every_pair(config_name, resource)
    assert len(after) > 40000
    got, want = _two_float(after, cap), _definition(after, cap)
    off = np.flatnonzero(got != want)
    assert off.size == 0, (after[off][:5], cap[off][:5])


@pytest.mark.parametrize("seed", [31, 1826525683, 2**31 + 11])
def test_two_float_is_the_definition_on_a_seeded_grid(seed):
    rng = np.random.default_rng(seed)
    cap = rng.integers(1000, 70000, 200000)
    after = (rng.random(200000) * (cap + 1)).astype(np.int64)
    assert np.array_equal(_two_float(after, cap), _definition(after, cap))
    # x over [0, 1] on an even grid: after = k of a capacity of 2^18
    k = np.arange(0, 2**18 + 1, dtype=np.int64)
    cap = np.full_like(k, 2**18)
    assert np.array_equal(_two_float(k, cap), _definition(k, cap))


def test_the_free_share_is_float64s_to_the_pair():
    after = np.arange(0, 31901, dtype=np.int64)
    cap = np.full_like(after, 31900)
    with jax.enable_x64(False):
        hi, lo = jax.jit(twofloat.free_share)(
            jnp.asarray(after, jnp.float32), jnp.asarray(cap, jnp.float32)
        )
    pair = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    want = 1.0 - after / cap.astype(np.float64)
    assert np.max(np.abs(pair - want)) < 2.0**-46
    assert np.array_equal(np.asarray(hi), want.astype(np.float32))


def test_plain_float32_pow_is_not_the_definition():
    """What the two floats are for: float32's own division and pow miss
    the definition on a large share of the same pairs, even under IEEE
    (on the TPU: on nearly all of them, PERF.md)."""
    after, cap = _every_pair("binpack-10k", "cpu")
    with jax.enable_x64(False):
        a = jnp.asarray(after, jnp.float32)
        c = jnp.asarray(cap, jnp.float32)
        plain = np.asarray(jnp.power(jnp.float32(10.0), 1.0 - a / c))
    assert np.mean(plain != _definition(after, cap)) > 0.1


def test_the_float32_trace_scores_with_the_two_floats():
    after = np.asarray([600.0, 8400.0, 31900.0], np.float32)
    cap = np.asarray([7900.0, 15900.0, 31900.0], np.float32)
    mem_after = np.asarray([384.0, 8000.0, 16128.0], np.float32)
    mem_cap = np.asarray([16128.0, 32512.0, 65280.0], np.float32)
    with jax.enable_x64(False):
        base = np.asarray(jax.jit(_fit_exponentials, static_argnums=4)(
            after, cap, mem_after, mem_cap, jnp.float32
        ))
        assert base.dtype == np.float32
    want = _definition(after, cap) + _definition(mem_after, mem_cap)
    assert np.array_equal(base, want)


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_at_x64_on_the_lowered_pow10_is_what_it_was():
    x = jnp.linspace(0.0, 1.0, 16, dtype=jnp.float64)
    text = _lowered(lambda v: _pow10(v, jnp.float64), x)
    ops = [
        line.split("=", 1)[1].split()[0]
        for line in text.splitlines()
        if "stablehlo." in line and "=" in line
    ]
    assert ops == [
        "stablehlo.constant", "stablehlo.broadcast_in_dim",
        "stablehlo.power", "stablehlo.convert", "stablehlo.convert",
    ], text
    assert "tensor<16xf32>" in text and "bitcast" not in text


def test_at_x64_on_the_score_terms_lower_to_the_float64_trace_of_before():
    """``_fit_exponentials`` at float64 is the inline code the kernels
    held before it, operation for operation."""
    def before(cpu_after, cpu_cap, mem_after, mem_cap):
        dtype = cpu_after.dtype
        free_cpu = 1.0 - cpu_after / cpu_cap
        free_mem = 1.0 - mem_after / mem_cap
        return (
            jnp.power(jnp.asarray(10.0, dtype), free_cpu)
            .astype(jnp.float32).astype(dtype)
            + jnp.power(jnp.asarray(10.0, dtype), free_mem)
            .astype(jnp.float32).astype(dtype)
        )

    def now(cpu_after, cpu_cap, mem_after, mem_cap):
        return _fit_exponentials(
            cpu_after, cpu_cap, mem_after, mem_cap, cpu_after.dtype
        )

    args = [jnp.arange(1.0, 9.0, dtype=jnp.float64) * k for k in (1, 3, 2, 5)]
    text = _lowered(now, *args)
    assert text.replace("jit_now", "jit_before") == _lowered(before, *args)
    assert "bitcast" not in text and "f32" in text


CHILD = os.path.join(repo_root(), "tests", "_float32_parity_child.py")


@pytest.mark.parametrize("seed", [3, 1000000007, 2**31 + 11])
@pytest.mark.parametrize(
    "config_name,nodes,jobs", [("binpack-10k", 400, 40), ("spread-5k", 150, 24)]
)
def test_the_chained_kernel_at_x64_off_places_as_the_reference_and_the_sequential_scheduler(
    config_name, nodes, jobs, seed
):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, CHILD, config_name, str(nodes), str(jobs), str(seed)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["x64"] is False
    # every evaluation through the chained kernel, none down the host path
    assert line["prescored"] == jobs == line["jobs_compared"]
    assert line["differ_from_sequential"] == 0
    assert line["mismatched_placements"] == 0
    assert line["lost_or_duplicate"] == 0
    # the walk's counters: one pick a placement, a pull or more a pick
    assert line["walk_picks"] == line["placements"]
    assert line["walk_pulls"] >= line["walk_picks"]
