"""Compile-only checks for one TPU v5e chip, with no chip attached.

The three Pallas kernels of the main path (``containment`` inside the
flat and trie joins, ``trie_walk`` inside the fused walk, and
``match_count``) and two jnp steps (the fused walk without the kernel,
and the miner's ``match_signatures_batch``) are lowered and compiled for
a described v5e chip at the largest shapes ``chip_smoke.py`` dispatches
at the paper's Table 3 defaults (its ``[shapes]`` lines).  What the
chip's compiler refuses - a gather or scatter Mosaic cannot lower, a
block over the VMEM limit, a program over HBM - fails here at no chip
time.  The kernel cases must hold a ``tpu_custom_call``: the kernel was
compiled, not replaced by a reference.

The topology is described in a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.containment import containment
from repro.kernels.match_count import match_count
from repro.kernels.match_count.ops import match_signatures_kernel
from repro.kernels.trie_walk import trie_walk
from repro.mining.engine import match_signatures_batch
from repro.serving.batch import (
    fused_trie_walk,
    pair_contains_indexed,
    trie_level_advance_gather,
)

HBM_BYTES = 16 * 2**30  # one v5e chip
KERNEL_MODULES = (containment, match_count, trie_walk)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def b1(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_)


# One flush of 256 queries: tokens, order, start, count (T = 64 tokens,
# K = 36 label keys) - shared by every serving join.
QUERY = [i32(256, 64, 6), i32(256, 64), i32(256, 36), i32(256, 36)]
# The miner's token table over the |DB| = 1000 sequences, and one chunk
# of e_batch = 1024 embedding rows (ni = 16, nv = 12).
DB_TOKENS = i32(1000, 33, 6)
ROWS = [i32(1024), i32(1024, 16), i32(1024, 12), i32(1024)]

# name -> (jitted fn, arguments, static kwargs)
KERNEL_CASES = {
    "containment_flat": (
        pair_contains_indexed,
        QUERY + [i32(77, 2, 8), i32(16384), i32(16384)],
        dict(nv=3, emax=4, tmax=8, use_kernel=True, block_g=64,
             uniform_length=True),
    ),
    "containment_trie": (
        trie_level_advance_gather,
        QUERY + [i32(8192, 4, 6), i32(8192, 4, 3), b1(8192, 4),
                 b1(8192), i32(4096, 10)],
        dict(emax=4, tmax=8, use_kernel=True, block_g=64, compact=False),
    ),
    "trie_walk": (
        fused_trie_walk,
        QUERY + [i32(8192, 2), i32(59, 8, 8), i32(59, 8), i32(59, 8, 36)],
        dict(ni=6, nv=3, emax=4, tmax=8, use_kernel=True),
    ),
    "match_count": (
        match_signatures_kernel,
        [DB_TOKENS] + ROWS + [i32(64, 5), i32(), i32(), i32()],
        dict(interpret=False, lane_pad=True),
    ),
}

JNP_CASES = {
    "fused_trie_walk": (
        fused_trie_walk,
        KERNEL_CASES["trie_walk"][1],
        dict(KERNEL_CASES["trie_walk"][2], use_kernel=False),
    ),
    "match_signatures_batch": (
        match_signatures_batch,
        [DB_TOKENS] + ROWS + [i32(1024), i32(256, 64, 5), i32(256),
                              i32(256), i32(256)],
        {},
    ),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent
    compilation cache off: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # the kernels pick interpret mode from the default backend,
        # which is the CPU here: compile them for real
        for mod in KERNEL_MODULES:
            mp.setattr(mod, "default_interpret", lambda: False)
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, args, static):
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in args]
    compiled = fn.lower(*args, **static).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled.as_text()


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args, static = KERNEL_CASES[case]
    assert "tpu_custom_call" in _compile(one_chip, fn, args, static)


@pytest.mark.parametrize("case", sorted(JNP_CASES))
def test_jnp_step_compiles_for_v5e(one_chip, case):
    fn, args, static = JNP_CASES[case]
    assert "tpu_custom_call" not in _compile(one_chip, fn, args, static)
