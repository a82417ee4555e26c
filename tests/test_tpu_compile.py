"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

jaxlib ships the TPU compiler, and it compiles for a described, unattached
chip.  These tests lower the served path's Pallas kernels through Mosaic at
real widths, so what interpret mode cannot see — Mosaic's layout rules,
unsupported primitives, scoped VMEM at the vertex cap — fails here instead
of on the chip.  Nothing runs; the results are checked by the interpret-mode
sweeps in ``test_kernels.py``.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports every test file.  Keep all such compiles in this one file.  The last
tests pin what the chip entry points promise off the chip: the persistent
compile-cache rule, and that ``chip_smoke.py`` refuses a machine without a TPU.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

# the served path's widths: the vertex cap, the row-slice ceiling of a join
# dispatch, a 1,024-candidate level, 16-vertex queries (T = 15 matched
# columns) and J = 4 constrained neighbors
N_CAP, ROWS, CANDS, T, J = 8192, 4096, 1024, 15, 4


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with Mosaic forced on the kernel path.

    The library wrappers pick interpret mode from ``jax.default_backend()``,
    which is the CPU here; the fixture points that probe at the TPU for the
    module, and keeps the persistent compile cache off (entries compiled
    for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    import repro.kernels.cni_update.ops as cni_update_ops
    import repro.kernels.embed_join.ops as embed_join_ops

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:  # else the compiler logs to /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this jaxlib
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        mp.setattr(embed_join_ops, "_on_tpu", lambda: True)
        mp.setattr(cni_update_ops, "_on_tpu", lambda: True)
        yield desc
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _join_operands(sharding):
    return (
        _shape((ROWS, T), jnp.int32, sharding),    # table
        _shape((ROWS,), jnp.bool_, sharding),      # row_valid
        _shape((CANDS,), jnp.int32, sharding),     # cand_list
        _shape((CANDS,), jnp.bool_, sharding),     # cand_valid
        _shape((N_CAP, CANDS), jnp.int32, sharding),  # elab_cols
        _shape((J,), jnp.int32, sharding),         # q_pos
        _shape((J,), jnp.int32, sharding),         # q_lab
        _shape((J,), jnp.bool_, sharding),         # q_valid
    )


@pytest.mark.parametrize("phase", ["count", "grid"])
def test_embed_join_compiles_at_vertex_cap(one_chip, phase):
    """Count and grid kernels (the emit pass runs the grid kernel) at
    N = search_vertex_cap: the (N, BC) edge-label block and the chunked
    one-hot contraction fit scoped VMEM."""
    from repro.kernels.embed_join.ops import (
        embed_join_count_raw,
        embed_join_raw,
    )

    raw = embed_join_count_raw if phase == "count" else embed_join_raw
    compiled = jax.jit(
        lambda *a: raw(*a, use_kernel=True)
    ).lower(*_join_operands(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cni_update_compiles_at_human_widths(one_chip):
    """The index-maintenance kernel at the HUMAN stand-in's widths: 44
    labels, and ``d_max`` / ``max_p`` as an ``IncrementalIndex`` derives
    them when it is attached to that graph's store."""
    from repro.core.incremental import IncrementalIndex
    from repro.graphs.datasets import paper_dataset
    from repro.graphs.store import GraphStore
    from repro.kernels.cni_update.ops import cni_update

    store = GraphStore.from_graph(paper_dataset("HUMAN", seed=0))
    idx = IncrementalIndex()
    store.attach_index(idx)
    n_lab = int(idx.universe.size)
    assert n_lab == 44
    rows = _shape((512, n_lab), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda r, d: cni_update.__wrapped__(
            r, d, d_max=idx.d_max, max_p=idx.max_p
        )
    ).lower(rows, rows).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_enum_count_compiles_on_four_chips(topo):
    """The mesh enumerator's count phase (``_enum_count_fn``) over a
    four-device mesh: the Pallas count kernel inside ``shard_map``."""
    from repro.core.distributed import _enum_count_fn

    n_dev = 4
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    pcap = ROWS
    fn = _enum_count_fn.__wrapped__(mesh, "data", pcap, CANDS, J, True)
    compiled = fn.lower(
        _shape((n_dev, pcap, T), jnp.int32, rows),     # table
        _shape((n_dev, 1), jnp.int32, rows),           # n_rows
        _shape((CANDS,), jnp.int32, rep),              # cand
        _shape((), jnp.int32, rep),                    # n_cand
        _shape((N_CAP, N_CAP), jnp.int32, rep),        # elab
        _shape((J,), jnp.int32, rep),
        _shape((J,), jnp.int32, rep),
        _shape((J,), jnp.bool_, rep),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch):
    """Without ``JAX_COMPILATION_CACHE_DIR`` the cache goes to the one
    fixed, git-ignored directory of the checkout."""
    from repro import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the helper configures nothing,
    and JAX itself reads the variable."""
    from repro import compile_cache

    env_dir = str(tmp_path / "cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == before
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.stdout.strip().splitlines()[-1] == env_dir, out.stderr


def test_chip_smoke_refuses_a_machine_without_tpu():
    """``chip_smoke.py`` has no CPU fallback: without a TPU it exits
    non-zero and prints no result line."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
