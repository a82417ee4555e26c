"""Pallas TPU kernels: fused BFS-join expansion round (grid + count).

One pass produces the (R × C) validity grid a join level consumes: for a
tile of partial-embedding rows and a tile of candidate vertices, the fused
chain is

    gather matched-neighbor ids → adjacency/edge-label compare → injectivity

with no intermediate round trip to HBM.  The gather that dominates the join
(``elab[table[r, pos_j], cand_c]``) is phrased as a one-hot matmul so it
runs on the MXU instead of as scalar loads: each matched query neighbor j
contributes ``onehot(mapped_j) @ elab_cols`` — a (BR × N) · (N × BC)
contraction per neighbor, the GSI-style "prefix-table join as matmul".  The
contraction runs in ``block_n``-row chunks of N, so the one-hot stays
(BR × block_n) at the 8,192-vertex cap.

Two entry points share the validity math (``_validity_tile``):

* ``embed_join_pallas`` — emits the (R, C) int8 grid (the emit pass and the
  parity tests consume it);
* ``embed_join_count_pallas`` — the two-phase join's *count* pass: the grid
  is reduced to per-row survivor counts inside the kernel (accumulated
  across candidate tiles), so only (R, 1) int32 leaves the core — no
  (R, C) materialization, no table writes.

Each Pallas call carries a fixed ``name`` — ``embed_join_emit`` for the
grid (on the kernel path it runs only inside the emit pass) and
``embed_join_count`` — so a device trace names the kernels, whatever
jitted wrapper calls them.

Edge labels ride through the matmul as f32 at full (``HIGHEST``) precision,
exact for labels < 2²⁴.  The neighbor count J and table width T are static,
so both loops unroll.  Mosaic layout rules shape the operands: row vectors
are (R, 1) and candidate vectors (1, C) — no 1-D vector is broadcast — and
the per-neighbor constraint scalars live in SMEM.

Grid output is int8 (bool is awkward across Mosaic versions); the wrapper
casts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIB = 1 << 20


def _validity_tile(
    table_ref,       # (BR, T) int32
    row_valid_ref,   # (BR, 1) int32 (0/1)
    cand_ref,        # (1, BC) int32
    cand_valid_ref,  # (1, BC) int32 (0/1)
    elab_ref,        # (N, BC) f32 — data→candidate edge labels (−1 = none)
    q_pos_ref,       # (J,) int32, SMEM
    q_lab_ref,       # (J,) f32, SMEM
    q_valid_ref,     # (J,) int32 (0/1), SMEM
    *,
    n_prev: int,
    n_nbr: int,
    block_n: int,
):
    """The fused (BR, BC) bool validity tile both kernels reduce/emit.

    Every vector stays 2-D — row vectors are (BR, 1), candidate vectors
    (1, BC) — and the constraint scalars are SMEM reads.  The one-hot
    contraction runs over the N axis in ``block_n`` chunks, so the (BR, N)
    one-hot never materializes at the full vertex cap."""
    tab = table_ref[...]                       # (BR, T)
    cand = cand_ref[...]                       # (1, BC)
    br = tab.shape[0]
    bc = cand.shape[1]
    n = elab_ref.shape[0]
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (1, n_prev), 1)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (br, block_n), 1)

    # matched data vertex of each constrained query neighbor: column
    # select via a one-hot row sum (pos is a runtime scalar; T is static)
    mapped = [
        jnp.sum(jnp.where(iota_t == q_pos_ref[j], tab, 0), axis=1,
                keepdims=True)                 # (BR, 1)
        for j in range(n_nbr)
    ]

    def chunk(k, got):
        start = pl.multiple_of(k * block_n, block_n)
        elabs = elab_ref[pl.ds(start, block_n), :]          # (BN, BC)
        return tuple(
            g + jnp.dot(
                (iota_n == m - start).astype(jnp.float32), elabs,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            for g, m in zip(got, mapped)
        )

    zero = jnp.zeros((br, bc), jnp.float32)
    got = jax.lax.fori_loop(0, n // block_n, chunk, (zero,) * n_nbr)

    valid = (row_valid_ref[...] > 0) & (cand_valid_ref[...] > 0)  # (BR, BC)
    for j in range(n_nbr):
        valid = valid & ((got[j] == q_lab_ref[j]) | (q_valid_ref[j] == 0))
    for t in range(n_prev):
        valid = valid & (tab[:, t : t + 1] != cand)
    return valid


def _embed_join_kernel(
    table_ref, row_valid_ref, cand_ref, cand_valid_ref, elab_ref,
    q_pos_ref, q_lab_ref, q_valid_ref,
    out_ref,         # (BR, BC) int8
    *,
    n_prev: int,
    n_nbr: int,
    block_n: int,
):
    valid = _validity_tile(
        table_ref, row_valid_ref, cand_ref, cand_valid_ref, elab_ref,
        q_pos_ref, q_lab_ref, q_valid_ref,
        n_prev=n_prev, n_nbr=n_nbr, block_n=block_n,
    )
    out_ref[...] = valid.astype(jnp.int32).astype(jnp.int8)


def _embed_join_count_kernel(
    table_ref, row_valid_ref, cand_ref, cand_valid_ref, elab_ref,
    q_pos_ref, q_lab_ref, q_valid_ref,
    out_ref,         # (BR, 1) int32 — per-row survivor counts
    *,
    n_prev: int,
    n_nbr: int,
    block_n: int,
):
    valid = _validity_tile(
        table_ref, row_valid_ref, cand_ref, cand_valid_ref, elab_ref,
        q_pos_ref, q_lab_ref, q_valid_ref,
        n_prev=n_prev, n_nbr=n_nbr, block_n=block_n,
    )
    # the candidate axis is the innermost grid dim: the same (BR, 1) output
    # block is revisited across candidate tiles, so init at k == 0 and
    # accumulate — the classic Pallas reduction pattern
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.sum(
        valid.astype(jnp.int32), axis=1, keepdims=True
    )


def _call(kernel, name, out_spec, out_shape, table, row_valid, cand_list,
          cand_valid, elab_cols, q_pos, q_lab, q_valid, *, block_r, block_c,
          block_n, interpret):
    """Shared pallas_call plumbing of both kernels (same operands, grid)."""
    r, n_prev = table.shape
    c = cand_list.shape[1]
    n = elab_cols.shape[0]
    j = q_pos.shape[0]
    assert r % block_r == 0 and c % block_c == 0 and n % block_n == 0
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # the (N, BC) edge-label block is double-buffered; the J one-hot chunks
    # and accumulators are the rest of the working set
    work = 2 * n * block_c * 4 + (2 * j + 4) * block_r * max(block_n, block_c) * 4
    return pl.pallas_call(
        functools.partial(kernel, n_prev=n_prev, n_nbr=j, block_n=block_n),
        grid=(r // block_r, c // block_c),
        in_specs=[
            pl.BlockSpec((block_r, n_prev), lambda i, k: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, k: (0, k)),
            pl.BlockSpec((1, block_c), lambda i, k: (0, k)),
            pl.BlockSpec((n, block_c), lambda i, k: (0, k)),
            smem, smem, smem,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 * _MIB, work + 8 * _MIB),
        ),
        interpret=interpret,
        name=name,
    )(table, row_valid, cand_list, cand_valid, elab_cols, q_pos, q_lab,
      q_valid)


def embed_join_pallas(
    table,
    row_valid,
    cand_list,
    cand_valid,
    elab_cols,
    q_pos,
    q_lab,
    q_valid,
    *,
    block_r: int = 256,
    block_c: int = 128,
    block_n: int = 1024,
    interpret: bool = False,
):
    """(R, C) int8 validity grid.  Operands come tile-aligned from the
    wrapper: ``table`` (R, T), ``row_valid`` (R, 1), ``cand_list`` and
    ``cand_valid`` (1, C), ``elab_cols`` (N, C) f32 with R % block_r ==
    C % block_c == N % block_n == 0."""
    r = table.shape[0]
    c = cand_list.shape[1]
    return _call(
        _embed_join_kernel,
        "embed_join_emit",
        pl.BlockSpec((block_r, block_c), lambda i, k: (i, k)),
        jax.ShapeDtypeStruct((r, c), jnp.int8),
        table, row_valid, cand_list, cand_valid, elab_cols,
        q_pos, q_lab, q_valid,
        block_r=block_r, block_c=block_c, block_n=block_n,
        interpret=interpret,
    )


def embed_join_count_pallas(
    table,
    row_valid,
    cand_list,
    cand_valid,
    elab_cols,
    q_pos,
    q_lab,
    q_valid,
    *,
    block_r: int = 256,
    block_c: int = 128,
    block_n: int = 1024,
    interpret: bool = False,
):
    """(R, 1) int32 per-row survivor counts (the two-phase count pass).

    Same tiling contract as ``embed_join_pallas``; the (R, C) grid never
    leaves the core — each candidate tile folds its row-sums into the
    revisited (block_r, 1) output block."""
    r = table.shape[0]
    return _call(
        _embed_join_count_kernel,
        "embed_join_count",
        pl.BlockSpec((block_r, 1), lambda i, k: (i, 0)),
        jax.ShapeDtypeStruct((r, 1), jnp.int32),
        table, row_valid, cand_list, cand_valid, elab_cols,
        q_pos, q_lab, q_valid,
        block_r=block_r, block_c=block_c, block_n=block_n,
        interpret=interpret,
    )
