"""Jit'd wrappers: pad to tile multiples, dispatch kernel/ref, cast.

Three entry points back the two-phase (count → scan → emit) device join
(core/search.py::device_join_search):

* ``embed_join``       — the (R, C) bool validity grid (one fused round);
* ``embed_join_count`` — per-row survivor counts, no grid materialization
  on the kernel path (the *count* pass);
* ``embed_join_emit``  — re-evaluates the grid and scatters each survivor's
  flat cell id into its prefix-summed output slot (the *emit* pass).

Each has an un-jitted ``*_raw`` twin with identical semantics — the
shard_map-compatible entry point: the mesh-partitioned enumerator
(core/distributed.py, DESIGN.md §13) calls the raw forms inside its
``shard_map`` bodies, where a nested ``jax.jit`` would only add dispatch
layering.  The public names below jit the raw forms for direct callers.

On TPU the Pallas kernels compile to Mosaic; elsewhere ``use_kernel=None``
(auto) runs the pure-jnp oracle *inside the same jit* — the device-resident
join stays one fused dispatch per phase on every backend, and
interpret-mode kernel execution is reserved for the parity tests
(``use_kernel=True`` off-TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.embed_join.kernel import (
    embed_join_count_pallas,
    embed_join_pallas,
)
from repro.kernels.embed_join.ref import (
    embed_join_count_ref,
    embed_join_ref,
    emit_slots_ref,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _block_n(n: int) -> int:
    """Contraction chunk of the one-hot gather: the lane-aligned N, capped
    at 1,024 rows so the (block_r, block_n) one-hot stays small at the
    vertex cap."""
    return min(1024, max(128, -(-n // 128) * 128))


def _padded_kernel_args(table, row_valid, cand_list, cand_valid, elab_cols,
                        q_pos, q_lab, q_valid, block_r, block_c):
    """Tile-align every operand the Pallas kernels consume, in the 2-D
    layouts they read: row vectors (R, 1), candidate vectors (1, C)."""
    r = table.shape[0]
    c = cand_list.shape[0]
    n = elab_cols.shape[0]
    pad_r = (-r) % block_r
    pad_c = (-c) % block_c
    pad_n = (-n) % _block_n(n)
    return (
        jnp.pad(table, ((0, pad_r), (0, 0))),
        jnp.pad(jnp.asarray(row_valid, jnp.int32), (0, pad_r))[:, None],
        jnp.pad(cand_list, (0, pad_c))[None, :],
        jnp.pad(jnp.asarray(cand_valid, jnp.int32), (0, pad_c))[None, :],
        jnp.pad(
            jnp.asarray(elab_cols, jnp.float32),
            ((0, pad_n), (0, pad_c)),
            constant_values=-1.0,
        ),
        jnp.asarray(q_pos, jnp.int32),
        jnp.asarray(q_lab, jnp.float32),
        jnp.asarray(q_valid, jnp.int32),
    )


def embed_join_raw(
    table,       # (R, T) int32 partial embeddings (matching order)
    row_valid,   # (R,) bool
    cand_list,   # (C,) int32
    cand_valid,  # (C,) bool
    elab_cols,   # (N, C) int32 data→candidate edge labels (−1 = none)
    q_pos,       # (J,) int32
    q_lab,       # (J,) int32
    q_valid,     # (J,) bool
    *,
    block_r: int = 256,
    block_c: int = 128,
    use_kernel: bool | None = None,
):
    """(R, C) bool validity grid for one join expansion round (un-jitted)."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return embed_join_ref(
            table, jnp.asarray(row_valid, bool),
            cand_list, jnp.asarray(cand_valid, bool),
            elab_cols, q_pos, q_lab, jnp.asarray(q_valid, bool),
        )
    r = table.shape[0]
    c = cand_list.shape[0]
    mask = embed_join_pallas(
        *_padded_kernel_args(table, row_valid, cand_list, cand_valid,
                             elab_cols, q_pos, q_lab, q_valid,
                             block_r, block_c),
        block_r=block_r,
        block_c=block_c,
        block_n=_block_n(elab_cols.shape[0]),
        interpret=not _on_tpu(),
    )
    return mask[:r, :c].astype(bool)


embed_join = jax.jit(
    embed_join_raw, static_argnames=("block_r", "block_c", "use_kernel")
)


def embed_join_count_raw(
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
    use_kernel: bool | None = None,
):
    """(R,) int32 per-row survivor counts (the two-phase *count* pass).

    On the kernel path the row-sum folds inside the Pallas grid loop, so
    only (R,) int32 leaves the core; the oracle reduces the ref grid."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return embed_join_count_ref(
            table, jnp.asarray(row_valid, bool),
            cand_list, jnp.asarray(cand_valid, bool),
            elab_cols, q_pos, q_lab, jnp.asarray(q_valid, bool),
        )
    r = table.shape[0]
    counts = embed_join_count_pallas(
        *_padded_kernel_args(table, row_valid, cand_list, cand_valid,
                             elab_cols, q_pos, q_lab, q_valid,
                             block_r, block_c),
        block_r=block_r,
        block_c=block_c,
        block_n=_block_n(elab_cols.shape[0]),
        interpret=not _on_tpu(),
    )
    return counts[:r, 0]


embed_join_count = jax.jit(
    embed_join_count_raw,
    static_argnames=("block_r", "block_c", "use_kernel"),
)


def embed_join_emit_raw(
    idx_map,     # (out_cap,) int32 — slot → flat cell id, scattered into
    table,       # (R, T) int32
    row_valid,   # (R,) bool
    cand_list,   # (C,) int32
    cand_valid,  # (C,) bool
    elab_cols,   # (N, C) int32
    q_pos,       # (J,) int32
    q_lab,       # (J,) int32
    q_valid,     # (J,) bool
    row_off,     # (R,) int32 — exclusive scan of per-row counts (global)
    row_base,    # () int32 — this slice's first row in the full table
    *,
    block_r: int = 256,
    block_c: int = 128,
    use_kernel: bool | None = None,
):
    """Scatter survivors' flat cell ids into their exact output slots.

    The *emit* pass of the two-phase join: re-evaluates the validity grid
    (kernel or oracle — bit-identical), ranks survivors within each row,
    and writes ``(row_base + r) * C + c`` at slot ``row_off[r] + rank``.
    Invalid cells address slot ``len(idx_map)`` and are dropped, so the
    buffer is written exactly ``Σ counts`` times — the exact-sizing
    invariant.  Returns the updated ``idx_map``; the caller decodes it
    with one gather (``table[idx // C]``, ``cand[idx % C]``)."""
    valid = embed_join_raw(
        table, row_valid, cand_list, cand_valid, elab_cols,
        q_pos, q_lab, q_valid,
        block_r=block_r, block_c=block_c, use_kernel=use_kernel,
    )
    slots = emit_slots_ref(valid, jnp.asarray(row_off, jnp.int32))
    out_cap = idx_map.shape[0]
    slots = jnp.where(valid, slots, out_cap)  # −1 → drop sentinel
    r = table.shape[0]
    c = cand_list.shape[0]
    cells = (
        (jnp.asarray(row_base, jnp.int32) + jnp.arange(r, dtype=jnp.int32))
        [:, None] * c
        + jnp.arange(c, dtype=jnp.int32)[None, :]
    )
    return idx_map.at[slots.reshape(-1)].set(
        cells.reshape(-1), mode="drop"
    )


embed_join_emit = jax.jit(
    embed_join_emit_raw,
    static_argnames=("block_r", "block_c", "use_kernel"),
)
