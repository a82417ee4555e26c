"""Pallas TPU kernel: fused count-delta apply + CNI digest re-encode.

The incremental-maintenance hot loop (core/incremental.py): after an edge
batch, only the *touched-vertex frontier* needs new digests.  The host
gathers the frontier's count rows and the batch's per-row count deltas; the
kernel fuses the scatter-add (``rows + delta``) with the digest re-encode so
updated counts never round-trip through HBM between the two steps.

Tiling mirrors cni_encode: the frontier dimension is blocked into
VMEM-resident (BF × L) tiles; the (D_max+1 × max_p+1) log-ħ table rides
along in VMEM.  Everything inside the tile is dense VPU work, phrased in
operations Mosaic lowers (no reversal, no in-kernel scan, no gather):

* the descending label expansion and its prefix sums come from one static
  pass over the L labels, highest first: with ``S`` the count of labels
  above label l, positions ``[S, S + c_l)`` hold label l, so the prefix sum
  at position j gains ``(l+1) · clip(j + 1 − S, 0, c_l)``;
* the table lookup ``ħ(j+1, p_j)`` is a loop over the D positions, each a
  masked lane-select of the table row ``j+1`` — exact, one value survives;
* a streaming-free logsumexp (max, then Σ exp) over the D terms.

TPU adaptation notes (DESIGN.md §3): the exact two-limb integer digests are
maintained host-side (no 64-bit integer datapath on TPU); the kernel
maintains the *log-space* digest (f32) the candidate-filter fast path
compares with ε tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIB = 1 << 20


def _cni_update_kernel(
    rows_ref,     # (BF, L) int32 — frontier count rows (pre-update)
    delta_ref,    # (BF, L) int32 — per-row count deltas (±)
    table_ref,    # (D+1, P+1) f32 log ħ
    out_rows_ref,  # (BF, L) int32 — updated count rows
    out_log_ref,  # (BF, 1) f32
    out_deg_ref,  # (BF, 1) int32
    *,
    d_max: int,
    max_p: int,
):
    counts = rows_ref[...] + delta_ref[...]
    out_rows_ref[...] = counts
    bf, n_lab = counts.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (bf, d_max), 1)
    # descending expansion: labels from the highest ord value down; `above`
    # counts the neighbors already placed, so after the pass it is the degree
    above = jnp.zeros((bf, 1), jnp.int32)
    prefix = jnp.zeros((bf, d_max), jnp.int32)
    for lab in reversed(range(n_lab)):
        c = counts[:, lab : lab + 1]                       # (BF, 1)
        prefix = prefix + (lab + 1) * jnp.clip(pos + 1 - above, 0, c)
        above = above + c
    deg = above                                            # (BF, 1)
    valid = pos < deg
    p = jnp.clip(prefix, 0, max_p)

    iota_p = jax.lax.broadcasted_iota(jnp.int32, (1, max_p + 1), 1)

    def lookup(j, terms):
        pj = jnp.sum(jnp.where(pos == j, p, 0), axis=1, keepdims=True)
        row = table_ref[pl.ds(j + 1, 1), :]               # ħ(j+1, ·)
        t = jnp.sum(jnp.where(iota_p == pj, row, 0.0), axis=1, keepdims=True)
        return jnp.where(pos == j, t, terms)

    terms = jax.lax.fori_loop(
        0, d_max, lookup, jnp.zeros((bf, d_max), jnp.float32)
    )
    neg_inf = jnp.float32(-jnp.inf)
    terms = jnp.where(valid, terms, neg_inf)
    m = jnp.max(terms, axis=1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    s = jnp.sum(jnp.where(valid, jnp.exp(terms - m_safe), 0.0), axis=1,
                keepdims=True)
    out = m_safe + jnp.log(jnp.maximum(s, 1e-30))
    out_log_ref[...] = jnp.where(deg > 0, out, neg_inf)
    out_deg_ref[...] = deg


def cni_update_pallas(
    rows: jnp.ndarray,
    delta: jnp.ndarray,
    log_table: jnp.ndarray,
    *,
    d_max: int,
    max_p: int,
    block_f: int = 256,
    interpret: bool = False,
):
    """rows/delta (F, L) int32 -> (new_rows (F, L) int32, cni_log (F, 1)
    f32, deg (F, 1) int32).  F must be a multiple of block_f (the wrapper
    pads)."""
    f, n_lab = rows.shape
    assert f % block_f == 0
    # the table block is double-buffered; each lookup step holds a few
    # (BF, P+1) lane-select temporaries
    work = 2 * log_table.size * 4 + 4 * block_f * (max_p + 1) * 4
    kernel = functools.partial(_cni_update_kernel, d_max=d_max, max_p=max_p)
    return pl.pallas_call(
        kernel,
        grid=(f // block_f,),
        in_specs=[
            pl.BlockSpec((block_f, n_lab), lambda i: (i, 0)),
            pl.BlockSpec((block_f, n_lab), lambda i: (i, 0)),
            pl.BlockSpec(log_table.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_f, n_lab), lambda i: (i, 0)),
            pl.BlockSpec((block_f, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_f, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((f, n_lab), jnp.int32),
            jax.ShapeDtypeStruct((f, 1), jnp.float32),
            jax.ShapeDtypeStruct((f, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 * _MIB, work + 8 * _MIB),
        ),
        interpret=interpret,
        name="cni_update",
    )(rows, delta, log_table)
