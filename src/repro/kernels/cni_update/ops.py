"""Jit'd public wrapper for the cni_update kernel (padding + table mgmt).

On CPU the kernel executes in Pallas ``interpret`` mode (bit-accurate body
semantics); on TPU it compiles to Mosaic.  ``use_kernel=False`` falls back to
the pure-jnp oracle — ``core.incremental.IncrementalIndex`` exposes this as
its ``use_kernel`` knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.cni import log_hbar_table
from repro.kernels.cni_update.kernel import cni_update_pallas
from repro.kernels.cni_update.ref import cni_update_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit, static_argnames=("d_max", "max_p", "block_f", "use_kernel")
)
def cni_update(
    rows: jnp.ndarray,
    delta: jnp.ndarray,
    *,
    d_max: int,
    max_p: int,
    block_f: int = 256,
    use_kernel: bool = True,
):
    """Fused frontier update: (rows, delta) (F, L) int32 ->
    (new_rows (F, L) int32, cni_log (F,) f32, deg (F,) int32)."""
    rows = jnp.asarray(rows, jnp.int32)
    delta = jnp.asarray(delta, jnp.int32)
    if not use_kernel:
        return cni_update_ref(rows, delta, d_max, max_p)
    f = rows.shape[0]
    pad = (-f) % block_f
    rows_p = jnp.pad(rows, ((0, pad), (0, 0)))
    delta_p = jnp.pad(delta, ((0, pad), (0, 0)))
    table = log_hbar_table(d_max, max_p)
    new_rows, log_out, deg_out = cni_update_pallas(
        rows_p,
        delta_p,
        table,
        d_max=d_max,
        max_p=max_p,
        block_f=block_f,
        interpret=not _on_tpu(),
    )
    return new_rows[:f], log_out[:f, 0], deg_out[:f, 0]
