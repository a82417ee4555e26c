"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.cni import default_max_p
from repro.kernels.candidate_filter.ops import candidate_filter
from repro.kernels.candidate_filter.ref import candidate_filter_ref
from repro.kernels.cni_encode.ops import cni_encode
from repro.kernels.cni_encode.ref import cni_encode_ref
from repro.kernels.embed_join.ops import (
    embed_join,
    embed_join_count,
    embed_join_emit,
)
from repro.kernels.embed_join.ref import embed_join_count_ref, embed_join_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.rwkv6_wkv.ops import wkv6
from repro.kernels.rwkv6_wkv.ref import wkv6_ref

RNG = np.random.default_rng(1234)


class TestCniEncodeKernel:
    @pytest.mark.parametrize("v,L,d_max,block_v", [
        (64, 4, 8, 32),
        (130, 9, 24, 64),     # non-multiple of block — wrapper pads
        (256, 16, 32, 128),
        (33, 3, 6, 256),      # block larger than V
    ])
    def test_matches_ref(self, v, L, d_max, block_v):
        counts = RNG.integers(0, 3, size=(v, L)).astype(np.int32)
        mp = default_max_p(d_max, L)
        log_k, deg_k = cni_encode(
            jnp.asarray(counts), d_max=d_max, max_p=mp, block_v=block_v
        )
        log_r, deg_r = cni_encode_ref(jnp.asarray(counts), d_max, mp)
        np.testing.assert_array_equal(np.asarray(deg_k), np.asarray(deg_r))
        lk, lr = np.asarray(log_k), np.asarray(log_r)
        fin = np.isfinite(lr)
        assert (np.isfinite(lk) == fin).all()
        np.testing.assert_allclose(lk[fin], lr[fin], rtol=1e-5, atol=1e-5)


class TestEmbedJoinKernel:
    def _random_inputs(self, r, t, c, n, j, seed):
        rng = np.random.default_rng(seed)
        table = rng.integers(0, n, size=(r, t)).astype(np.int32)
        row_valid = rng.random(r) < 0.8
        cand = rng.integers(0, n, size=c).astype(np.int32)
        cand_valid = rng.random(c) < 0.8
        # sparse labeled adjacency (−1 = no edge), zero diagonal optional
        elab_cols = np.where(
            rng.random((n, c)) < 0.25,
            rng.integers(0, 3, size=(n, c)),
            -1,
        ).astype(np.int32)
        q_pos = rng.integers(0, t, size=j).astype(np.int32)
        q_lab = rng.integers(0, 3, size=j).astype(np.int32)
        q_valid = rng.random(j) < 0.7
        return (table, row_valid, cand, cand_valid, elab_cols,
                q_pos, q_lab, q_valid)

    @pytest.mark.parametrize("r,t,c,n,j,br,bc", [
        (64, 3, 32, 50, 2, 32, 16),
        (100, 1, 33, 40, 1, 64, 32),   # non-multiples — wrapper pads
        (16, 5, 128, 130, 4, 256, 64),  # blocks larger than R; N > 128
        # served widths: 16-vertex query (T = 15), J = 4, N > one
        # 1,024-row contraction chunk
        (300, 15, 200, 1100, 4, 256, 128),
    ])
    def test_matches_ref(self, r, t, c, n, j, br, bc):
        args = self._random_inputs(r, t, c, n, j, seed=r + c)
        jargs = tuple(map(jnp.asarray, args))
        mk = embed_join(*jargs, block_r=br, block_c=bc, use_kernel=True)
        mr = embed_join_ref(*jargs)
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))

    @pytest.mark.parametrize("r,t,c,n,j,br,bc", [
        (64, 3, 32, 50, 2, 32, 16),
        (100, 1, 33, 40, 1, 64, 32),   # non-multiples — wrapper pads
        (16, 5, 128, 130, 4, 256, 64),  # blocks larger than R; N > 128
        # served widths: 16-vertex query (T = 15), J = 4, N > one
        # 1,024-row contraction chunk
        (300, 15, 200, 1100, 4, 256, 128),
    ])
    def test_count_matches_ref(self, r, t, c, n, j, br, bc):
        """Count pass: the in-core row-sum kernel == oracle == grid sum."""
        args = self._random_inputs(r, t, c, n, j, seed=r + c)
        jargs = tuple(map(jnp.asarray, args))
        ck = embed_join_count(*jargs, block_r=br, block_c=bc,
                              use_kernel=True)
        cr = embed_join_count_ref(*jargs)
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        grid = np.asarray(embed_join_ref(*jargs))
        np.testing.assert_array_equal(
            np.asarray(cr), grid.sum(axis=1).astype(np.int32)
        )

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_emit_flat_row_major_order(self, use_kernel):
        """Emit pass: slot k of the idx_map holds the k-th survivor in
        flat row-major grid order (the bit-order contract the enumerator's
        truncation parity rests on); slack slots stay untouched and
        row_base shifts only the row component of the cell id."""
        r, t, c, n, j = 64, 3, 32, 50, 2
        args = self._random_inputs(r, t, c, n, j, seed=9)
        jargs = tuple(map(jnp.asarray, args))
        grid = np.asarray(embed_join_ref(*jargs))
        counts = grid.sum(axis=1).astype(np.int32)
        row_off = np.cumsum(counts, dtype=np.int32) - counts
        total = int(counts.sum())
        assert total > 0
        out_cap = total + 5  # deliberate slack: must keep its fill value
        fill = np.full(out_cap, -7, np.int32)
        ri, ci = np.nonzero(grid)  # numpy nonzero IS flat row-major order
        for row_base in (0, 100):
            got = np.asarray(embed_join_emit(
                jnp.asarray(fill), *jargs,
                jnp.asarray(row_off), jnp.asarray(row_base, jnp.int32),
                block_r=32, block_c=16, use_kernel=use_kernel,
            ))
            np.testing.assert_array_equal(got[:total],
                                          (ri + row_base) * c + ci)
            np.testing.assert_array_equal(got[total:], -7)

    def test_inert_constraint_rows_pass_all(self):
        """q_valid=False rows (padding) must never constrain the join."""
        args = list(self._random_inputs(32, 2, 16, 20, 1, seed=3))
        args[7] = np.zeros(1, bool)  # no valid constraints
        jargs = tuple(map(jnp.asarray, args))
        got = np.asarray(embed_join(*jargs, block_r=32, block_c=16,
                                    use_kernel=True))
        # only injectivity + row/cand validity remain
        inj = (args[0][:, :, None] != args[2][None, None, :]).all(axis=1)
        exp = inj & args[1][:, None] & args[3][None, :]
        np.testing.assert_array_equal(got, exp)


class TestCniUpdateKernel:
    def test_matches_ref_at_human_widths(self):
        """HUMAN's 44 labels at its index ``d_max`` (64) and ``max_p``;
        the small-width case lives in test_incremental.py."""
        from repro.kernels.cni_update.ops import cni_update
        from repro.kernels.cni_update.ref import cni_update_ref

        f, L, d_max = 300, 44, 64
        rng = np.random.default_rng(f + L)
        mp = default_max_p(d_max, L)
        # sparse rows, one empty row and one at exactly d_max; deltas
        # that would push a row past d_max are dropped
        rows = (rng.integers(0, 3, size=(f, L))
                * (rng.random((f, L)) < 4.0 / L)).astype(np.int32)
        rows[:2] = 0
        np.add.at(rows[1], rng.integers(0, L, size=d_max), 1)
        delta = np.maximum(
            rng.integers(-1, 2, size=(f, L)).astype(np.int32), -rows
        )
        delta[:2] = 0
        delta[(rows + delta).sum(axis=1) > d_max] = 0
        nr_k, log_k, deg_k = cni_update(
            jnp.asarray(rows), jnp.asarray(delta), d_max=d_max, max_p=mp,
        )
        nr_r, log_r, deg_r = cni_update_ref(
            jnp.asarray(rows), jnp.asarray(delta), d_max, mp
        )
        assert int(np.asarray(deg_r)[1]) == d_max
        np.testing.assert_array_equal(np.asarray(nr_k), np.asarray(nr_r))
        np.testing.assert_array_equal(np.asarray(deg_k), np.asarray(deg_r))
        lk, lr = np.asarray(log_k), np.asarray(log_r)
        fin = np.isfinite(lr)
        assert (np.isfinite(lk) == fin).all()
        np.testing.assert_allclose(lk[fin], lr[fin], rtol=1e-5, atol=1e-5)


class TestCandidateFilterKernel:
    @pytest.mark.parametrize("v,u,block_v", [(128, 5, 64), (500, 17, 128),
                                             (64, 1, 512)])
    def test_matches_ref(self, v, u, block_v):
        args = (
            RNG.integers(0, 4, size=v).astype(np.int32),
            RNG.integers(0, 10, size=v).astype(np.int32),
            (RNG.normal(size=v) * 5).astype(np.float32),
            RNG.integers(1, 4, size=u).astype(np.int32),
            RNG.integers(0, 10, size=u).astype(np.int32),
            (RNG.normal(size=u) * 5).astype(np.float32),
        )
        jargs = tuple(map(jnp.asarray, args))
        mk = candidate_filter(*jargs, block_v=block_v)
        mr = candidate_filter_ref(*jargs)
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))

    def test_matches_exact_limb_filter_on_graph(self):
        """Log-space kernel filter ⊇ exact filter (ε-tolerance only widens)."""
        from repro.core import ilgf
        from repro.graphs import random_labeled_graph, random_walk_query

        g = random_labeled_graph(200, 700, 5, seed=3)
        q = random_walk_query(g, 5, sparse=True, seed=4)
        exact = np.asarray(ilgf(g, q, variant="cni").candidates)
        logv = np.asarray(ilgf(g, q, variant="cni_log").candidates)
        assert not np.any(exact & ~logv), "log filter must not over-prune"


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("b,hq,hkv,s,d,causal,window", [
        (2, 4, 2, 128, 32, True, None),
        (1, 8, 8, 96, 16, True, None),    # padded seq
        (1, 4, 1, 64, 64, True, 32),      # MQA + sliding window
        (2, 2, 2, 80, 32, False, None),   # bidirectional (encoder)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, hq, hkv, s, d, causal, window, dtype):
        q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), dtype)
        k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
        v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
        out_k = flash_attention(q, k, v, causal, window, 0, 64, 64, True)
        out_r = mha_ref(q, k, v, causal=causal, window=window)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=tol, atol=tol,
        )

    def test_decode_offset(self):
        q = jnp.asarray(RNG.normal(size=(2, 4, 1, 32)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(2, 2, 100, 32)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(2, 2, 100, 32)), jnp.float32)
        out_k = flash_attention(q, k, v, True, None, 99, 64, 64, True)
        out_r = mha_ref(q, k, v, causal=True, q_offset=99)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_r), rtol=2e-5, atol=2e-5
        )

    def test_grad_path_works(self):
        import jax

        q = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)), jnp.float32)

        def loss_k(q, k, v):
            return flash_attention(q, k, v).sum()

        def loss_r(q, k, v):
            return mha_ref(q, k, v, causal=True).sum()

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestWkv6Kernel:
    @pytest.mark.parametrize("b,h,t,dk,dv,bt", [
        (2, 3, 70, 16, 16, 32),   # padded T
        (1, 2, 64, 32, 16, 32),   # dk != dv
        (1, 1, 128, 64, 64, 64),
    ])
    def test_matches_ref(self, b, h, t, dk, dv, bt):
        r = jnp.asarray(RNG.normal(size=(b, h, t, dk)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(b, h, t, dk)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(b, h, t, dv)), jnp.float32)
        w = jnp.asarray(RNG.uniform(0.2, 0.99, size=(b, h, t, dk)), jnp.float32)
        u = jnp.asarray(RNG.normal(size=(h, dk)), jnp.float32)
        s0 = jnp.asarray(RNG.normal(size=(b, h, dk, dv)), jnp.float32)
        o_k, s_k = wkv6(r, k, v, w, u, s0, bt, True)
        o_r, s_r = wkv6_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                   rtol=2e-4, atol=2e-4)

    def test_state_chaining(self):
        """Running two halves with carried state == one full run."""
        b, h, t, d = 1, 2, 64, 16
        r = jnp.asarray(RNG.normal(size=(b, h, t, d)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(b, h, t, d)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(b, h, t, d)), jnp.float32)
        w = jnp.asarray(RNG.uniform(0.5, 0.99, size=(b, h, t, d)), jnp.float32)
        u = jnp.asarray(RNG.normal(size=(h, d)), jnp.float32)
        s0 = jnp.zeros((b, h, d, d), jnp.float32)
        o_full, s_full = wkv6(r, k, v, w, u, s0, 32, True)
        o1, s1 = wkv6(r[:, :, :32], k[:, :, :32], v[:, :, :32], w[:, :, :32],
                      u, s0, 32, True)
        o2, s2 = wkv6(r[:, :, 32:], k[:, :, 32:], v[:, :, 32:], w[:, :, 32:],
                      u, s1, 32, True)
        np.testing.assert_allclose(np.asarray(o_full[:, :, :32]), np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o_full[:, :, 32:]), np.asarray(o2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                                   rtol=1e-4, atol=1e-4)
