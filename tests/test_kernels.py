"""Pallas kernels: interpret-mode shape/dtype sweeps against pure-jnp oracles."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sparse import dg_laplace_2d, csr_to_bsr, random_spd
from repro.kernels.bsr_spmbv.kernel import bsr_spmbv_pallas
from repro.kernels.bsr_spmbv.ref import bsr_spmbv_ref
from repro.kernels.bsr_spmbv.ops import bsr_to_block_ell
from repro.kernels.fused_gram.kernel import fused_gram_pallas
from repro.kernels.fused_gram.ref import fused_gram_ref
from repro.kernels.block_update.kernel import block_update_pallas, ecg_tail_pallas
from repro.kernels.block_update.ref import block_update_ref, ecg_tail_ref


def tol_for(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


class TestBsrSpmbv:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("blk,t", [(8, 1), (8, 4), (16, 8), (8, 20)])
    def test_against_ref_and_dense(self, rng, blk, t, dtype):
        a = dg_laplace_2d((4, 3), block=blk, dtype=jnp.float32)
        b = csr_to_bsr(a, blk, blk)
        blocks, indices = bsr_to_block_ell(b)
        blocks = blocks.astype(dtype)
        v = jnp.asarray(rng.standard_normal((b.shape[1], t)), dtype)
        w_ref = bsr_spmbv_ref(blocks, indices, v)
        w_pal = bsr_spmbv_pallas(blocks, indices, v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(w_pal, np.float32), np.asarray(w_ref, np.float32), **tol_for(dtype)
        )
        if dtype == jnp.float32:
            ad = np.asarray(a.todense(), np.float64)
            np.testing.assert_allclose(
                np.asarray(w_pal, np.float64)[: a.shape[0]],
                ad @ np.asarray(v, np.float64),
                rtol=1e-4, atol=1e-4,
            )

    def test_irregular_block_rows(self, rng):
        """Rows with differing tile counts exercise the zero-padding path."""
        a = random_spd(48, density=0.15, seed=9)
        b = csr_to_bsr(a, 4, 4)
        blocks, indices = bsr_to_block_ell(b)
        per_row = np.diff(np.asarray(b.block_indptr))
        assert per_row.min() != per_row.max(), "want irregular structure"
        v = jnp.asarray(rng.standard_normal((b.shape[1], 3)), jnp.float32)
        w_pal = bsr_spmbv_pallas(blocks.astype(jnp.float32), indices, v, interpret=True)
        ad = np.asarray(a.todense(), np.float64)
        np.testing.assert_allclose(
            np.asarray(w_pal, np.float64)[:48], ad @ np.asarray(v, np.float64), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("matrix", ["dg", "random"])
    @pytest.mark.parametrize("conversion", ["sequential", "per_rank"])
    def test_lane_groups_against_dense(self, rng, matrix, conversion):
        """More than LANES block rows: every lane group g > 0 of the
        lane-major layout, through the sequential conversion and the
        distributed executor's per-rank one, checked against a dense A·V."""
        from repro.kernels.bsr_spmbv.ops import block_ell_arrays
        from repro.sparse.spmbv import _stack_block_ell

        a = (dg_laplace_2d((20, 16), block=4) if matrix == "dg"
             else random_spd(1280, density=0.004, seed=3))
        n, bs = a.shape[0], 4
        ad = np.asarray(a.todense(), np.float64)
        v = rng.standard_normal((n, 8))
        if conversion == "sequential":
            blocks, indices, m_pad, meta, _ = block_ell_arrays(a, bs, bs)
            assert meta["nbr"] > 2 * 128
            parts = [(np.arange(n), blocks, indices)]
        else:
            indptr, indices_all = np.asarray(a.indptr), np.asarray(a.indices)
            data, m_pad = np.asarray(a.data), n
            per_rank = []
            for rows in np.array_split(np.arange(n), 2):
                lo, hi = indptr[rows[0]], indptr[rows[-1] + 1]
                per_rank.append((rows, indptr[rows[0]: rows[-1] + 2] - lo,
                                 indices_all[lo:hi], data[lo:hi]))
            n_rows_max = max(len(r[0]) for r in per_rank)
            blocks, idx, _ = _stack_block_ell(per_rank, n_rows_max, n, bs, bs, np.float64)
            assert idx.shape[1] > 128
            parts = [(rows, jnp.asarray(blocks[r]), jnp.asarray(idx[r]))
                     for r, (rows, *_) in enumerate(per_rank)]
        vp = jnp.asarray(np.pad(v, ((0, m_pad - n), (0, 0))))
        for rows, blk, ix in parts:
            got = bsr_spmbv_pallas(blk, ix, vp, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got)[: len(rows)], ad[rows] @ v, rtol=1e-12, atol=1e-12
            )

    @pytest.mark.parametrize("case,vregs", [
        ("whole_groups", True),    # 256 block rows: two whole lane groups
        ("ragged_groups", True),   # 300 block rows: the third group ragged
        ("zero_slots", True),      # irregular tiles per row: zero-padded slots
        ("float64", True),         # the CPU oracle's precision
        ("t4", False),             # bc·t = 64: the lane path
        ("bfloat16", False),       # two rows per sublane: the lane path
    ])
    def test_vreg_path_against_lane_path(self, rng, case, vregs):
        """16x16 tiles at t = 8 take the vreg path (relayouts in VMEM,
        gather in grid order, W written as Wᵀ); it gives the lane path's
        W bit for bit, and the oracle's.  Other shapes keep the lane path."""
        from repro.kernels.bsr_spmbv import kernel as K
        from repro.kernels.bsr_spmbv.ops import block_ell_arrays

        a = {"whole_groups": dg_laplace_2d((16, 16), block=16),
             "ragged_groups": dg_laplace_2d((20, 15), block=16),
             "zero_slots": random_spd(800, density=0.01, seed=5)}.get(
                 case, dg_laplace_2d((8, 6), block=16))
        blocks, indices, m_pad, meta, _ = block_ell_arrays(a, 16, 16)
        dtype = {"float64": jnp.float64, "bfloat16": jnp.bfloat16}.get(case, jnp.float32)
        t = 4 if case == "t4" else 8
        blocks = blocks.astype(dtype)
        v = jnp.asarray(rng.standard_normal((m_pad, t)), dtype)
        if case == "zero_slots":
            assert meta["pad_hist"][-1] < meta["nbr"]
        nbg, kbc = blocks.shape[:2]
        nbr = indices.shape[0]
        assert K._on_vregs(16, 16, t, dtype) == vregs
        got = bsr_spmbv_pallas(blocks, indices, v, interpret=True)
        lane = K._product(
            blocks, K._gather_lanes(v, indices, bc=16, nbg=nbg, vregs=False, interpret=True),
            nbr=nbr, vregs=False, interpret=True)
        assert got.shape == lane.shape == (nbr * 16, t) and got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float64), np.asarray(lane, np.float64))
        want = bsr_spmbv_ref(blocks, indices, v)
        tol = tol_for(dtype) if dtype != jnp.float64 else dict(rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(got, np.float64), np.asarray(want, np.float64), **tol)

    def test_ell_spmbv_over_own_and_halo_rows(self, rng, monkeypatch):
        """The distributed executor's local product, ``_ell_spmbv`` over a
        rank's padded [own ‖ halo] block vector (the 2x2 cell's path, 16x16
        tiles at t = 8: the vreg path), equals the CSR rows' product."""
        import importlib

        from repro.sparse.partition import partition_csr, rebased_local_csr
        from repro.sparse.spmbv import DistributedSpMBV, _stack_block_ell

        monkeypatch.setattr(importlib.import_module("repro.kernels.bsr_spmbv.ops"),
                            "resolve_dispatch", lambda op, use: (True, True))
        a = dg_laplace_2d((12, 10), block=16)
        n = a.shape[0]
        ad = np.asarray(a.todense(), np.float64)
        v = rng.standard_normal((n, 8))
        pm = partition_csr(a, 2)
        rmax = pm.part.max_local_rows
        halo_max = max(len(h) for h in pm.halo_sources)
        assert halo_max > 0
        rebased = rebased_local_csr(pm)
        per_rank = [(np.arange(n_local), ptr, ix, dat) for ptr, ix, dat, n_local in rebased]
        blocks, idx, _ = _stack_block_ell(per_rank, rmax, rmax + halo_max, 16, 16, np.float64)
        for r in range(2):
            lo, hi = pm.part.local_range(r)
            own = np.zeros((rmax, 8))
            own[: hi - lo] = v[lo:hi]
            halo = np.zeros((halo_max, 8))
            halo[: len(pm.halo_sources[r])] = v[pm.halo_sources[r]]
            xfull = jnp.asarray(np.concatenate([own, halo]))
            w = DistributedSpMBV._ell_spmbv(None, xfull, jnp.asarray(blocks[r]), jnp.asarray(idx[r]))
            np.testing.assert_allclose(np.asarray(w)[: hi - lo], ad[lo:hi] @ v,
                                       rtol=1e-12, atol=1e-12)


class TestFusedGram:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,t,block_rows", [(64, 4, 16), (200, 5, 64), (1000, 20, 256), (37, 3, 8)])
    def test_against_ref(self, rng, n, t, block_rows, dtype):
        mats = [jnp.asarray(rng.standard_normal((n, t)), dtype) for _ in range(4)]
        got = fused_gram_pallas(*mats, block_rows=block_rows, interpret=True)
        want = fused_gram_ref(*mats)
        assert got.shape == (t, 3 * t)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=(3e-1 if n >= 1000 else 1e-1) if dtype == jnp.bfloat16 else 1e-3,
        )


class TestBlockUpdate:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,t,block_rows", [(64, 4, 16), (130, 7, 32), (512, 20, 128)])
    def test_against_ref(self, rng, n, t, block_rows, dtype):
        x, r, p, ap = (jnp.asarray(rng.standard_normal((n, t)), dtype) for _ in range(4))
        c = jnp.asarray(rng.standard_normal((t, t)), dtype)
        xo, ro = block_update_pallas(x, r, p, ap, c, block_rows=block_rows, interpret=True)
        xw, rw = block_update_ref(x, r, p, ap, c)
        np.testing.assert_allclose(np.asarray(xo, np.float32), np.asarray(xw, np.float32), **tol_for(dtype))
        np.testing.assert_allclose(np.asarray(ro, np.float32), np.asarray(rw, np.float32), **tol_for(dtype))


# ---------------------------------------------------------------------------
# hot-path sweeps: interpret-mode Pallas vs oracle over {f32, f64} x t {2,4,8}
# (the dtypes and widths the solver backend switch actually runs)
# ---------------------------------------------------------------------------
SWEEP_DTYPES = [jnp.float32, jnp.float64]
SWEEP_T = [2, 4, 8]


def sweep_tol(dtype):
    return dict(rtol=1e-12, atol=1e-12) if dtype == jnp.float64 else dict(rtol=2e-5, atol=2e-5)


class TestHotPathSweeps:
    @pytest.mark.parametrize("dtype", SWEEP_DTYPES)
    @pytest.mark.parametrize("t", SWEEP_T)
    def test_bsr_spmbv_sweep(self, rng, t, dtype):
        a = dg_laplace_2d((4, 3), block=8, dtype=jnp.float32)
        blocks, indices = bsr_to_block_ell(csr_to_bsr(a, 8, 8))
        blocks = blocks.astype(dtype)
        v = jnp.asarray(rng.standard_normal((a.shape[1], t)), dtype)
        got = bsr_spmbv_pallas(blocks, indices, v, interpret=True)
        want = bsr_spmbv_ref(blocks, indices, v)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64), **sweep_tol(dtype)
        )

    @pytest.mark.parametrize("dtype", SWEEP_DTYPES)
    @pytest.mark.parametrize("t", SWEEP_T)
    def test_fused_gram_sweep(self, rng, t, dtype):
        mats = [jnp.asarray(rng.standard_normal((300, t)), dtype) for _ in range(4)]
        got = fused_gram_pallas(*mats, block_rows=64, interpret=True)
        want = fused_gram_ref(*mats)
        assert got.shape == (t, 3 * t) and got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            **(dict(rtol=1e-12, atol=1e-11) if dtype == jnp.float64
               else dict(rtol=1e-4, atol=1e-3)),
        )

    @pytest.mark.parametrize("dtype", SWEEP_DTYPES)
    @pytest.mark.parametrize("t", SWEEP_T)
    def test_ecg_tail_sweep(self, rng, t, dtype):
        n = 210
        x, r, p, ap, po = (
            jnp.asarray(rng.standard_normal((n, t)), dtype) for _ in range(5)
        )
        c, d, do = (jnp.asarray(rng.standard_normal((t, t)), dtype) for _ in range(3))
        got = ecg_tail_pallas(x, r, p, ap, po, c, d, do, block_rows=64, interpret=True)
        want = ecg_tail_ref(x, r, p, ap, po, c, d, do)
        for g, w in zip(got, want):
            assert g.shape == (n, t) and g.dtype == dtype
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64), **sweep_tol(dtype)
            )


# ---------------------------------------------------------------------------
# edges of the (t, n) view: a ragged last block over several grid steps (the
# Gram's lane mask: interpret mode pads blocks with NaN), CG width t = 1, the
# packed widths 16 and 24, and the default lane step
# ---------------------------------------------------------------------------
TN_EDGES = [(1000, 8, 128), (1000, 1, 128), (1000, 16, 256), (1000, 24, 384),
            (20000, 8, None), (300, 24, None)]


class TestTnView:
    @pytest.mark.parametrize("dtype", SWEEP_DTYPES)
    @pytest.mark.parametrize("n,t,block_rows", TN_EDGES)
    def test_fused_gram_edges(self, rng, n, t, block_rows, dtype):
        mats = [jnp.asarray(rng.standard_normal((n, t)), dtype) for _ in range(4)]
        got = fused_gram_pallas(*mats, block_rows=block_rows, interpret=True)
        want = fused_gram_ref(*mats)
        assert got.shape == (t, 3 * t) and got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            **(dict(rtol=1e-12, atol=1e-10) if dtype == jnp.float64
               else dict(rtol=1e-4, atol=2e-3)),
        )

    @pytest.mark.parametrize("dtype", SWEEP_DTYPES)
    @pytest.mark.parametrize("n,t,block_rows", TN_EDGES)
    def test_ecg_tail_edges(self, rng, n, t, block_rows, dtype):
        x, r, p, ap, po = (jnp.asarray(rng.standard_normal((n, t)), dtype) for _ in range(5))
        c, d, do = (jnp.asarray(rng.standard_normal((t, t)), dtype) for _ in range(3))
        got = ecg_tail_pallas(x, r, p, ap, po, c, d, do, block_rows=block_rows, interpret=True)
        want = ecg_tail_ref(x, r, p, ap, po, c, d, do)
        for g, w in zip(got, want):
            assert g.shape == (n, t) and g.dtype == dtype
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64), **sweep_tol(dtype)
            )

    @pytest.mark.parametrize("n,t,block_rows", [(1000, 8, 128), (1000, 16, 256)])
    def test_block_update_edges(self, rng, n, t, block_rows):
        x, r, p, ap = (jnp.asarray(rng.standard_normal((n, t)), jnp.float64) for _ in range(4))
        c = jnp.asarray(rng.standard_normal((t, t)), jnp.float64)
        got = block_update_pallas(x, r, p, ap, c, block_rows=block_rows, interpret=True)
        for g, w in zip(got, block_update_ref(x, r, p, ap, c)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **sweep_tol(jnp.float64))

    @pytest.mark.parametrize("n,t,dtype,block_rows,want", [
        (1000, 8, jnp.float32, 128, 128),        # a multiple of 128 stays
        (1000, 8, jnp.float32, 100, 128),        # rounded up to one
        (1000, 8, jnp.float32, 1000, 1000),      # covers n: all of it
        (1000, 8, jnp.float32, 960, 1000),       # rounded up past n
        (1310720, 8, jnp.float32, None, 16384),  # (8, 16384) f32: 512 KiB
        (1310720, 1, jnp.float32, None, 16384),  # t pads to 8 sublanes
        (1310720, 16, jnp.float32, None, 8192),
        (1310720, 24, jnp.float32, None, 5376),  # rounded down to 128s
        (1310720, 8, jnp.bfloat16, None, 16384),  # bf16 packs 16 sublanes
        (5000, 8, jnp.float32, None, 5000),
    ])
    def test_lane_block(self, n, t, dtype, block_rows, want):
        from repro.kernels.lanes import lane_block

        assert lane_block(n, t, dtype, block_rows) == want


class TestWindowShuffled:
    """The Table-3 audikw_1 surrogate: element ids shuffled within windows
    (neighbour tiles scattered across the block columns, not in order as in
    DG) and a block-row count that leaves the last lane group ragged."""

    def test_spmbv_against_ref(self, rng):
        from repro.kernels.bsr_spmbv.ops import block_ell_arrays
        from repro.sparse import suite_surrogate

        a = suite_surrogate("audikw_1", scale=0.05)  # 12 x 12 elements of 16
        blocks, indices, m_pad, meta, _ = block_ell_arrays(a, 16, 16)
        assert meta["nbr"] == 144 and meta["nbr"] % 128 and meta["kmax"] == 5
        v = jnp.asarray(rng.standard_normal((m_pad, 8)))
        got = bsr_spmbv_pallas(blocks, indices, v, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(bsr_spmbv_ref(blocks, indices, v)),
                                   rtol=1e-12, atol=1e-12)
        ad = np.asarray(a.todense(), np.float64)
        np.testing.assert_allclose(np.asarray(got), ad @ np.asarray(v), rtol=1e-12, atol=1e-12)

    def test_convert_span_counters(self):
        from repro.observe import MemorySink, Tracer
        from repro.solver import ECGSolver, KernelConfig, SolverConfig
        from repro.sparse import suite_surrogate
        from repro.sparse.spmbv import _stack_block_ell

        def counters(a):
            sink = MemorySink()
            ECGSolver.build(a, None, SolverConfig(
                t=4, tol=1e-8, max_iters=10, tune="off",
                kernel=KernelConfig(backend="pallas", ell_block=(16, 16))),
                tracer=Tracer(sinks=[sink]))
            (span,) = [s for s in sink.spans if s.name == "build/convert"]
            return span.args

        dg = dg_laplace_2d((8, 8), block=16)
        # 64 elements of 5 tiles, less the 2 * (8 + 8) missing boundary neighbours
        got = counters(dg)
        assert got["ell_kmax"] == 5 and got["ell_tiles"] == 288
        assert got["ell_zero_slot_share"] == pytest.approx(32 / 320)
        assert got["gather_reach"] == 8  # the element grid's second side
        shuffled = counters(suite_surrogate("audikw_1", scale=8 / 243))  # 8 x 8 elements
        assert shuffled["ell_kmax"] == 5 and shuffled["ell_tiles"] == 288
        assert shuffled["gather_reach"] > 8
        # the distributed conversion's counters over two parts of the rows;
        # each part numbers its block rows from 0 and here keeps the global
        # columns, so the second part's gather reaches 32 + 8 block rows
        n, ptr, ix = dg.shape[0], np.asarray(dg.indptr), np.asarray(dg.indices)
        per_rank = [(rows, ptr[rows[0]: rows[-1] + 2] - ptr[rows[0]],
                     ix[ptr[rows[0]]: ptr[rows[-1] + 1]], np.ones(ptr[rows[-1] + 1] - ptr[rows[0]]))
                    for rows in np.array_split(np.arange(n), 2)]
        *_, parts = _stack_block_ell(per_rank, n // 2, n, 16, 16, np.float64)
        assert parts == dict(ell_kmax=5, ell_tiles=288, gather_reach=40,
                             ell_zero_slot_share=pytest.approx(1 - 288 / (2 * 32 * 5)))
