"""Distributed SpMBV:  W = A · V  with node-aware halo exchange (shard_map).

The matrix is row-partitioned over a ("node", "proc") device grid; block
vectors share the row distribution (paper §3).  The halo exchange replays a
static :class:`~repro.core.node_aware.ExchangePlan` — then the local SpMBV
runs on [own rows ‖ halo rows].

The executor is *phase-packed*: the plan's steps are grouped into phases
(consecutive rounds sharing axis/src/dst, see ``ExchangePlan.phases``), and
each phase is executed as ONE ``halo_pack`` kernel (a fused gather into a
contiguous, persistent send-buffer layout), one ``lax.ppermute`` per
nonzero rotation offset, and ONE ``halo_unpack`` kernel (fused scatter into
the halo/stage slots).  Gather/scatter dispatches are therefore O(phases)
instead of O(steps), and the ppermute payload is exactly the packed bytes.

The executor is also *width-aware*: ``matvec_fn(t_active=...)`` applies the
operator at a reduced block width through ``plan.at_width(t_active)`` — the
per-width index arrays are re-sliced on the host (cheap, cached) and the
wire payload shrinks to ``t_active·rows·f`` bytes.  The adaptive solver
uses this to stop paying full-width exchange bytes for retired search
directions (see ``distributed_ecg``).

Three orthogonal execution levers, all fixed at setup time (and all
selectable by the :mod:`repro.tune` autotuner via ``tune="model"|"measure"``
instead of by hand):

* ``backend="jnp" | "pallas"`` — the local SpMBV formulation.  ``jnp`` is the
  scalar-gather CSR ``segment_sum`` reference; ``pallas`` converts each
  rank's local [own ‖ halo] CSR block to Block-ELL once (see
  ``repro.kernels.bsr_spmbv``) so every local product is a pipeline of dense
  (br x bc) @ (bc x t) MXU matmuls.  The one-time conversion cost is
  O(nnz log nnz) host work plus a kmax/nnz_tile densification factor in
  device memory — amortized over all solver iterations.
* ``ell_block=(br, bc)`` — the Block-ELL tile shape for the pallas backend.
  The right shape trades zero-fill flops against MXU/sublane utilization and
  depends on t and the matrix's block structure; the tuner picks it from the
  block-structure histogram (see ``repro.tune``).
* ``overlap=True`` — comm/compute overlap.  At partition time local rows are
  split into *interior* rows (no halo-column dependence) and *boundary* rows
  (see :func:`repro.sparse.partition.interior_boundary_split`; with the
  pallas backend the split is block-row-granular so it never re-fragments
  the tiles).  The device program then issues the interior SpMBV with **no
  data dependence on the ppermute rounds**, so XLA's latency-hiding
  scheduler can run it while the inter-node messages of the ExchangePlan are
  in flight; only the boundary rows wait on the halo.  This is the
  node-aware analogue of the paper's pipeline: the exchange latency is
  hidden behind |interior|/|local| of the SpMBV flops.

Col-split plans (wide-halo payload splitting, nodal-optimal strategy) are
transparent here: the executor reshapes ``(rmax, t) -> (rmax·cs, t/cs)``
around the exchange rounds and reassembles whole halo rows afterwards — see
``repro.core.node_aware``.

This module also provides the distributed ECG wrapper: the same iteration
body as :func:`repro.core.ecg.ecg_solve` with `psum` reductions, executed
entirely inside one shard_map (so the two fused allreduces of §3.1 appear as
exactly two psums per iteration in the lowered HLO).  With
``backend="pallas"`` the packed gram product runs through
``kernels/fused_gram`` and the X/R/Z tail through
``kernels/block_update.ecg_tail`` — per-device Pallas kernels feeding the
same two psums.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.sparse.csr import CSRMatrix
from repro.sparse.partition import (
    PartitionedMatrix,
    interior_boundary_split,
    partition_csr,
    rebased_local_csr,
)
from repro.core.node_aware import ExchangePlan, build_exchange_plan
from repro.kernels.bsr_spmbv.kernel import LANES
from repro.kernels.bsr_spmbv.ops import (
    block_ell_tile_keys,
    bsr_spmbv,
    csr_arrays_to_block_ell,
    tile_counters,
)
from repro.kernels.halo_pack.ops import halo_pack, halo_unpack
from repro.observe import scopes


@dataclasses.dataclass
class DistributedSpMBV:
    """Device-ready distributed SpMBV operator.

    ``backend`` selects the local SpMBV formulation (CSR segment-sum vs the
    Block-ELL Pallas kernel); ``overlap`` selects the split interior/boundary
    schedule that hides the halo exchange behind interior compute.  The
    corresponding device arrays live in ``ell`` (pallas, blocking) and
    ``split`` (either backend, overlapped); see ``make_distributed_spmbv``.
    """

    mesh: Mesh
    plan: ExchangePlan
    n: int                 # true global rows
    rmax: int              # padded rows per device
    starts: np.ndarray     # (p+1,) partition row offsets (true global ids)
    # stacked per-device CSR (sharded on axis 0 at call time); None when the
    # selected (backend, overlap) mode never reads it — only the matrix
    # representation the device program actually consumes is device_put
    indptr: jax.Array | None   # (p, rmax + 1)
    indices: jax.Array | None  # (p, nnz_max) — local ids; halo ids offset by rmax
    data: jax.Array | None     # (p, nnz_max)
    # stacked per-PHASE exchange arrays (packed executor) at the compiled width
    gathers: list[jax.Array]
    scatters: list[jax.Array]
    backend: str = "jnp"
    overlap: bool = False
    ell_block: int | tuple[int, int] = 8  # Block-ELL tile shape (br, bc)
    # pallas blocking path: Block-ELL of the full [own ‖ halo] local block,
    # and its structure (kernels.bsr_spmbv.ops.tile_counters)
    ell: dict = dataclasses.field(default_factory=dict)
    ell_counters: dict = dataclasses.field(default_factory=dict)
    # overlap path: interior/boundary structures (CSR or Block-ELL per backend)
    split: dict = dataclasses.field(default_factory=dict)
    # TunedConfig when the operator was built via tune= (None otherwise)
    tuned: object = None
    # per-width device index arrays, filled on demand by width re-slices
    _width_arrays: dict = dataclasses.field(default_factory=dict)

    @property
    def p(self) -> int:
        return self.plan.p

    @property
    def n_padded(self) -> int:
        return self.p * self.rmax

    # ---------------------------------------------------------------- spec
    @property
    def vec_spec(self) -> P:
        return P(("node", "proc"), None)

    def shard_vector(self, v: np.ndarray | jax.Array, t: int | None = None) -> jax.Array:
        """Lay out a global (n,) or (n, t) array into the padded per-rank
        layout (device r's block holds its partition rows) and device_put."""
        v = np.asarray(v)
        out = np.zeros((self.p * self.rmax,) + v.shape[1:], v.dtype)
        for r in range(self.p):
            lo, hi = self.starts[r], self.starts[r + 1]
            out[r * self.rmax : r * self.rmax + (hi - lo)] = v[lo:hi]
        spec = self.vec_spec if v.ndim > 1 else P(("node", "proc"))
        return jax.device_put(out, NamedSharding(self.mesh, spec))

    def unshard(self, w: jax.Array) -> np.ndarray:
        """Inverse of :meth:`shard_vector`."""
        w = np.asarray(w)
        out = np.zeros((self.n,) + w.shape[1:], w.dtype)
        for r in range(self.p):
            lo, hi = self.starts[r], self.starts[r + 1]
            out[lo:hi] = w[r * self.rmax : r * self.rmax + (hi - lo)]
        return out

    def padded_mask(self) -> np.ndarray:
        """(n_padded,) 1.0 where the slot backs a true row."""
        m = np.zeros(self.p * self.rmax)
        for r in range(self.p):
            lo, hi = self.starts[r], self.starts[r + 1]
            m[r * self.rmax : r * self.rmax + (hi - lo)] = 1.0
        return m

    def true_row_of_slot(self) -> np.ndarray:
        """(n_padded,) true global row id per padded slot (-1 for pads)."""
        m = np.full(self.p * self.rmax, -1, dtype=np.int64)
        for r in range(self.p):
            lo, hi = self.starts[r], self.starts[r + 1]
            m[r * self.rmax : r * self.rmax + (hi - lo)] = np.arange(lo, hi)
        return m

    # ------------------------------------------------------------- exchange
    def _exchange(self, x_local: jax.Array, plan: ExchangePlan, gathers, scatters) -> jax.Array:
        """Per-device packed halo exchange.  x_local: (rmax, t) block rows;
        returns the halo block in row units, (plan.halo_rows, t).

        One ``halo_pack`` + ``halo_unpack`` pair per *phase* (fused gather/
        scatter over all of the phase's rounds), one ppermute per nonzero
        rotation offset operating on a static slice of the packed buffer.

        Col-split plans index (row, column-segment) slots: the executor
        reshapes ``(rmax, t) -> (rmax·cs, t/cs)`` around the rounds (padding
        t up to a multiple of cs when the applied width differs from the
        width the plan was sliced for, e.g. the width-1 initial residual)."""
        with jax.named_scope(scopes.EXCHANGE):
            return self._exchange_rounds(x_local, plan, gathers, scatters)

    def _exchange_rounds(self, x_local, plan: ExchangePlan, gathers, scatters):
        t = x_local.shape[-1]
        cs = plan.col_split
        if cs > 1:
            tp = -(-t // cs) * cs
            if tp != t:
                x_local = jnp.pad(x_local, ((0, 0), (0, tp - t)))
            xs = x_local.reshape(self.rmax * cs, tp // cs)
        else:
            xs = x_local
        w = xs.shape[-1]
        halo = jnp.zeros((plan.halo_size + 1, w), x_local.dtype)
        stage = jnp.zeros((plan.stage_size + 1, w), x_local.dtype)
        for phase, g_idx, s_pos in zip(plan.phases, gathers, scatters):
            src = xs if phase.src == "x" else stage
            buf = halo_pack(src, g_idx)  # (phase.width, w) — one dispatch
            if any(phase.offsets):
                axis = ("node", "proc") if phase.axis == "flat" else phase.axis
                parts = []
                for i, off in enumerate(phase.offsets):
                    seg = buf[phase.bounds[i] : phase.bounds[i + 1]]
                    if off:
                        seg = jax.lax.ppermute(
                            seg, axis, _perm(phase.axis, off, plan)
                        )
                    parts.append(seg)
                buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            if phase.dst == "halo":
                halo = halo_unpack(halo, buf, s_pos)
            else:
                stage = halo_unpack(stage, buf, s_pos)
        halo = halo[: plan.halo_size]
        if cs > 1:
            halo = halo.reshape(plan.halo_rows, -1)[:, :t]
        return halo

    # -------------------------------------------------------- local kernels
    def _csr_rows_spmbv(self, xfull, indptr, indices, data, n_rows: int):
        """CSR SpMBV over a (possibly gathered) row set; returns (n_rows, t)."""
        rows = jnp.repeat(
            jnp.arange(n_rows, dtype=jnp.int32),
            jnp.diff(indptr),
            total_repeat_length=indices.shape[0],
        )
        prod = data[:, None] * xfull[indices]
        return jax.ops.segment_sum(prod, rows, num_segments=n_rows)

    def _local_spmbv(self, x_local, halo, indptr, indices, data):
        """CSR SpMBV over [own ‖ halo] rows; returns (rmax, t)."""
        xfull = jnp.concatenate([x_local, halo], axis=0)
        return self._csr_rows_spmbv(xfull, indptr, indices, data, self.rmax)

    def _ell_spmbv(self, xfull, blocks, indices):
        """Block-ELL SpMBV; pads xfull to the tile grid the blocks index."""
        bc = blocks.shape[1] // indices.shape[-1]
        m_pad = (xfull.shape[0] + bc - 1) // bc * bc
        vp = jnp.pad(xfull, ((0, m_pad - xfull.shape[0]), (0, 0)))
        return bsr_spmbv(blocks, indices, vp)

    # ------------------------------------------------- width-sliced arrays
    def exchange_arrays(self, plan: ExchangePlan):
        """Stacked per-phase device index arrays for ``plan`` (cached by the
        plan's width — the host-side cost of a width re-slice event)."""
        key = (plan.t, plan.col_split)
        hit = self._width_arrays.get(key)
        if hit is not None:
            return hit
        sharding = NamedSharding(self.mesh, P(("node", "proc")))
        put = lambda arr: jax.device_put(jnp.asarray(arr), sharding)
        arrays = (
            [put(ph.gather_idx) for ph in plan.phases],
            [put(ph.scatter_pos) for ph in plan.phases],
        )
        self._width_arrays[key] = arrays
        return arrays

    # ------------------------------------------------------------------ api
    def matvec_fn(self, t_active: int | None = None):
        """Returns f(V_sharded (n_padded, t)) -> (n_padded, t), jit-able.

        ``t_active`` applies the operator through the width-sliced sub-plan
        ``plan.at_width(t_active)`` — same matrix arrays, re-sliced exchange
        index arrays, wire payload of exactly t_active columns.  The block
        vectors passed to the returned function must then carry ``t_active``
        columns."""
        plan = self.plan if t_active is None else self.plan.at_width(t_active)
        if plan is self.plan or plan.phases is self.plan.phases:
            # width-sliced plans with shared index arrays (col_split divides
            # t_active) reuse the device-resident copies — no re-upload
            gathers_, scatters_ = self.gathers, self.scatters
        else:
            gathers_, scatters_ = self.exchange_arrays(plan)
        k = len(plan.phases)

        def per_device(v, csr, ell, split, *exchange_arrays):
            gathers = [a[0] for a in exchange_arrays[:k]]
            scatters = [a[0] for a in exchange_arrays[k:]]
            shape = v.shape
            v = v.reshape(self.rmax, -1)
            t = v.shape[1]
            if not self.overlap:
                halo = self._exchange(v, plan, gathers, scatters)
                if self.backend == "pallas":
                    xfull = jnp.concatenate([v, halo], axis=0)
                    w = self._ell_spmbv(xfull, ell["blocks"][0], ell["indices"][0])
                    w = w[: self.rmax]
                else:
                    w = self._local_spmbv(
                        v, halo, csr["indptr"][0], csr["indices"][0], csr["data"][0]
                    )
            else:
                sp = {key: arr[0] for key, arr in split.items()}
                n_int = sp["int_rows"].shape[0]
                n_bnd = sp["bnd_rows"].shape[0]
                w = jnp.zeros((self.rmax + 1, t), v.dtype)  # +1 = dump row
                # Interior SpMBV reads only own rows — no data dependence on
                # the ppermute rounds below, so it overlaps the exchange.
                if n_int:
                    if self.backend == "pallas":
                        w_int = self._ell_spmbv(v, sp["int_blocks"], sp["int_idx"])[:n_int]
                    else:
                        w_int = self._csr_rows_spmbv(
                            v, sp["int_indptr"], sp["int_indices"], sp["int_data"], n_int
                        )
                    w = w.at[sp["int_rows"]].add(w_int)
                halo = self._exchange(v, plan, gathers, scatters)
                # Only the boundary rows wait on the halo.
                if n_bnd:
                    xfull = jnp.concatenate([v, halo], axis=0)
                    if self.backend == "pallas":
                        w_bnd = self._ell_spmbv(xfull, sp["bnd_blocks"], sp["bnd_idx"])[:n_bnd]
                    else:
                        w_bnd = self._csr_rows_spmbv(
                            xfull, sp["bnd_indptr"], sp["bnd_indices"], sp["bnd_data"], n_bnd
                        )
                    w = w.at[sp["bnd_rows"]].add(w_bnd)
                w = w[: self.rmax]
            return w.reshape(shape)

        dev_specs = P(("node", "proc"),)
        smapped = shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(self.vec_spec, dev_specs, dev_specs, dev_specs)
            + (dev_specs,) * (2 * k),
            out_specs=self.vec_spec,
            check_vma=False,
        )

        def apply(v):
            csr = (
                {}
                if self.indptr is None
                else {"indptr": self.indptr, "indices": self.indices, "data": self.data}
            )
            return smapped(v, csr, self.ell, self.split, *gathers_, *scatters_)

        return apply

    def masked_matvec_fn(self, t_active: int):
        """Width-compacted apply for the adaptive solver.

        Returns ``f(V (n_padded, t), active (t,) bool) -> (n_padded, t)``:
        the ``t_active`` active columns (zero-masked block vectors guarantee
        the rest are zero) are gathered to the front, pushed through the
        width-``t_active`` operator — so the halo exchange moves exactly
        ``t_active`` columns of bytes — and scattered back into a zero
        (n, t) block.  Bit-exact vs the full-width apply: column gather/
        scatter is pure data movement and A·0 = 0 for the retired columns.
        """
        apply_active = self.matvec_fn(t_active=t_active)

        def apply(v, active):
            # stable argsort: active columns first, original order preserved
            cols = jnp.argsort(~active)[:t_active]
            vc = jnp.take(v, cols, axis=1)
            wc = apply_active(vc)
            return jnp.zeros_like(v).at[:, cols].set(wc)

        return apply


def _perm(axis: str, offset: int, plan: ExchangePlan):
    if axis == "proc":
        n = plan.ppn
    elif axis == "node":
        n = plan.n_nodes
    else:
        n = plan.p
    return [(i, (i + offset) % n) for i in range(n)]


def _gather_csr_rows(ptr, ix, dat, rows):
    """Extract the CSR rows ``rows`` as a compact (len(rows), ·) CSR triple."""
    counts = np.diff(ptr)[rows]
    gptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    if len(rows):
        gix = np.concatenate([ix[ptr[r] : ptr[r + 1]] for r in rows])
        gdat = np.concatenate([dat[ptr[r] : ptr[r + 1]] for r in rows])
    else:
        gix = np.zeros(0, dtype=np.int64)
        gdat = np.zeros(0, dtype=dat.dtype)
    return gptr, gix, gdat


def _stack_gathered_csr(per_rank, n_rows_max, rmax, dtype):
    """Stack per-rank gathered CSR triples + scatter row ids into (p, ·) arrays.

    per_rank: list of (rows, gptr, gix, gdat); scatter ids pad with the dump
    row ``rmax``; nnz pads with index 0 / value 0 (contribute nothing).
    """
    p = len(per_rank)
    nnz_max = max((int(g[1][-1]) for g in per_rank), default=0)
    rows_ids = np.full((p, n_rows_max), rmax, np.int32)
    indptr = np.zeros((p, n_rows_max + 1), np.int32)
    indices = np.zeros((p, nnz_max), np.int32)
    data = np.zeros((p, nnz_max), dtype)
    for r, (rows, gptr, gix, gdat) in enumerate(per_rank):
        rows_ids[r, : len(rows)] = rows
        indptr[r, : len(gptr)] = gptr
        indptr[r, len(gptr) :] = gptr[-1]
        indices[r, : len(gix)] = gix
        data[r, : len(gdat)] = gdat
    return rows_ids, indptr, indices, data


def _stack_block_ell(per_rank, n_rows_max, n_cols, br, bc, dtype):
    """Convert per-rank gathered CSR triples to one stacked Block-ELL array;
    returns ``(blocks, idx, counters)`` (:func:`tile_counters`)."""
    p = len(per_rank)
    nbr = max(1, (n_rows_max + br - 1) // br)
    nbc = (n_cols + bc - 1) // bc
    keys = [block_ell_tile_keys(g[1], g[2], len(g[0]), n_cols, br, bc) for g in per_rank]
    kmax = max([int(np.bincount(k // nbc).max()) for k in keys if len(k)] + [1])
    blocks = np.zeros((p, max(1, -(-nbr // LANES)), kmax * bc, br, LANES), dtype)
    idx = np.zeros((p, nbr, kmax), np.int32)
    for r, (rows, gptr, gix, gdat) in enumerate(per_rank):
        blocks[r], idx[r] = csr_arrays_to_block_ell(
            gptr, gix, gdat, len(rows), n_cols, br, bc, nbr, kmax
        )
    return blocks, idx, tile_counters(keys, nbc, nbr, kmax)


def make_distributed_spmbv(
    a: CSRMatrix,
    mesh: Mesh,
    strategy: str = "standard",
    t: int = 1,
    machine=None,
    pm: PartitionedMatrix | None = None,
    backend: str = "jnp",
    overlap: bool = False,
    ell_block: int | tuple[int, int] = 8,
    tune: str | object = "off",
    col_split: int | None = None,
) -> DistributedSpMBV:
    """Deprecated spelling of the operator build — the handle API owns it.

    ``ECGSolver.build(a, mesh, SolverConfig(...))`` performs the same
    partition + plan + tune + Block-ELL setup once and exposes the operator
    as ``solver.op``; this function remains for external callers that only
    want the bare SpMBV operator.  See :func:`_make_distributed_spmbv` for
    the argument documentation.
    """
    import warnings

    warnings.warn(
        "make_distributed_spmbv() is the legacy stringly-typed spelling; "
        "build a repro.solver.ECGSolver handle (typed SolverConfig) and use "
        "solver.op instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return _make_distributed_spmbv(
        a, mesh, strategy, t=t, machine=machine, pm=pm, backend=backend,
        overlap=overlap, ell_block=ell_block, tune=tune, col_split=col_split,
    )


def _make_distributed_spmbv(
    a: CSRMatrix,
    mesh: Mesh,
    strategy: str = "standard",
    t: int = 1,
    machine=None,
    pm: PartitionedMatrix | None = None,
    backend: str = "jnp",
    overlap: bool = False,
    ell_block: int | tuple[int, int] = 8,
    tune: str | object = "off",
    col_split: int | None = None,
) -> DistributedSpMBV:
    """Partition ``a`` over ``mesh`` and build the device-ready operator.

    backend="pallas" additionally converts each rank's local [own ‖ halo]
    CSR block to Block-ELL here (one-time host cost, see module docstring);
    overlap=True splits rows into interior/boundary sets so the device
    program hides the exchange rounds behind interior compute; ``ell_block``
    is the Block-ELL tile shape — an int for square (b, b) tiles or an
    explicit (br, bc) pair.

    ``tune`` hands those three knobs to the setup-time autotuner
    (:mod:`repro.tune`): ``"model"`` selects (strategy, tile, overlap) from
    the paper's analytic performance models, ``"model:structural"`` from the
    executor-structural model (plan dispatches + moved bytes — the right
    ranking on host/TPU backends), ``"measure"`` from setup-time
    microbenchmarks on ``mesh``, and a :class:`repro.tune.TunedConfig`
    applies a previously computed choice.  ``"off"`` (default) keeps the
    explicit arguments.  ``col_split`` overrides the nodal-optimal wide-halo
    splitting factor (must divide t; ``None`` = §4.3 byte model).
    """
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    n_nodes, ppn = mesh.devices.shape
    p = n_nodes * ppn
    pm = pm or partition_csr(a, p)

    tuned = None
    if not (tune is None or tune == "off"):
        from repro.tune import TunedConfig, tune as run_tune

        if isinstance(tune, TunedConfig):
            tuned = tune
        elif tune in ("model", "model:structural", "measure"):
            tuned = run_tune(
                a, t=t, machine=machine, n_nodes=n_nodes, ppn=ppn,
                pm=pm, backend=backend, mode=tune, mesh=mesh,
            )
        else:
            raise ValueError(f"unknown tune mode {tune!r}")
        strategy = tuned.strategy
        overlap = tuned.overlap
        ell_block = (tuned.br, tuned.bc)
        # keep the built plan consistent with the config's byte-model
        # decisions: the tuner's dtype-resolved machine wins over the raw
        # caller argument it was derived from
        machine = tuned.machine or machine
        if col_split is None and tuned.col_split > 1:
            col_split = tuned.col_split

    plan = build_exchange_plan(
        pm, n_nodes, ppn, strategy, t=t, machine=machine, col_split=col_split
    )

    rmax = pm.part.max_local_rows
    val_dtype = np.asarray(pm.local_data[0]).dtype
    # per-rank (indptr, indices-with-halo-at-rmax, data, n_local): halo ids
    # were n_local-based, re-based to rmax so x can be padded
    rebased = rebased_local_csr(pm)

    # the full stacked CSR is only consumed by the blocking jnp path; don't
    # ship a second copy of the matrix to devices in the other modes
    indptr = indices = data = None
    if backend == "jnp" and not overlap:
        nnz_max = max(len(ix) for ix in pm.local_indices)
        indptr = np.zeros((p, rmax + 1), np.int32)
        indices = np.zeros((p, nnz_max), np.int32)
        data = np.zeros((p, nnz_max), val_dtype)
        for r, (ptr, ix, dat, n_local) in enumerate(rebased):
            indptr[r, : n_local + 1] = ptr
            indptr[r, n_local + 1 :] = ptr[-1]
            indices[r, : len(ix)] = ix
            data[r, : len(dat)] = dat

    n_cols_full = rmax + plan.halo_rows
    br, bc = (ell_block, ell_block) if isinstance(ell_block, int) else ell_block

    ell, ell_counters = {}, {}
    if backend == "pallas" and not overlap:
        per_rank = [
            (np.arange(n_local), ptr, ix, dat) for ptr, ix, dat, n_local in rebased
        ]
        blocks, idx, ell_counters = _stack_block_ell(
            per_rank, rmax, n_cols_full, br, bc, val_dtype
        )
        ell = {"blocks": blocks, "indices": idx}

    split = {}
    if overlap:
        # pallas: classify whole (br-aligned) block rows so gathering the
        # interior/boundary subsets preserves the Block-ELL tiles as built
        io = interior_boundary_split(pm, block_row=br if backend == "pallas" else 1)
        n_int_max = max(len(i) for i, _ in io)
        n_bnd_max = max(len(b) for _, b in io)
        int_per_rank, bnd_per_rank = [], []
        for (ptr, ix, dat, n_local), (int_rows, bnd_rows) in zip(rebased, io):
            gi = _gather_csr_rows(ptr, ix, dat, int_rows)
            gb = _gather_csr_rows(ptr, ix, dat, bnd_rows)
            int_per_rank.append((int_rows,) + gi)
            bnd_per_rank.append((bnd_rows,) + gb)
        int_ids, int_ptr, int_ix, int_dat = _stack_gathered_csr(
            int_per_rank, n_int_max, rmax, val_dtype
        )
        bnd_ids, bnd_ptr, bnd_ix, bnd_dat = _stack_gathered_csr(
            bnd_per_rank, n_bnd_max, rmax, val_dtype
        )
        split = {"int_rows": int_ids, "bnd_rows": bnd_ids}
        if backend == "pallas":
            split["int_blocks"], split["int_idx"], _ = _stack_block_ell(
                int_per_rank, n_int_max, rmax, br, bc, val_dtype
            )
            split["bnd_blocks"], split["bnd_idx"], _ = _stack_block_ell(
                bnd_per_rank, n_bnd_max, n_cols_full, br, bc, val_dtype
            )
        else:
            split.update(
                int_indptr=int_ptr, int_indices=int_ix, int_data=int_dat,
                bnd_indptr=bnd_ptr, bnd_indices=bnd_ix, bnd_data=bnd_dat,
            )

    dev_sharding = NamedSharding(mesh, P(("node", "proc")))
    put = lambda arr: jax.device_put(arr, dev_sharding)
    return DistributedSpMBV(
        mesh=mesh,
        plan=plan,
        n=a.shape[0],
        rmax=rmax,
        starts=pm.part.starts,
        indptr=put(indptr) if indptr is not None else None,
        indices=put(indices) if indices is not None else None,
        data=put(data) if data is not None else None,
        gathers=[put(ph.gather_idx) for ph in plan.phases],
        scatters=[put(ph.scatter_pos) for ph in plan.phases],
        backend=backend,
        overlap=overlap,
        ell_block=(br, bc),
        ell={k2: put(v) for k2, v in ell.items()},
        ell_counters=ell_counters,
        split={k2: put(v) for k2, v in split.items()},
        tuned=tuned,
    )


# ----------------------------------------------------------------------------
# distributed ECG: same body as core.ecg, inside one shard_map
# ----------------------------------------------------------------------------
def distributed_ecg(
    a: CSRMatrix,
    b: np.ndarray,
    mesh: Mesh,
    t: int | str,
    strategy: str = "standard",
    tol: float = 1e-8,
    max_iters: int = 500,
    machine=None,
    backend: str = "jnp",
    overlap: bool = False,
    ell_block: int | tuple[int, int] = 8,
    tune: str | object = "off",
    adaptive: object = None,
    t_candidates: tuple = (1, 2, 4, 8, 16),
):
    """Distributed ECG solve with the selected node-aware SpMBV strategy.

    Runs the whole while_loop inside jit with the distributed operator; the
    two fused reductions appear as psums over ("node", "proc").  With
    ``backend="pallas"`` the per-device local work (SpMBV, packed gram, X/R/Z
    tail) runs through the Pallas kernel suite — the collective structure
    (two psums per iteration) is unchanged.  ``overlap=True`` additionally
    hides the halo-exchange rounds behind interior SpMBV compute.

    ``tune="model"|"measure"`` (or a precomputed ``TunedConfig``) delegates
    the (strategy, tile shape, overlap) choice to :mod:`repro.tune` — see
    :func:`make_distributed_spmbv`; ``strategy="tuned"`` is shorthand for
    ``tune="model"``.

    ``t="auto"`` picks the enlarging factor at setup time from the
    iterations-vs-cost model of :mod:`repro.adaptive.select_t` (iteration
    probes run on the sequential CSR product — the iteration count depends
    only on the math — and per-iteration cost on this mesh's (n_nodes, ppn)
    via :mod:`repro.tune`); the :class:`TSelection` is recorded on both the
    result and the applied ``TunedConfig``.  With the default ``tune="off"``
    the solver then *executes the tuner config the choice was modeled with*
    — explicit ``strategy``/``overlap``/``ell_block`` arguments are
    overridden (with a warning when non-default), because a t optimized for
    one config but run under another would make the selection meaningless;
    pass a fixed ``t`` to force an explicit config, or ``tune="model"|
    "measure"`` to re-tune at the chosen t.  ``adaptive`` selects the in-
    solve width controller ("rankrev" | "reduce" | "reduce+restart" | a
    :class:`repro.adaptive.ReductionPolicy`): the active-width mask lives in
    the replicated t-wide coefficient space, so the per-device block vectors
    stay (rmax, t) with zero-masked columns and the Pallas kernels and
    two-psum structure are untouched.  The halo exchange, however, is
    *width-aware*: for non-restarting policies the solve runs in width
    segments — the active mask is threaded into the exchange (retired
    columns are compacted out of the wire payload), and each reduction
    event triggers a cheap ``plan.at_width`` re-slice so subsequent
    iterations move ``t_active·rows·f`` bytes instead of full-width zeros.
    ``SolveResult.comm_segments`` records the (width, iterations) trace.

    .. deprecated::
        This is the legacy stringly-typed spelling.  It now builds a
        :class:`repro.solver.ECGSolver` handle, solves once, and discards
        the compiled session — build the handle yourself to amortize setup
        and compilation over many right-hand sides.
    """
    import warnings

    warnings.warn(
        "distributed_ecg() is the legacy stringly-typed spelling; build a "
        "repro.solver.ECGSolver handle (compile-once / solve-many, typed "
        "SolverConfig) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    solver = _build_legacy_solver(
        a, mesh, t, strategy=strategy, tol=tol, max_iters=max_iters,
        machine=machine, backend=backend, overlap=overlap,
        ell_block=ell_block, tune=tune, adaptive=adaptive,
        t_candidates=t_candidates, b=b,
    )
    return solver.solve(b), solver.op


def _build_legacy_solver(
    a, mesh, t, *, strategy="standard", tol=1e-8, max_iters=500, machine=None,
    backend="jnp", overlap=False, ell_block=8, tune="off", adaptive=None,
    t_candidates=(1, 2, 4, 8, 16), b=None,
):
    """Map the legacy ``distributed_ecg`` argument list onto a typed
    :class:`~repro.solver.SolverConfig` and build the handle."""
    from repro.solver import (
        AdaptiveConfig, CommConfig, ECGSolver, KernelConfig, SolverConfig,
        TuneConfig,
    )

    if strategy == "tuned":
        strategy = "standard"
        if tune is None or tune == "off":
            tune = "model"
    config = SolverConfig(
        t=t,
        tol=tol,
        max_iters=max_iters,
        comm=CommConfig(strategy=strategy, overlap=overlap, machine=machine),
        kernel=KernelConfig(backend=backend, ell_block=ell_block),
        tune=TuneConfig.coerce(None if tune == "off" else tune),
        adaptive=AdaptiveConfig(policy=adaptive, t_candidates=tuple(t_candidates)),
    )
    return ECGSolver.build(a, mesh, config, b=b)
