"""The compile-once / solve-many ECG session handle.

The paper's premise is that ECG setup cost — partitioning, node-aware
exchange planning, tuning — is paid once and amortized over the solve
(§4).  :class:`ECGSolver` is that amortization made explicit in the API:

    from repro.solver import ECGSolver, SolverConfig, CommConfig

    solver = ECGSolver.build(a, mesh, SolverConfig(
        t=8, tol=1e-8, comm=CommConfig(strategy="3step"),
    ))
    res = solver.solve(b)           # first call traces + compiles the loop
    more = solver.solve_many(bs)    # every further RHS reuses the program

``build`` performs partitioning, :class:`~repro.core.node_aware.ExchangePlan`
construction, autotuning, ``t="auto"`` selection, and Block-ELL conversion
exactly once.  ``solve`` wraps the whole guarded while-loop (initial
residual included) in one ``jax.jit`` per active width, so a second solve
with the same operand shape/dtype is a pure cache hit —
``ECGSolver.stats.traces`` counts retraces and stays flat across repeated
solves (asserted in the test suite).  ``with_config`` derives a sibling
handle cheaply: overrides that only touch the solve loop (tol, max_iters,
the adaptive policy) reuse the operator and plan outright; operator-level
overrides rebuild it but always reuse the row partition.

The legacy functional spellings (``ecg_solve`` / ``distributed_ecg`` /
``make_distributed_spmbv``) are thin deprecated wrappers over this handle
and its machinery — see ``docs/api.md`` for the migration table.
"""

from __future__ import annotations

import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from repro.adaptive.groups import GroupSpec
from repro.adaptive.reduce import resolve_policy
from repro.core.cg import SolveResult
from repro.core.ecg import finalize_result, jit_solve, make_ecg_runner
from repro.observe.tracer import coerce_tracer
from repro.solver.config import SolverConfig


def _auto_axes(mesh):
    """``mesh`` with every axis of type ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which type-check the
    sharding of every op outside the shard_maps (the t×t factorization and
    its triangular solves on row-sharded blocks are refused); the solver
    leaves that code to the compiler's sharding propagation.
    """
    from jax.sharding import AxisType, Mesh

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@dataclasses.dataclass
class SolverStats:
    """Build/compile accounting of one handle (reuse made observable)."""

    builds: int = 0            # operator/plan constructions this handle paid
    traces: int = 0            # solve-loop (re)traces; flat across cache hits
    solves: int = 0            # solve() calls served
    partition_reused: bool = False  # with_config reused the parent partition
    op_reused: bool = False         # with_config reused the parent operator
    conv_analyzed: bool = False     # this build ran the CSR→Block-ELL tile
    #                                 analysis (the expensive conversion pass)
    conv_reused: bool = False       # this build skipped conversion entirely
    #                                 (precomputed Block-ELL arrays supplied)


class ECGSolver:
    """Compile-once / solve-many ECG session (see module docstring).

    Attributes after ``build``:

    t:         the resolved enlarging factor (an int, even for ``t="auto"``).
    op:        the :class:`~repro.sparse.spmbv.DistributedSpMBV` operator
               (None for a sequential, single-device handle).
    tuned:     the applied :class:`~repro.tune.TunedConfig` (None untuned).
    selection: the :class:`~repro.adaptive.TSelection` when ``t="auto"``.
    policy:    the resolved in-solve :class:`~repro.adaptive.ReductionPolicy`.
    stats:     :class:`SolverStats` — builds/traces/solves/reuse flags.
    """

    def __init__(self, *args, **kwargs):
        raise TypeError("use ECGSolver.build(a, mesh=None, config=...) ")

    # ------------------------------------------------------------- building
    @classmethod
    def build(
        cls,
        a,
        mesh=None,
        config: SolverConfig | dict | None = None,
        *,
        b=None,
        pm=None,
        conversion=None,
        tracer=None,
    ) -> "ECGSolver":
        """Build a solver handle for matrix ``a``.

        a:      :class:`~repro.sparse.csr.CSRMatrix` (SPD).
        mesh:   a ``("node", "proc")`` device mesh for the distributed
                node-aware solver, or None for the sequential solver.
        config: a :class:`SolverConfig` (or dict of its fields).
        b:      optional probe right-hand side for ``t="auto"`` (defaults to
                a seeded Gaussian — the selection only needs a representative
                RHS, but passing the real one sharpens the probe).
        pm:     optional precomputed partition to reuse.
        conversion: optional CSR→Block-ELL conversion artifacts to reuse
                (sequential ``backend="pallas"`` only) — a dict with
                ``"arrays"`` (a previous handle's ``self.conversion["arrays"]``
                — skips the conversion outright) and/or ``"meta"`` (the tile
                analysis from :func:`repro.kernels.block_ell_meta` — skips
                the analysis pass).  Mismatched artifacts (different tile,
                shape, or dtype) are ignored, never an error.
        tracer: a :class:`repro.observe.Tracer` to record build-phase and
                solve-segment spans on (default: the process tracer —
                normally the free null tracer, so instrumentation is a
                no-op unless one was installed).
        """
        self = cls.__new__(cls)
        self.a = a
        self.mesh = None if mesh is None else _auto_axes(mesh)
        self.config = SolverConfig.coerce(config)
        self._tracer = coerce_tracer(tracer)
        self.stats = SolverStats()
        self.selection = None
        self.tuned = None
        self.op = None
        self._pm = pm
        self._probe_b = b
        self._runners: dict = {}
        self._jits: dict = {}
        self._onehot_cache: dict = {}
        self._packed_applies: dict = {}
        self._conversion_in = conversion
        self.conversion = None
        with self._tracer.span(
            "build", cat="build", n=int(a.shape[0]), nnz=int(a.nnz),
            distributed=mesh is not None,
        ) as sp:
            self._build()
            sp.args["t"] = int(self.t)
        self._tracer.counter("solver.builds", self.stats.builds)
        return self

    def _auto_probe_b(self):
        if self._probe_b is not None:
            return self._probe_b
        return np.random.default_rng(0).standard_normal(self.a.shape[0])

    def _build(self):
        if self.mesh is None:
            self._build_sequential()
        else:
            self._build_distributed()

    def _build_sequential(self):
        from repro.sparse.csr import csr_spmbv

        cfg = self.config
        t = cfg.t
        adaptive = "off" if cfg.adaptive.explicit_off else cfg.adaptive.policy
        tuned = cfg.tune.tuned
        if cfg.tune.mode == "measure":
            raise ValueError(
                'tune mode "measure" times candidate operators on a device '
                "mesh; build the handle with mesh= (or use mode='model')"
            )
        if isinstance(t, str):  # "auto"
            from repro.adaptive.select_t import resolve_auto_t

            with self._tracer.span("build/select_t", cat="build"):
                t, self.selection, adaptive = resolve_auto_t(
                    "auto", adaptive, a=self.a, b=self._auto_probe_b(),
                    select=cfg.adaptive.select,
                    candidates=cfg.adaptive.t_candidates,
                    tol=cfg.tol, machine=cfg.comm.machine,
                    backend=cfg.kernel.backend,
                    probe_iters=cfg.adaptive.probe_iters,
                    probe_rtol=cfg.adaptive.probe_rtol,
                    method=cfg.method.name, s=cfg.method.s,
                    reorth=cfg.method.reorth,
                )
            if tuned is None and cfg.kernel.backend == "pallas":
                # execute the tile the candidate costs were modeled with
                tuned = self.selection.configs.get(t)
        elif tuned is None and cfg.tune.active and cfg.kernel.backend == "pallas":
            from repro.tune import tune as run_tune

            with self._tracer.span("build/tune", cat="build",
                                   mode=cfg.tune.mode):
                tuned = run_tune(
                    self.a, t=t, machine=cfg.comm.machine, n_nodes=1, ppn=1,
                    backend="pallas", mode=cfg.tune.mode,
                )
        self.stats.builds += 1
        self.tuned = tuned
        self.t = t
        self.policy = resolve_policy(adaptive)
        self._segmented = False
        ell_block = tuned.ell_block if tuned is not None else cfg.kernel.ell_block
        if cfg.kernel.backend == "pallas":
            with self._tracer.span("build/convert", cat="build") as sp:
                self._build_ell_apply(ell_block)
                sp.args.update(
                    analyzed=self.stats.conv_analyzed,
                    reused=self.stats.conv_reused,
                    **(self.conversion["meta"] or {}).get("counters", {}),
                )
        else:
            self._apply = lambda V: csr_spmbv(self.a, V)
        self._gram1 = self._gram2 = self._sqnorm = self._tail = None
        self._gram2p = self._sqnorm_cols = None
        self._split_fn = None
        self._precond = self._build_precond()

    def _build_ell_apply(self, ell_block):
        """Sequential Block-ELL apply, reusing supplied conversion artifacts.

        Priority: precomputed arrays (skip conversion outright — the
        eviction-aware warm path) > tile-analysis meta (skip the analysis
        pass, direct-fill the blocks) > full cold conversion.  The produced
        artifacts are published on ``self.conversion`` so the serve registry
        can persist/reshare them; ``stats.conv_analyzed``/``conv_reused``
        make the chosen path observable (gated in serve_bench).
        """
        from repro.kernels import make_block_ell_apply_from_arrays
        from repro.kernels.bsr_spmbv.ops import block_ell_arrays

        br, bc = (
            (ell_block, ell_block) if isinstance(ell_block, int) else ell_block
        )
        conv_in = self._conversion_in or {}
        reuse = conv_in.get("arrays")
        dtype = str(np.dtype(self.a.data.dtype))
        if reuse is not None and not (
            reuse.get("br") == br
            and reuse.get("bc") == bc
            and reuse.get("shape") == tuple(self.a.shape)
            and reuse.get("dtype") == dtype
        ):
            reuse = None  # stale artifacts (tile/shape/dtype changed): ignore
        if reuse is not None:
            blocks, indices, m_pad = (
                reuse["blocks"], reuse["indices"], reuse["m_pad"]
            )
            meta = reuse.get("meta")
            self.stats.conv_reused = True
        else:
            blocks, indices, m_pad, meta, analyzed = block_ell_arrays(
                self.a, br, bc, meta=conv_in.get("meta")
            )
            self.stats.conv_analyzed = analyzed
        self._apply = make_block_ell_apply_from_arrays(
            blocks, indices, m_pad, self.a.shape[0]
        )
        self.conversion = dict(
            arrays=dict(
                blocks=blocks, indices=indices, m_pad=m_pad,
                br=br, bc=bc, shape=tuple(self.a.shape), dtype=dtype,
                meta=meta,
            ),
            meta=meta,
        )

    def _build_distributed(self):
        from repro.sparse.partition import partition_csr
        from repro.sparse.spmbv import _make_distributed_spmbv

        cfg = self.config
        n_nodes, ppn = self.mesh.devices.shape
        if self._pm is None:
            with self._tracer.span("build/partition", cat="build",
                                   p=n_nodes * ppn):
                self._pm = partition_csr(self.a, n_nodes * ppn)

        t = cfg.t
        adaptive = "off" if cfg.adaptive.explicit_off else cfg.adaptive.policy
        tune_arg = cfg.tune.tuned if cfg.tune.tuned is not None else cfg.tune.mode
        strategy = cfg.comm.strategy
        overlap = cfg.comm.overlap
        ell_block = cfg.kernel.ell_block
        if isinstance(t, str):  # "auto"
            from repro.adaptive.select_t import resolve_auto_t

            tune_mode = (
                cfg.tune.mode if cfg.tune.mode in ("model", "model:structural")
                else "model"
            )
            with self._tracer.span("build/select_t", cat="build"):
                t, self.selection, adaptive = resolve_auto_t(
                    "auto", adaptive, a=self.a, b=self._auto_probe_b(),
                    select=cfg.adaptive.select,
                    candidates=cfg.adaptive.t_candidates,
                    tol=cfg.tol, machine=cfg.comm.machine,
                    n_nodes=n_nodes, ppn=ppn,
                    backend=cfg.kernel.backend, tune_mode=tune_mode,
                    probe_iters=cfg.adaptive.probe_iters,
                    probe_rtol=cfg.adaptive.probe_rtol,
                    method=cfg.method.name, s=cfg.method.s,
                    reorth=cfg.method.reorth,
                )
            if not cfg.tune.active:
                # execute the exact config the choice was modeled with — a t
                # optimized for one (strategy, tile, overlap) but run under
                # another would make the selection meaningless.  Explicit
                # comm/kernel settings are overridden (warn when that
                # discards a non-default request).
                tcfg = self.selection.configs.get(t)
                if tcfg is not None:
                    if strategy != "standard" or overlap or ell_block != (8, 8):
                        warnings.warn(
                            "t='auto' executes the tuner config its choice was "
                            f"modeled with ({tcfg.strategy}/{tcfg.ell_block}/"
                            f"{'overlap' if tcfg.overlap else 'blocking'}); the "
                            f"explicit strategy={strategy!r}/overlap={overlap}/"
                            f"ell_block={ell_block} settings are ignored — pass "
                            "a fixed t to force them",
                            stacklevel=4,
                        )
                    tune_arg = tcfg
        # one span for plan construction + tuning + Block-ELL conversion:
        # _make_distributed_spmbv owns those phases, and the span's
        # structural attributes (wire bytes, packed dispatch count) are the
        # accounting every later solve span inherits
        with self._tracer.span(
            "build/operator", cat="build", strategy=strategy, t=int(t),
        ) as sp:
            self.op = _make_distributed_spmbv(
                self.a, self.mesh, strategy, t=t, machine=cfg.comm.machine,
                pm=self._pm, backend=cfg.kernel.backend, overlap=overlap,
                ell_block=ell_block, tune=tune_arg,
                col_split=cfg.comm.col_split,
            )
            f = int(np.dtype(self.a.data.dtype).itemsize)
            sp.args.update(
                wire_bytes=int(self.op.plan.wire_bytes(f)),
                dispatch_count=int(self.op.plan.dispatch_count(packed=True)),
                tuned_strategy=(
                    self.op.tuned.strategy if self.op.tuned else strategy
                ),
                **self.op.ell_counters,
            )
        self.stats.builds += 1
        if self.selection is not None and self.op.tuned is not None:
            self.op.tuned = dataclasses.replace(
                self.op.tuned, selection=self.selection
            )
        self.tuned = self.op.tuned
        self.t = t
        self.policy = resolve_policy(adaptive)
        self._segmented = self.policy is not None and not self.policy.restart
        self._apply = self.op.matvec_fn()
        with self._tracer.span("build/reducers", cat="build"):
            self._build_reducers()
        self._precond = self._build_precond()

    def _build_reducers(self):
        """The fused shard_map reductions of §3.1 (one psum each) and the
        padded-layout T_{r,t} splitting — built once per operator."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.kernels.block_update.ops import ecg_tail
        from repro.kernels.fused_gram.ops import fused_gram

        op, mesh = self.op, self.mesh
        backend = self.config.kernel.backend
        axes = ("node", "proc")
        vspec = op.vec_spec
        self._gram1 = shard_map(
            lambda z, az: jax.lax.psum(z.T @ az, axes),
            mesh=mesh,
            in_specs=(vspec, vspec),
            out_specs=P(None, None),
            check_vma=False,
        )
        if backend == "pallas":
            self._gram2 = shard_map(
                lambda pp, rr, ap, apo: jax.lax.psum(
                    fused_gram(pp, rr, ap, apo), axes
                ),
                mesh=mesh,
                in_specs=(vspec,) * 4,
                out_specs=P(None, None),
                check_vma=False,
            )
            self._tail = shard_map(
                lambda x, r, pp, ap, po, c, d, do: ecg_tail(
                    x, r, pp, ap, po, c, d, do
                ),
                mesh=mesh,
                in_specs=(vspec,) * 5 + (P(None, None),) * 3,
                out_specs=(vspec, vspec, vspec),
                check_vma=False,
            )
        else:
            self._gram2 = shard_map(
                lambda pp, rr, ap, apo: jax.lax.psum(
                    jnp.concatenate([pp.T @ rr, ap.T @ ap, apo.T @ ap], axis=1),
                    axes,
                ),
                mesh=mesh,
                in_specs=(vspec,) * 4,
                out_specs=P(None, None),
                check_vma=False,
            )
            self._tail = None
        self._sqnorm = shard_map(
            lambda v: jax.lax.psum(jnp.vdot(v, v), axes),
            mesh=mesh,
            in_specs=P(("node", "proc")),
            out_specs=P(),
            check_vma=False,
        )
        # per-column squared norms for packed multi-RHS solves: one psum of
        # g floats that REPLACES the scalar sqnorm collective in group mode
        # (the per-iteration collective count is identical to a solo solve)
        self._sqnorm_cols = shard_map(
            lambda m: jax.lax.psum(jnp.sum(m * m, axis=0), axes),
            mesh=mesh,
            in_specs=vspec,
            out_specs=P(None),
            check_vma=False,
        )
        # preconditioned packed reduction [PᵀR | APᵀW | AP_oldᵀW]: three
        # asymmetric products the fused_gram kernel cannot express, fused
        # locally so the payload still rides ONE psum — the §3.1 two-psum
        # structure survives preconditioning (asserted in dist_worker.py)
        self._gram2p = shard_map(
            lambda pp, rr, ap, apo, w: jax.lax.psum(
                jnp.concatenate([pp.T @ rr, ap.T @ w, apo.T @ w], axis=1),
                axes,
            ),
            mesh=mesh,
            in_specs=(vspec,) * 5,
            out_specs=P(None, None),
            check_vma=False,
        )

        # T_{r,t} on the padded layout: subdomains follow *true* global row
        # ids so the splitting matches the sequential solver exactly.
        t = self.t
        true_rows = op.true_row_of_slot()
        sub = np.where(true_rows >= 0, (true_rows * t) // op.n, 0)
        onehot_np = np.zeros((op.n_padded, t))
        onehot_np[np.arange(op.n_padded), np.minimum(sub, t - 1)] = (
            true_rows >= 0
        ).astype(float)
        self._onehot_np = onehot_np

        def split(r, t_):
            return r[:, None] * self._onehot(r.dtype)

        self._split_fn = split

    def _build_precond(self):
        """Build the preconditioner apply for this handle's operator
        (None when ``config.precondition`` is inactive)."""
        cfg = self.config
        if not cfg.precondition.active:
            return None
        if self.mesh is None:
            from repro.precondition import build_sequential_preconditioner

            return build_sequential_preconditioner(
                self.a, cfg.precondition, self._apply
            )
        from repro.precondition import build_distributed_preconditioner

        return build_distributed_preconditioner(
            self.a, cfg.precondition, self.op, self.mesh, self._apply
        )

    def _onehot(self, dtype):
        """Device-resident T_{r,t} one-hot for ``dtype``.

        Must be warmed *outside* a trace (see :meth:`solve`): the cached
        value is a concrete sharded array that the traced split closure then
        captures as a constant — device_put during tracing would leak a
        tracer into the cache.
        """
        from jax.sharding import NamedSharding

        key = jnp.dtype(dtype).name
        hit = self._onehot_cache.get(key)
        if hit is None:
            hit = jax.device_put(
                jnp.asarray(self._onehot_np, dtype),
                NamedSharding(self.mesh, self.op.vec_spec),
            )
            self._onehot_cache[key] = hit
        return hit

    # ------------------------------------------------------------- runners
    def _runner(self, width: int):
        runner = self._runners.get(width)
        if runner is None:
            cfg = self.config
            masked = None
            exit_bw = None
            if self._segmented:
                # Width-segmented exchange: the full-width segment still
                # carries the active mask (so the loop can exit on a
                # reduction event); narrower segments compact the payload.
                masked = (
                    (lambda z, act: self._apply(z)) if width == self.t
                    else self.op.masked_matvec_fn(width)
                )
                exit_bw = width
            runner = make_ecg_runner(
                self._apply, self.t, tol=cfg.tol, max_iters=cfg.max_iters,
                split=self._split_fn, gram1=self._gram1, gram2=self._gram2,
                sqnorm=self._sqnorm, tail=self._tail,
                backend=cfg.kernel.backend, policy=self.policy,
                a_apply_masked=masked, exit_below_width=exit_bw,
                method=cfg.method.name, s=cfg.method.s,
                reorth=cfg.method.reorth, rank_rtol=cfg.method.rank_rtol,
                precond=self._precond, gram2p=self._gram2p,
                precond_reseed=(
                    cfg.precondition.reseed
                    if cfg.precondition.kind == "inexact"
                    else None
                ),
            )
            self._runners[width] = runner
        return runner

    def _jit(self, width: int, kind: str):
        key = (width, kind)
        fn = self._jits.get(key)
        if fn is None:
            runner = self._runner(width)
            if kind == "fresh":
                def go(b, x0):
                    self.stats.traces += 1  # trace-time side effect only
                    return runner.run(runner.init(b, x0))
            else:
                def go(carry):
                    self.stats.traces += 1
                    return runner.run(carry)
            fn = jit_solve(go)
            self._jits[key] = fn
        return fn

    # -------------------------------------------------------------- solving
    def _device_vec(self, v):
        if self.mesh is not None:
            return self.op.shard_vector(np.asarray(v))
        return jnp.asarray(v)

    def _struct_attrs(self, width: int) -> dict:
        """Structural accounting of one solve segment at active ``width``
        — the attributes that make a trace self-describing (plan wire
        bytes at the re-sliced width, packed dispatch count, the scheme's
        psums/iteration).  Called only when tracing is enabled."""
        from repro.core.methods import get_method

        cfg = self.config
        spec = get_method(cfg.method.name)
        attrs = dict(psums_per_iter=float(
            spec.collectives_per_iteration(cfg.method.s, cfg.method.reorth)
        ))
        if self.op is not None:
            f = int(np.dtype(self.a.data.dtype).itemsize)
            plan_w = self.op.plan.at_width(width)
            attrs.update(
                wire_bytes=int(plan_w.wire_bytes(f)),
                dispatch_count=int(plan_w.dispatch_count(packed=True)),
            )
        return attrs

    def _emit_solve_telemetry(self, result):
        """Counters + per-iteration event markers for one finished solve.

        Lifts the recovery/reseed/re-slice events out of the device-side
        histories (``iter_trace`` is the reader) — a host transfer, so
        strictly gated on the tracer being enabled."""
        tr = self._tracer
        if not tr.enabled:
            return
        tr.counter("solver.solves", self.stats.solves)
        tr.counter("solver.traces", self.stats.traces)
        for k, before, after in result.reduction_events():
            tr.instant("solve/width_change", k=k, before=before, after=after)
        for k in result.recovery_events():
            tr.instant("solve/recovery", k=k)
        for k in result.reseed_events():
            tr.instant("solve/reseed", k=k)

    def solve(self, b, x0=None):
        """Solve A x = b; returns a :class:`~repro.core.cg.SolveResult`.

        ``b``/``x0`` are global (n,) vectors (numpy or jax); on a
        distributed handle they are laid out onto the mesh here and the
        returned ``res.x`` is in the padded per-rank layout — use
        :meth:`unshard` for the global vector.  The first call traces and
        compiles the solve loop; subsequent calls with the same operand
        shape/dtype reuse the compiled program (``stats.traces`` is flat).
        """
        cfg = self.config
        b_dev = self._device_vec(b)
        x0_dev = jnp.zeros_like(b_dev) if x0 is None else self._device_vec(x0)
        if self.mesh is not None:
            self._onehot(b_dev.dtype)  # warm eagerly — a trace must not put
        tr = self._tracer
        if not self._segmented:
            # dispatch span: the async enqueue only; finalize covers the
            # host syncs — together they bracket the whole device solve
            with tr.span("solve/dispatch", cat="solve", width=self.t) as spd:
                out = self._jit(self.t, "fresh")(b_dev, x0_dev)
            with tr.span("solve/finalize", cat="solve") as spf:
                result = finalize_result(
                    out, x0=x0_dev, t=self.t, tol=cfg.tol, policy=self.policy,
                    selection=self.selection,
                )
                spf.args.update(iters=result.n_iters,
                                converged=bool(result.converged))
            if tr.enabled:
                # one segment span covering dispatch through the finalize
                # host sync — the unsegmented solve's (width, iters, wall)
                tr.emit(
                    "solve/segment", spd.t0, spf.t0 + spf.dur - spd.t0,
                    cat="solve", width=self.t, iters=result.n_iters,
                    **self._struct_attrs(self.t),
                )
        else:
            # Width-segmented solve: each segment runs the jitted loop with
            # the exchange compacted to the current static active width;
            # when the reduction controller retires directions the loop
            # exits, the plan is re-sliced at the new width (cached host
            # work, no rebuild), and the solve resumes from the same carry.
            t_seg, carry, k_prev, segments = self.t, None, 0, []
            while True:
                with tr.span("solve/segment", cat="solve",
                             width=t_seg) as sp:
                    if carry is None:
                        carry = self._jit(t_seg, "fresh")(b_dev, x0_dev)
                    else:
                        carry = self._jit(t_seg, "resume")(carry)
                    k = int(carry["k"])
                    bd = bool(carry["bd"])
                    it_seg = k - k_prev
                    sp.args["iters"] = it_seg
                    if tr.enabled:
                        sp.args.update(self._struct_attrs(t_seg))
                segments.append((t_seg, it_seg))
                k_prev = k
                n_act = int(jnp.sum(carry["act"]))
                if (
                    bool(carry["rn"] <= cfg.tol)
                    or bd
                    or k >= cfg.max_iters
                    or n_act >= t_seg
                    # every direction dead (rank-0 Gram without a non-finite
                    # iterate) or a zero-progress segment: nothing a narrower
                    # re-slice could fix — stop instead of spinning
                    or n_act == 0
                    or it_seg == 0
                ):
                    break
                t_seg = max(n_act, 1)  # width-reduction event -> re-slice
            with tr.span("solve/finalize", cat="solve"):
                result = finalize_result(
                    carry, x0=x0_dev, t=self.t, tol=cfg.tol,
                    policy=self.policy, selection=self.selection,
                )
            result.comm_segments = segments
        self.stats.solves += 1
        self._emit_solve_telemetry(result)
        return result

    def solve_many(self, bs, x0s=None):
        """Solve the same operator against many right-hand sides.

        Every solve reuses the jitted while-loop — after the first solve,
        no retrace or recompile happens (asserted in the test suite via
        ``stats.traces``).  On a non-segmented handle the solves are
        *dispatch-pipelined*: all of them are enqueued on the device
        before the first host sync, so finalizing result ``i`` (the
        ``int(k)``/``bool(rn <= tol)`` transfers) overlaps the device
        compute of result ``i+1``.  Results are exactly what per-RHS
        :meth:`solve` calls would return — same programs, same operands.
        """
        x0s = [None] * len(bs) if x0s is None else list(x0s)
        if len(x0s) != len(bs):
            raise ValueError(f"got {len(bs)} rhs but {len(x0s)} initial guesses")
        if self._segmented:
            # width-segmented solves sync the host between segments anyway
            return [self.solve(b, x0) for b, x0 in zip(bs, x0s)]
        cfg = self.config
        tr = self._tracer
        fn = None
        outs = []
        # the dispatch span covers only async enqueues — it must NOT force
        # a host sync, or the pipelining this method exists for is gone
        with tr.span("solve_many/dispatch", cat="solve",
                     requests=len(bs), width=self.t):
            for b, x0 in zip(bs, x0s):
                b_dev = self._device_vec(b)
                x0_dev = (
                    jnp.zeros_like(b_dev) if x0 is None
                    else self._device_vec(x0)
                )
                if self.mesh is not None:
                    self._onehot(b_dev.dtype)  # warm eagerly — a trace must
                    #                            not put
                if fn is None:
                    fn = self._jit(self.t, "fresh")
                outs.append((fn(b_dev, x0_dev), x0_dev))
                self.stats.solves += 1
        with tr.span("solve_many/finalize", cat="solve", requests=len(bs)):
            results = [
                finalize_result(
                    out, x0=x0_dev, t=self.t, tol=cfg.tol, policy=self.policy,
                    selection=self.selection,
                )
                for out, x0_dev in outs
            ]
        if tr.enabled:
            tr.counter("solver.solves", self.stats.solves)
            tr.counter("solver.traces", self.stats.traces)
        return results

    # ------------------------------------------------------- packed solving
    def _packed_apply(self, width: int):
        """Full-width SpMBV for a packed solve (re-sliced plan at ``width``)."""
        fn = self._packed_applies.get(width)
        if fn is None:
            fn = self.op.matvec_fn(t_active=width)
            self._packed_applies[width] = fn
        return fn

    def _packed_runner(self, spec: GroupSpec, width_seg: int):
        key = ("pack", spec, width_seg)
        runner = self._runners.get(key)
        if runner is None:
            cfg = self.config
            width = spec.width
            if self.mesh is None:
                apply_w = self._apply  # width-polymorphic CSR/Block-ELL apply
                masked = None
                exit_bw = None
            else:
                apply_w = self._packed_apply(width)
                # group retirement drives the compacted exchange even with
                # no reduction policy: the full-width segment carries the
                # live mask so the loop can exit at a retirement event,
                # narrower segments compact the payload
                masked = (
                    (lambda z, act: apply_w(z)) if width_seg == width
                    else self.op.masked_matvec_fn(width_seg)
                )
                exit_bw = width_seg
            runner = make_ecg_runner(
                apply_w, width, tol=cfg.tol, max_iters=cfg.max_iters,
                split=self._split_fn, gram1=self._gram1, gram2=self._gram2,
                sqnorm=self._sqnorm, tail=self._tail,
                backend=cfg.kernel.backend, policy=self.policy,
                a_apply_masked=masked, exit_below_width=exit_bw,
                method=cfg.method.name, s=cfg.method.s,
                reorth=cfg.method.reorth, rank_rtol=cfg.method.rank_rtol,
                precond=self._precond, gram2p=self._gram2p,
                precond_reseed=(
                    cfg.precondition.reseed
                    if cfg.precondition.kind == "inexact"
                    else None
                ),
                groups=spec, sqnorm_cols=self._sqnorm_cols,
            )
            self._runners[key] = runner
        return runner

    def _packed_jit(self, spec: GroupSpec, width_seg: int, kind: str):
        key = ("pack", spec, width_seg, kind)
        fn = self._jits.get(key)
        if fn is None:
            runner = self._packed_runner(spec, width_seg)
            if kind == "fresh":
                def go(b, x0):
                    self.stats.traces += 1  # trace-time side effect only
                    return runner.run(runner.init(b, x0))
            else:
                def go(carry):
                    self.stats.traces += 1
                    return runner.run(carry)
            fn = jit_solve(go)
            self._jits[key] = fn
        return fn

    def solve_packed(self, bs, x0s=None, tols=None):
        """Solve k right-hand sides as ONE enlarged block solve of width
        ``k·t``, each request retiring against its own tolerance.

        Request j owns the contiguous column slab ``[j·t, (j+1)·t)`` of the
        packed program; all k requests share every halo exchange and both
        Gram psums per iteration (the amortization the paper prices per
        *column* now amortizes per *request*).  When a request's per-group
        residual norm reaches its tolerance its R/Z slabs are zero-retired,
        its solution freezes, and on a distributed handle the exchange is
        re-sliced at the shrunken live width (``ExchangePlan.at_width``) so
        late finishers stop paying early finishers' bytes.

        ``tols`` is one absolute residual-norm tolerance per request (None
        entries inherit ``config.tol``).  Results are NOT bit-identical to
        solo :meth:`solve` calls — the shared search space couples the
        iterates (that coupling is exactly why the pack converges in fewer
        total iterations than k solo solves) — so each
        :class:`~repro.core.cg.SolveResult` carries honest per-request
        telemetry: its own residual history/iteration count and a
        ``pack`` dict (group layout, retirement iteration, total packed
        iterations).  Requires ``method="classic"`` and no restart policy.
        """
        cfg = self.config
        if len(bs) == 0:
            raise ValueError("solve_packed needs at least one right-hand side")
        if cfg.method.name != "classic":
            raise ValueError(
                f"solve_packed requires method 'classic', got {cfg.method.name!r}"
            )
        if self.policy is None:
            raise ValueError(
                "solve_packed requires a rank-revealing policy (build with "
                "adaptive='rankrev' at minimum): retirement makes the Gram "
                "matrix structurally singular, which the pivoted "
                "factorization absorbs as zero-masked columns"
            )
        if self.policy.restart:
            raise ValueError(
                "solve_packed cannot run a restart policy (re-enlarging would "
                "mix request boundaries); use adaptive='rankrev' or 'reduce'"
            )
        x0s = [None] * len(bs) if x0s is None else list(x0s)
        tols = [None] * len(bs) if tols is None else list(tols)
        if len(x0s) != len(bs) or len(tols) != len(bs):
            raise ValueError(
                f"got {len(bs)} rhs but {len(x0s)} guesses / {len(tols)} tols"
            )
        spec = GroupSpec(
            t_each=self.t,
            tols=tuple(cfg.tol if tt is None else float(tt) for tt in tols),
        )
        g = spec.n_groups
        b_mat = np.stack([np.asarray(b) for b in bs], axis=1)
        x0_mat = np.stack(
            [np.zeros(b_mat.shape[0], b_mat.dtype) if x0 is None
             else np.asarray(x0) for x0 in x0s],
            axis=1,
        )
        if self.mesh is not None:
            b_dev = self.op.shard_vector(b_mat)
            x0_dev = self.op.shard_vector(x0_mat.astype(b_mat.dtype))
            self._onehot(b_dev.dtype)  # warm eagerly — a trace must not put
        else:
            b_dev = jnp.asarray(b_mat)
            x0_dev = jnp.asarray(x0_mat)
        tr = self._tracer
        segments = None
        if self.mesh is None:
            with tr.span("solve_packed/dispatch", cat="solve",
                         width=spec.width, groups=g):
                out = self._packed_jit(spec, spec.width, "fresh")(
                    b_dev, x0_dev
                )
        else:
            # width-segmented packed solve: each retirement (or policy
            # reduction) event exits the loop, the exchange re-slices at the
            # live width, and the solve resumes from the same carry
            t_seg, carry, k_prev, segments = spec.width, None, 0, []
            while True:
                with tr.span("solve/segment", cat="solve", width=t_seg,
                             packed=True, groups=g) as sp:
                    if carry is None:
                        carry = self._packed_jit(spec, t_seg, "fresh")(
                            b_dev, x0_dev
                        )
                    else:
                        carry = self._packed_jit(spec, t_seg, "resume")(carry)
                    k = int(carry["k"])
                    bd = bool(carry["bd"])
                    it_seg = k - k_prev
                    sp.args["iters"] = it_seg
                    if tr.enabled:
                        sp.args.update(self._struct_attrs(t_seg))
                segments.append((t_seg, it_seg))
                k_prev = k
                n_act = int(jnp.sum(carry["act"]))
                if (
                    not bool(jnp.any(carry["grp_live"]))
                    or bd
                    or k >= cfg.max_iters
                    or n_act >= t_seg
                    or n_act == 0
                ):
                    break
                new_w = max(n_act, 1)
                if it_seg == 0 and new_w == t_seg:
                    break  # zero-progress segment at a stable width
                # retirement (or reduction) event -> re-slice; a pack whose
                # groups arrive pre-converged (x0 at tolerance) exits its
                # first segment after zero iterations and re-slices straight
                # to the initial live width
                t_seg = new_w
            out = carry
        self.stats.solves += g
        with tr.span("solve_packed/finalize", cat="solve", groups=g):
            results = self._finalize_packed(out, x0_dev, spec, segments)
        if tr.enabled:
            tr.counter("solver.solves", self.stats.solves)
        return results

    def _finalize_packed(self, out, x0_dev, spec: GroupSpec, segments):
        """Split one packed loop carry into k honest per-request results."""
        te, g = spec.t_each, spec.n_groups
        big_x = out["X"]
        xs = x0_dev + big_x.reshape(big_x.shape[0], g, te).sum(axis=2)
        xs = np.asarray(xs)
        grp_iter = np.asarray(out["grp_iter"])
        grp_hist = np.asarray(out["grp_hist"])
        k_total = int(out["k"])
        bd = bool(out["bd"])
        results = []
        for j in range(g):
            retired = int(grp_iter[j]) >= 0
            nit = int(grp_iter[j]) if retired else k_total
            hist_j = grp_hist[:, j].copy()
            hist_j[nit + 1:] = np.nan  # frozen-past-retirement -> NaN padding
            results.append(SolveResult(
                x=xs[:, j],
                n_iters=nit,
                res_hist=hist_j,
                converged=retired,
                breakdown=bd and not retired,
                t=te,
                selection=self.selection,
                comm_segments=segments,
                pack=dict(
                    width=spec.width,
                    t_each=te,
                    n_groups=g,
                    group=j,
                    tol=spec.tols[j],
                    retired_iter=int(grp_iter[j]) if retired else None,
                    packed_iters=k_total,
                ),
            ))
        return results

    def unshard(self, arr):
        """Padded per-rank layout -> global (n, ...) numpy array (identity
        for a sequential handle)."""
        if self.op is None:
            return np.asarray(arr)
        return self.op.unshard(arr)

    @property
    def partition(self):
        """The row partition this session was built on — pass it back to
        ``ECGSolver.build(..., pm=)`` to share the partitioning cost across
        independently configured sessions of the same matrix (None for a
        sequential handle built without one)."""
        return self._pm

    # ----------------------------------------------------------- derivation
    def with_config(self, **overrides) -> "ECGSolver":
        """Derive a sibling handle with config overrides, reusing as much
        setup as the overrides permit.

        Solve-level overrides (``tol``, ``max_iters``, the adaptive policy)
        reuse the operator, plan, and tuning outright — only the solve loop
        is re-jitted.  Operator-level overrides (strategy/backend/tile/
        overlap/tune/t) rebuild the operator but always reuse the row
        partition.  Accepts the flat field spellings of
        :meth:`SolverConfig.replace`.
        """
        new_cfg = self.config.replace(**overrides)
        clone = ECGSolver.__new__(ECGSolver)
        clone.a, clone.mesh, clone.config = self.a, self.mesh, new_cfg
        clone._tracer = self._tracer
        clone.stats = SolverStats()
        clone.selection = None
        clone.tuned = None
        clone.op = None
        clone._pm = self._pm
        clone._probe_b = self._probe_b
        clone._runners, clone._jits = {}, {}
        clone._onehot_cache = {}
        clone._packed_applies = {}
        # siblings of the same matrix may reuse the parent's conversion
        # artifacts (validated against tile/shape/dtype at build time)
        clone._conversion_in = self.conversion
        clone.conversion = None
        reuse_op = (
            new_cfg.t == self.config.t
            and new_cfg.comm == self.config.comm
            and new_cfg.kernel == self.config.kernel
            and new_cfg.tune == self.config.tune
            # a t="auto" resolution is derived from the adaptive knobs
            # (candidates, cached select, probe budget/rtol, explicit off),
            # the tolerance (est_iters-to-tol drives the ranking), AND the
            # method (its synchronization term enters the per-iteration
            # cost): changing any of them must re-run the selection.  A
            # method change under a fixed t reuses the operator outright —
            # the SpMBV and reducers are method-agnostic; only the loop
            # closures differ, and those are rebuilt per clone anyway.
            and (
                not isinstance(self.config.t, str)
                or (
                    new_cfg.adaptive == self.config.adaptive
                    and new_cfg.tol == self.config.tol
                    and new_cfg.method == self.config.method
                )
            )
        )
        if reuse_op:
            clone.op = self.op
            clone.tuned = self.tuned
            clone.selection = self.selection
            clone.t = self.t
            clone._apply = self._apply
            clone._gram1, clone._gram2 = self._gram1, self._gram2
            clone._sqnorm, clone._tail = self._sqnorm, self._tail
            clone._gram2p = self._gram2p
            clone._sqnorm_cols = self._sqnorm_cols
            clone._split_fn = self._split_fn
            clone.conversion = self.conversion
            # the preconditioner depends only on (a, op, precondition cfg):
            # operator reuse keeps it unless the precondition knobs changed
            if new_cfg.precondition == self.config.precondition:
                clone._precond = self._precond
            else:
                clone._precond = clone._build_precond()
            clone._onehot_cache = self._onehot_cache
            if self.mesh is not None:
                clone._onehot_np = self._onehot_np
            if new_cfg.adaptive == self.config.adaptive:
                clone.policy = self.policy  # keeps auto-t's implied rankrev
            else:
                pol = new_cfg.adaptive.policy
                if (
                    pol is None
                    and clone.selection is not None
                    and not new_cfg.adaptive.explicit_off
                ):
                    # auto-t implies breakdown safety unless explicitly off
                    pol = resolve_policy("rankrev")
                clone.policy = pol
            clone._segmented = (
                clone.policy is not None
                and not clone.policy.restart
                and self.mesh is not None
            )
            clone.stats.op_reused = True
            clone.stats.partition_reused = self.mesh is not None
        else:
            clone._build()
            clone.stats.partition_reused = self.mesh is not None
        return clone

    # ---------------------------------------------------------- diagnostics
    def lowered_text(self, dtype=None, width: int | None = None) -> str:
        """Compiled HLO of the (fresh) solve program at ``width`` — used by
        the collective-structure tests (§3.1 two-psum invariant)."""
        dtype = self.a.data.dtype if dtype is None else dtype
        width = self.t if width is None else width
        sds = self._vec_struct((), dtype)
        return self._jit(width, "fresh").lower(sds, sds).compile().as_text()

    def _vec_struct(self, cols: tuple, dtype):
        """Shape, dtype and placement of the solve's (n, *cols) operands,
        as :meth:`solve` lays them out (so an AOT compile from it is the
        program the solve call runs)."""
        if self.mesh is None:
            return jax.ShapeDtypeStruct((self.a.shape[0],) + cols, np.dtype(dtype))
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._onehot(dtype)  # warm eagerly — a trace must not put
        return jax.ShapeDtypeStruct(
            (self.op.n_padded,) + cols, np.dtype(dtype),
            sharding=NamedSharding(self.mesh, P(("node", "proc"), *(None,) * len(cols))),
        )

    def packed_lowered_text(
        self, tols, dtype=None, width_seg: int | None = None
    ) -> str:
        """Compiled HLO of the (fresh) *packed* solve program for a group
        layout of ``len(tols)`` requests, at exchange width ``width_seg`` —
        used by the retirement re-slice gates (all-reduce count unchanged,
        collective-permute payload drops with the live width)."""
        dtype = self.a.data.dtype if dtype is None else dtype
        spec = GroupSpec(
            t_each=self.t,
            tols=tuple(
                self.config.tol if tt is None else float(tt) for tt in tols
            ),
        )
        width_seg = spec.width if width_seg is None else width_seg
        sds = self._vec_struct((spec.n_groups,), dtype)
        fn = self._packed_jit(spec, width_seg, "fresh")
        return fn.lower(sds, sds).compile().as_text()
