"""Measured microbenchmark mode for the setup-time autotuner.

When the :class:`~repro.core.machines.MachineParams` constants are in doubt
(new machine, virtualized hosts, unknown NIC contention), the tuner can
*measure* instead of model: build the candidate distributed SpMBV operators
on the real mesh, time a few applications of each, and take the argmin.
This is the paper's "four trial SpMBVs at communicator-setup time" tuning,
extended to the tile-shape and overlap axes.

To keep setup cost bounded the search is coordinate descent rather than the
full grid: strategies first (blocking, reference tile), then tile shapes
under the winning strategy, then blocking-vs-overlap for the winning pair —
4 + |tiles| + 2 operator builds instead of 4·|tiles|·2.
``benchmarks/tuner_sweep.py`` measures the *full* grid to audit both the
models and this descent against the exhaustive answer.
"""

from __future__ import annotations

import numpy as np

from repro.sparse.partition import PartitionedMatrix, partition_csr


def measure_config(
    a,
    mesh,
    t: int,
    strategy: str,
    ell_block,
    overlap: bool,
    backend: str = "pallas",
    machine=None,
    pm: PartitionedMatrix | None = None,
    repeats: int = 3,
    seed: int = 0,
) -> float:
    """Wall microseconds per distributed SpMBV application for one config
    (fixed operand ``seed``, median of ``repeats`` — reproducible on hosts)."""
    import jax

    # the one warmup+median timer shared with the benchmark sweeps, so
    # tuner measurements and benchmark rows stay comparable
    from repro.analysis.ecg_bench import _timeit
    from repro.sparse.spmbv import _make_distributed_spmbv

    op = _make_distributed_spmbv(
        a, mesh, strategy, t=t, machine=machine, pm=pm,
        backend=backend, overlap=overlap, ell_block=ell_block,
    )
    f = jax.jit(op.matvec_fn())
    rng = np.random.default_rng(seed)
    v = op.shard_vector(rng.standard_normal((a.shape[0], t)))
    return _timeit(f, v, repeats=repeats)


def measure_dispatch_overhead(
    mesh,
    rows: int = 64,
    width: int = 4,
    chain: tuple[int, int] = (2, 16),
    repeats: int = 7,
    dtype=None,
) -> float:
    """Measured seconds per executor dispatch (one pack / ppermute / unpack
    op), the constant the structural cost model charges as
    ``MachineParams.dispatch_overhead``.

    Times two jitted shard_map programs that chain the packed executor's
    primitive triple — ``halo_pack`` → ``lax.ppermute`` → ``halo_unpack`` —
    ``chain[0]`` and ``chain[1]`` times over a tiny (rows, width) buffer,
    with a data dependency between links so XLA cannot elide or reorder
    them.  The buffer is deliberately small: the byte terms are negligible,
    so the wall-time *slope* over the extra links is pure per-op dispatch
    cost.  Returns the slope divided by 3 ops per link (clamped to a tiny
    positive floor so a noisy host never yields a non-positive constant).

    Feed the result back with
    ``dataclasses.replace(machine, dispatch_overhead=measured)`` to
    calibrate ``tune="model:structural"``; ``benchmarks/comm_sweep.py``
    records it in ``BENCH_comm_sweep.json``.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.analysis.ecg_bench import _timeit
    from repro.kernels.halo_pack.ops import halo_pack, halo_unpack

    dtype = dtype or np.float64
    p = int(mesh.devices.size)
    perm = [(i, (i + 1) % p) for i in range(p)]
    gidx = jnp.arange(rows, dtype=jnp.int32)
    spos = jnp.arange(rows, dtype=jnp.int32)

    def chain_fn(m):
        def per_device(x):
            for _ in range(m):
                buf = halo_pack(x, gidx)
                buf = jax.lax.ppermute(buf, ("node", "proc"), perm)
                stage = jnp.zeros((rows + 1, x.shape[1]), x.dtype)
                stage = halo_unpack(stage, buf, spos)
                x = stage[:rows]  # dependency: next link waits on this one
            return x
        return jax.jit(shard_map(
            per_device, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        ))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows, width)), dtype)
    m_lo, m_hi = chain
    us_lo = _timeit(chain_fn(m_lo), x, repeats=repeats)
    us_hi = _timeit(chain_fn(m_hi), x, repeats=repeats)
    per_op_s = (us_hi - us_lo) * 1e-6 / ((m_hi - m_lo) * 3)
    return max(per_op_s, 1e-9)


def tune_measured(
    a,
    mesh,
    t: int,
    backend: str = "pallas",
    tiles=None,
    machine=None,
    pm: PartitionedMatrix | None = None,
    repeats: int = 3,
):
    """Coordinate-descent measured tuning; returns a TunedConfig."""
    from repro.core.models import STRATEGIES
    from repro.tune.autotune import DEFAULT_TILES, TunedConfig, tile_stats

    tiles = tiles or DEFAULT_TILES
    n_nodes, ppn = mesh.devices.shape
    pm = pm or partition_csr(a, n_nodes * ppn)
    rmax = pm.part.max_local_rows
    measured: dict[str, float] = {}

    def probe(strategy, tile, overlap):
        key = f"{strategy}/{tile[0]}x{tile[1]}/{'overlap' if overlap else 'blocking'}"
        if key not in measured:
            measured[key] = measure_config(
                a, mesh, t, strategy, tile, overlap,
                backend=backend, machine=machine, pm=pm, repeats=repeats,
            )
        return measured[key]

    ref_tile = (8, 8) if rmax >= 8 else (rmax, rmax)
    strategy = min(STRATEGIES, key=lambda s: probe(s, ref_tile, False))

    tile = ref_tile
    if backend == "pallas":
        cand = [(br, bc) for br, bc in tiles if br <= rmax and bc <= rmax] or [ref_tile]
        tile = min(cand, key=lambda tl: probe(strategy, tl, False))

    overlap = min((False, True), key=lambda ov: probe(strategy, tile, ov))

    ts = tile_stats(pm, *tile)
    return TunedConfig(
        strategy=strategy,
        br=tile[0],
        bc=tile[1],
        kmax=ts.kmax,
        overlap=overlap,
        backend=backend,
        t=t,
        mode="measure",
        machine=machine,
        predicted={"measured_us": dict(measured)},
    )
