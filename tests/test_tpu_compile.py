"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on a CPU-only host.  They catch
what interpret mode cannot — Mosaic's tiling and layout rules, the SMEM and
VMEM limits — at the shapes of the paper's Example 2.1 DG problem in the
chip's precision (float32, x64 off): 1,310,720 rows, 16x16 element tiles,
five tiles per block row, t = 8, and one rank's halo of a 2x2 mesh.

A compile that passes is a rehearsal, not a chip run: nothing executes.
The topology is described inside a fixture, never at import (only one
process at a time may load the TPU library), and all of these tests live in
this one file, so under ``--dist loadfile`` one worker loads it.  They skip
only where the TPU compiler is not installed; any other failure to describe
the chip fails them.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

NBR, KMAX, TILE, T = 81920, 5, 16, 8
N = NBR * TILE           # 1,310,720 rows
RMAX = N // 4            # rows of one rank on a 2x2 mesh
HALO = 8192              # halo slots of one exchange phase


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def four_chips(v5e_2x2):
    """The host's four chips as one mesh axis ``r``, each holding a row
    block of every argument."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    return NamedSharding(Mesh(np.array(v5e_2x2.devices), ("r",)), PartitionSpec("r"))


@pytest.fixture
def chip_mode():
    """float32 semantics (x64 off), float32 matmul precision as the solve
    program traces it (``core.ecg.jit_solve``), and no persistent
    compilation cache: a compile for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False), jax.default_matmul_precision("highest"):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


F32, I32 = jnp.float32, jnp.int32


def test_bsr_spmbv_compiles(one_chip, chip_mode):
    from repro.kernels.bsr_spmbv.kernel import LANES, bsr_spmbv_pallas

    _compile(bsr_spmbv_pallas, one_chip,
             ((NBR // LANES, KMAX * TILE, TILE, LANES), F32), ((NBR, KMAX), I32),
             ((N, T), F32))


def test_fused_gram_compiles(one_chip, chip_mode):
    from repro.kernels.fused_gram.kernel import fused_gram_pallas

    _compile(fused_gram_pallas, one_chip, *[((N, T), F32)] * 4)


def test_ecg_tail_compiles(one_chip, chip_mode):
    from repro.kernels.block_update.kernel import ecg_tail_pallas

    _compile(ecg_tail_pallas, one_chip, *[((N, T), F32)] * 5, *[((T, T), F32)] * 3)


#: the repo's audikw_1 surrogate at scale 1.0: 243 x 243 elements of
#: 16 rows, 59,049 block rows (461.3 lane groups), 944,784 rows (57.7 blocks
#: of 16,384 block-vector rows): every kernel's last block is ragged
NBR_RAGGED = 243 * 243


@pytest.mark.parametrize("kernel", ["bsr_spmbv", "fused_gram", "ecg_tail", "v_rows"])
def test_kernels_compile_at_ragged_sizes(one_chip, chip_mode, kernel):
    from repro.kernels.block_update.kernel import ecg_tail_pallas
    from repro.kernels.bsr_spmbv.kernel import LANES, _v_rows, bsr_spmbv_pallas
    from repro.kernels.fused_gram.kernel import fused_gram_pallas

    n = NBR_RAGGED * TILE
    if kernel == "bsr_spmbv":
        _compile(bsr_spmbv_pallas, one_chip,
                 ((-(-NBR_RAGGED // LANES), KMAX * TILE, TILE, LANES), F32),
                 ((NBR_RAGGED, KMAX), I32), ((n, T), F32))
    elif kernel == "v_rows":
        _compile(lambda vt: _v_rows(vt, False), one_chip, ((T, n), F32))
    elif kernel == "fused_gram":
        _compile(fused_gram_pallas, one_chip, *[((n, T), F32)] * 4)
    else:
        _compile(ecg_tail_pallas, one_chip, *[((n, T), F32)] * 5, *[((T, T), F32)] * 3)


def test_halo_pack_compiles(one_chip, chip_mode):
    from repro.kernels.halo_pack.kernel import halo_pack_pallas

    _compile(halo_pack_pallas, one_chip, ((RMAX, T), F32), ((HALO,), I32))


def test_halo_unpack_compiles(one_chip, chip_mode):
    from repro.kernels.halo_pack.kernel import halo_unpack_pallas

    _compile(halo_unpack_pallas, one_chip,
             ((2 * HALO + 1, T), F32), ((HALO, T), F32), ((HALO,), I32))


def test_block_trisolve_compiles(one_chip, chip_mode):
    from repro.kernels.block_trisolve.kernel import block_trisolve_pallas

    _compile(block_trisolve_pallas, one_chip,
             ((NBR, TILE, TILE), F32), ((NBR, TILE, T), F32))


def test_solve_program_names_its_stages(one_chip, chip_mode, monkeypatch):
    """The whole solve program, compiled for the chip: each kernel's
    custom call carries its stage's scope and keeps the name the
    benchmark's readers select on (a small DG operator; the stages do not
    depend on its size)."""
    import importlib
    import re

    from repro.solver import ECGSolver, KernelConfig, SolverConfig
    from repro.sparse.matrices import dg_laplace_2d

    for name in ("bsr_spmbv", "fused_gram", "block_update"):
        # the kernels as the chip runs them: this host's backend is the CPU
        monkeypatch.setattr(importlib.import_module(f"repro.kernels.{name}.ops"),
                            "resolve_dispatch", lambda op, use: (True, False))
    a = dg_laplace_2d((8, 8), block=TILE, dtype=F32)
    solver = ECGSolver.build(a, None, SolverConfig(
        t=T, tol=1e-4, max_iters=100, tune="off",
        kernel=KernelConfig(backend="pallas", ell_block=(TILE, TILE))))
    vec = jax.ShapeDtypeStruct((a.shape[0],), F32, sharding=one_chip)
    fn, lifted = solver._jit(T, "fresh")._entry((vec, vec))
    consts = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip) for x in lifted]
    hlo = fn.lower(consts, vec, vec).compile().as_text()
    # the gather of V is named inside the SpMBV
    assert re.search(r'op_name="[^"]*/ecg\.spmbv/[^"]*ecg\.gather/', hlo)
    calls = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            op_name = re.search(r'op_name="([^"]*)"', line)[1]
            if "/while/body/" in op_name:
                calls[op_name.split("/")[-2]] = op_name.split("/")
    assert set(calls) == {"bsr_spmbv", "v_rows", "fused_gram", "ecg_tail"}
    assert "ecg.spmbv" in calls["bsr_spmbv"] and "jit(_product)" in calls["bsr_spmbv"]
    # the relayout of V is part of the gather; only the product's op_name
    # names the kernel, which the benchmark's readers match on
    assert "ecg.gather" in calls["v_rows"] and "bsr_spmbv" not in "/".join(calls["v_rows"])
    assert "ecg.gram" in calls["fused_gram"]
    assert "ecg.update" in calls["ecg_tail"]


def _instructions(hlo):
    """{name: (result shape with layout, opcode, operand names)} of every
    instruction of the compiled text; layouts without their tiling."""
    import re

    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)", line)
        op = m and re.search(r"\s([a-z][\w\-]*)\(([^)]*)\)", m[2])
        if not op:
            continue
        ty = re.sub(r":[^}]*\}", "}", m[2][: op.start()].strip())
        out[m[1]] = (ty, op[1], re.findall(r"%([\w.\-]+)", op[2]), line)
    return out


def test_block_vector_kernels_take_xla_layout(one_chip, chip_mode):
    """Ten steps whose five (N, T) carries go through the ``ecg_tail`` and
    ``fused_gram`` kernels compile with no relayout of a block vector: XLA
    keeps an (N, 8) array as (8, N) row-major, the view the kernels read,
    so every operand of the two custom calls is a bitcast, a parameter, a
    tuple element or a copy that keeps its layout, and no program value is
    an (N/16, 16, 8) fold view or an (N, 8) array laid out row-major."""
    from repro.kernels.block_update.kernel import ecg_tail_pallas
    from repro.kernels.fused_gram.kernel import fused_gram_pallas

    def run(x, r, p, ap, po, c, d, do):
        def body(i, carry):
            x, r, p, ap, po, g = carry
            x, r, z = ecg_tail_pallas(x, r, p, ap, po, c, d, do)
            return x, r, z, ap * 1.5, p, g + fused_gram_pallas(p, r, ap, po)

        g = jnp.zeros((T, 3 * T), F32)
        return jax.lax.fori_loop(0, 10, body, (x, r, p, ap, po, g))

    ins = _instructions(_compile(run, one_chip, *[((N, T), F32)] * 5, *[((T, T), F32)] * 3))
    for ty, _, _, line in ins.values():
        assert f"[{NBR}," not in ty and f"f32[{N},{T}]{{1,0}}" not in ty, line
    kernels = {}
    for name, (ty, opcode, operands, line) in ins.items():
        if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in line:
            kernels[name] = operands
            for operand in operands:
                oty, oop, src, oline = ins[operand]
                same = oop == "copy" and ins[src[0]][0] == oty
                assert oop in ("bitcast", "parameter", "get-tuple-element") or same, oline
    assert sorted(k.split(".")[0] for k in kernels) == ["ecg_tail", "fused_gram"]
    assert [len(v) for k, v in sorted(kernels.items())] == [8, 4]


def _computation(hlo, name):
    """The text of the computation ``name`` of the compiled program."""
    start = hlo.index(f"%{name} (")
    return hlo[start:hlo.index("\n}", start)]


@pytest.mark.parametrize("nbr,halo,ranks", [
    pytest.param(NBR, 0, 1, id=str(NBR)),
    pytest.param(NBR_RAGGED, 0, 1, id=str(NBR_RAGGED)),
    pytest.param(RMAX // TILE, HALO + 1, 1, id="rank_with_halo"),
    pytest.param(RMAX // TILE, HALO + 1, 4, id="ranks_on_2x2_mesh"),
])
def test_spmbv_relayouts_stay_in_vmem(request, chip_mode, monkeypatch, nbr, halo, ranks):
    """Ten steps whose (n, T) carry goes through ``bsr_spmbv_pallas``, at
    DG's size, at the surrogate's ragged one, and as one rank of the 2x2
    cell runs it (``DistributedSpMBV._ell_spmbv`` over the carry joined to
    a halo of HALO + 1 rows and padded to whole tiles: 20,993 block
    columns, a ragged number of lane groups; alone, and in a ``shard_map``
    over the host's four chips), compile with no relayout of
    V or W through HBM: no program value has a minor dimension of 8 or 16
    under a (8, 128) tile (``f32[8,81920,16]``, ``f32[640,128,16,8]``), no
    1-D loop-reshape of a block vector is left, and the kernel's gathered
    operand is the gather's result through bitcasts only, the gather
    reading the rows ``v_rows`` wrote from Vᵀ."""
    import importlib
    import re

    from repro.kernels.bsr_spmbv.kernel import LANES, bsr_spmbv_pallas
    from repro.sparse.spmbv import DistributedSpMBV

    # the kernel as the chip runs it: this host's backend is the CPU
    monkeypatch.setattr(importlib.import_module("repro.kernels.bsr_spmbv.ops"),
                        "resolve_dispatch", lambda op, use: (True, False))
    n = nbr * TILE

    def product(blocks, idx, v):
        if not halo:
            return bsr_spmbv_pallas(blocks, idx, v)
        xfull = jnp.concatenate([v, v[-halo:] * 0.25], axis=0)
        return DistributedSpMBV._ell_spmbv(None, xfull, blocks, idx)[:n]

    sharding = request.getfixturevalue("one_chip" if ranks == 1 else "four_chips")
    if ranks > 1:
        product = jax.shard_map(product, mesh=sharding.mesh, in_specs=sharding.spec,
                                out_specs=sharding.spec, check_vma=False)

    def run(blocks, idx, v):
        return jax.lax.fori_loop(0, 10, lambda i, v: product(blocks, idx, v) * 0.5 + v, v)

    hlo = _compile(run, sharding,
                   ((ranks * -(-nbr // LANES), KMAX * TILE, TILE, LANES), F32),
                   ((ranks * nbr, KMAX), I32), ((ranks * n, T), F32))
    ins = _instructions(hlo)
    for ty, _, _, line in ins.values():
        assert f"[{n * T}]" not in ty, line
        for dims, layout in re.findall(r"\[([\d,]*)\]\{([\d,]+):T\(8,128\)", line):
            dims = [int(d) for d in dims.split(",")]
            minor = dims[int(layout.split(",")[0])]
            assert minor not in (TILE, T), line
    (kernel,) = [k for k, (_, op, _, line) in ins.items()
                 if op == "custom-call" and k.startswith("bsr_spmbv")]
    src = ins[kernel][2][1]
    while ins[src][1] == "bitcast":
        src = ins[src][2][0]
    assert ins[src][1] in ("gather", "fusion"), ins[src][3]
    if ins[src][1] == "fusion":
        assert " gather(" in _computation(hlo, re.search(r"calls=%([\w.\-]+)", ins[src][3])[1])
    assert ins[ins[src][2][0]][0].startswith(f"f32[{-(-(n + halo) // TILE)},{LANES}]")
    assert ins[src][2][0].startswith("v_rows")
