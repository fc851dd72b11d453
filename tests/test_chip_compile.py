"""The Pallas kernels of the main path compiled for a described TPU v5e.

No chip is attached: the TPU compiler is installed and compiles for a
``v5e:2x2`` topology it is only told about. Each test lowers one solve at
a real size with shapes (not arrays) and asserts the compiled program
holds a Mosaic kernel (``tpu_custom_call``) — i.e. the kernel lowered for
the chip instead of the interpreter. Nothing runs, so nothing here says
anything about results or times. Each kernel also carries its stable
name into the compiled program (``ops.pallas_cg.named``): the custom
call's ``kernel_metadata``, which a TPU profile shows in the name of
each of the kernel's op events.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file. All these compiles stay in this one file
for the same reason.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from poisson_tpu.config import Problem
from poisson_tpu.ops import pallas_ca, pallas_cg, pallas_resident
from poisson_tpu.parallel import pallas_ca_sharded, pallas_sharded
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS

KERNEL = "tpu_custom_call"
# The name a Mosaic kernel's custom call carries (the JSON of its
# kernel_metadata spans lines; the operands' tuple elements carry it too,
# so only the custom call's own attribute counts).
KERNEL_NAME = re.compile(r'custom_call_target="tpu_custom_call"[^\n]*?'
                         r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"')
# Compiled text of each lowering, by key, within this module.
_TEXTS = {}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it.
    # And compile as the chip runs, in 32-bit mode: under conftest's x64
    # the kernels' index maps return i64, which Mosaic cannot legalize.
    saved = {name: getattr(jax.config, name) for name in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for name in saved:
        jax.config.update(name, False)
    compilation_cache.reset_cache()
    yield topology
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), (X_AXIS, Y_AXIS))


def _canvases(cv, sharding, n=5):
    shape = jax.ShapeDtypeStruct((cv.rows, cv.cols), jnp.float32,
                                 sharding=sharding)
    return [shape] * n


def _compiled(key, lower):
    if key not in _TEXTS:
        _TEXTS[key] = lower().compile().as_text()
    return _TEXTS[key]


def _assert_kernel(key, lower):
    assert KERNEL in _compiled(key, lower)


@pytest.mark.parametrize("M,N,serial", [
    (800, 1200, False), (800, 1200, True), (2400, 3200, False),
])
def test_fused(one_chip, M, N, serial):
    _assert_kernel(("fused", M, N, serial), lambda: _lower_fused(
        one_chip, M, N, serial))


@pytest.mark.parametrize("serial", [False, True])
def test_ca(one_chip, serial):
    _assert_kernel(("ca", serial), lambda: _lower_ca(one_chip, serial))


def test_resident(one_chip):
    _assert_kernel(("resident",), lambda: _lower_resident(one_chip))


def test_batched_fused(one_chip):
    _assert_kernel(("batched",), lambda: _lower_batched(one_chip))


def _stacked_args(spec, mesh):
    cv, shards = spec.cv, mesh.devices.size
    stacked = NamedSharding(mesh, P((X_AXIS, Y_AXIS)))
    canvas = jax.ShapeDtypeStruct((shards, cv.rows, cv.cols), jnp.float32,
                                  sharding=stacked)
    sc_int = jax.ShapeDtypeStruct((shards, spec.m_blk, spec.n_blk),
                                  jnp.float32, sharding=stacked)
    colmask = jax.ShapeDtypeStruct((1, cv.cols), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
    return [canvas] * 5 + [sc_int, colmask]


def _lower_fused(one_chip, M, N, serial):
    problem = Problem(M=M, N=N)
    cv = pallas_cg.canvas_spec(problem)
    return pallas_cg._fused_solve.lower(
        problem, cv, False, False, serial, *_canvases(cv, one_chip))


def _lower_ca(one_chip, serial):
    problem = Problem(M=800, N=1200)
    cv = pallas_cg.canvas_spec(problem, pallas_ca.pick_bm_ca(problem), 0)
    return pallas_ca._ca_solve.lower(
        problem, cv, False, False, serial, *_canvases(cv, one_chip))


def _lower_resident(one_chip):
    problem = Problem(M=400, N=600)
    cv = pallas_resident.resident_canvas(problem)
    return pallas_resident._resident_solve.lower(
        problem, cv, False, *_canvases(cv, one_chip))


def _lower_batched(one_chip):
    """The batch cell's program: 64 right-hand sides at 400×600 on the
    member-axis kernels, with the batched path's tight strips."""
    problem = Problem(M=400, N=600)
    cv = pallas_cg.canvas_spec(problem, pallas_cg.batched_bm(problem))
    canvas, _, _, _, _ = _canvases(cv, one_chip)
    stack = jax.ShapeDtypeStruct((64, cv.rows, cv.cols), jnp.float32,
                                 sharding=one_chip)
    sc_int = jax.ShapeDtypeStruct((problem.M - 1, problem.N - 1),
                                  jnp.float32, sharding=one_chip)
    return pallas_cg._fused_solve_batched.lower(
        problem, cv, False, canvas, canvas, canvas, stack, canvas, sc_int)


def _lower_fused_sharded(mesh):
    problem = Problem(M=2400, N=3200)
    spec = pallas_sharded.shard_spec(problem, 2, 2)
    return pallas_sharded._fused_solve_sharded.lower(
        problem, mesh, spec, False, *_stacked_args(spec, mesh))


def _lower_ca_sharded(mesh):
    problem = Problem(M=2400, N=3200)
    spec = pallas_ca_sharded.ca_shard_spec(problem, 2, 2)
    return pallas_ca_sharded._ca_solve_sharded.lower(
        problem, mesh, spec, False, *_stacked_args(spec, mesh))


def test_fused_sharded(mesh):
    _assert_kernel(("fused_sharded",), lambda: _lower_fused_sharded(mesh))


def test_ca_sharded(mesh):
    _assert_kernel(("ca_sharded",), lambda: _lower_ca_sharded(mesh))


# Each lowering above, by the key its test compiles it under, and the
# stable names of the kernels it holds: the fused pair serves the
# one-chip and the sharded solve alike, the batched pair the batches,
# the strip pair the finest level of the large MG solve.
NAMED = [
    (("fused", 800, 1200, False), "one_chip",
     lambda chip: _lower_fused(chip, 800, 1200, False),
     {"direction_and_stencil", "fused_update"}),
    (("fused", 2400, 3200, False), "one_chip",
     lambda chip: _lower_fused(chip, 2400, 3200, False),
     {"direction_and_stencil", "fused_update"}),
    (("ca", False), "one_chip", lambda chip: _lower_ca(chip, False),
     {"basis_sweep", "pair_update"}),
    (("resident",), "one_chip", _lower_resident, {"resident_solve"}),
    (("batched",), "one_chip", _lower_batched,
     {"batched_direction_and_stencil", "batched_fused_update"}),
    (("fused_sharded",), "mesh", _lower_fused_sharded,
     {"direction_and_stencil", "fused_update"}),
    (("ca_sharded",), "mesh", _lower_ca_sharded,
     {"basis_sweep", "pair_update"}),
    (("mg", 6400, 9600), "one_chip",
     lambda chip: _lower_mg(chip, 6400, 9600, _mg_kernel_levels(6400, 9600)),
     {"mg_presmooth_residual", "mg_postsmooth"}),
]


@pytest.mark.parametrize("key,where,lower,names", NAMED,
                         ids=["-".join(map(str, n[0])) for n in NAMED])
def test_kernels_carry_their_names(request, key, where, lower, names):
    target = request.getfixturevalue(where)
    text = _compiled(key, lambda: lower(target))
    # Every Mosaic kernel of the program carries one name, and the names
    # are the lowering's own.
    assert text.count(f'custom_call_target="{KERNEL}"') == len(
        KERNEL_NAME.findall(text)) > 0
    assert set(KERNEL_NAME.findall(text)) == names


def _mg_hierarchy(one_chip, M, N, strips):
    """Shapes of the MG hierarchy of an M×N grid, with the first
    ``strips`` levels also laid out for the strip kernels."""
    from poisson_tpu.mg import plan_levels
    from poisson_tpu.mg.hierarchy import MGLevels

    def grid(m, n):
        return jax.ShapeDtypeStruct((m + 1, n + 1), jnp.float32,
                                    sharding=one_chip)

    dims = plan_levels(M, N)
    (mc, nc) = dims[-1]
    coarse = (mc - 1) * (nc - 1)
    return MGLevels(
        levels=tuple((grid(m, n),) * 3 for m, n in dims),
        coarse_inv=jax.ShapeDtypeStruct((coarse, coarse), jnp.float32,
                                        sharding=one_chip),
        scinv=grid(M, N),
        strips=tuple((grid(n, m),) * 3 for m, n in dims[:strips]))


def _lower_mg(one_chip, M, N, strips=0):
    from poisson_tpu.mg import DEFAULT_MG
    from poisson_tpu.mg.preconditioner import _solve_mg

    hier = _mg_hierarchy(one_chip, M, N, strips)
    g = hier.scinv
    return _solve_mg.lower(Problem(M=M, N=N), True, DEFAULT_MG, 0, 0, 0.0,
                           g, g, g, g, hier, interpret=False)


def _mg_kernel_levels(M, N):
    from poisson_tpu.mg import plan_levels
    from poisson_tpu.mg.hierarchy import kernel_levels

    return kernel_levels("tpu", "float32", plan_levels(M, N))


def test_mg_solve_takes_no_gather(one_chip):
    """The V-cycle's restriction takes every other node by a strided slice
    and a reshape: jnp's step indexing would lower to gathers, which a v5e
    ran at under 1 GB/s (77% of a 6400x9600 MG solve's device time). Every
    level keeps its ``mg_level`` tag through the TPU compiler."""
    text = _compiled(("mg", 400, 600), lambda: _lower_mg(one_chip, 400, 600))
    assert " gather(" not in text
    from poisson_tpu.mg import plan_levels

    levels = {int(m) for m in re.findall(r'mg_level="(\d+)"', text)}
    assert levels == set(range(len(plan_levels(400, 600))))


def _custom_calls(text):
    """The text of each Mosaic custom call instruction of ``text``."""
    starts = [text.rfind("\n", 0, m.start())
              for m in re.finditer(f'custom_call_target="{KERNEL}"', text)]
    return [text[s:text.find("\n  %", s + 1)] for s in starts]


def test_mg_strip_kernels_at_the_cell_size(one_chip):
    """At 6400x9600 the rule puts levels 0 and 1 on the strip kernels:
    the solo program holds both, each carrying its level's ``mg_level``
    tag beside its name, and still no gather."""
    strips = _mg_kernel_levels(6400, 9600)
    assert strips == 2
    text = _compiled(("mg", 6400, 9600), lambda: _lower_mg(
        one_chip, 6400, 9600, strips))
    calls = _custom_calls(text)
    assert {re.search(r'"kernel"\s*:\s*"(\w+)"', c).group(1)
            for c in calls} == {"mg_presmooth_residual", "mg_postsmooth"}
    levels = [re.findall(r'mg_level="(\d+)"', c) for c in calls]
    assert all(len(found) == 1 for found in levels)
    assert {int(found[0]) for found in levels} == set(range(strips))
    assert " gather(" not in text


def test_batched_mg_keeps_the_xla_cycle(one_chip):
    """``_solve_batched_mg`` ignores the strip layout its hierarchy
    carries: no Mosaic kernel in the vmapped twin."""
    from poisson_tpu.mg import DEFAULT_MG
    from poisson_tpu.mg.preconditioner import _solve_batched_mg

    hier = _mg_hierarchy(one_chip, 400, 600, 1)
    g = hier.scinv
    stack = jax.ShapeDtypeStruct((2,) + g.shape, jnp.float32,
                                 sharding=one_chip)
    text = _compiled(("mg_batched", 400, 600), lambda: (
        _solve_batched_mg.lower(Problem(M=400, N=600), True, DEFAULT_MG, 0,
                                0.0, g, g, stack, g, hier)))
    assert KERNEL not in text


def _mg_mesh_operands(mesh, problem, plan, strips=0):
    """Shapes of the MG solve over ``mesh``: blocks of the sharded levels
    (a chip's (m̂_l + 2, n̂_l + 2) each), whole grids from the replication
    level down, and the first ``strips`` levels' transposed
    (n̂_l + 4, m̂_l + 4) strip-kernel blocks, as
    ``mg.hierarchy.mesh_hierarchy`` places them."""
    from poisson_tpu.mg.hierarchy import MGLevels

    blocked = NamedSharding(mesh, P(X_AXIS, Y_AXIS))
    whole = NamedSharding(mesh, P())

    def strip(lvl):
        shape = (plan.py * ((plan.n_blk >> lvl) + 4),
                 plan.px * ((plan.m_blk >> lvl) + 4))
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(
                                        mesh, P(Y_AXIS, X_AXIS)))

    def level(lvl):
        if lvl < plan.replicated_from:
            shape = (plan.px * ((plan.m_blk >> lvl) + 2),
                     plan.py * ((plan.n_blk >> lvl) + 2))
            return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=blocked)
        m, n = plan.dims[lvl]
        return jax.ShapeDtypeStruct((m + 1, n + 1), jnp.float32,
                                    sharding=whole)

    mc, nc = plan.dims[-1]
    coarse = (mc - 1) * (nc - 1)
    block = level(0)
    hier = MGLevels(
        # A strip level places none of its blocks but level 0's a and b.
        levels=tuple(tuple(None if lvl < strips and (lvl or k == 2)
                           else level(lvl) for k in range(3))
                     for lvl in range(len(plan.dims))),
        coarse_inv=jax.ShapeDtypeStruct((coarse, coarse), jnp.float32,
                                        sharding=whole),
        scinv=block,
        strips=tuple((strip(lvl),) * 3 for lvl in range(strips)))
    return hier, block, block


# Level-0-block-sized passes of their own (copies, transposes, pads) in the
# loop body of the mesh MG program before its levels 0-1 ran on the strip
# kernels: a relayout copy of a 6402x9602 block and one of the restriction's
# 6402x4800 half-width rows.
MESH_BLOCK_PASSES_ON_XLA = 2


def _loop_body(text):
    """The text of the while loop's body computation in ``text``."""
    body = re.search(r"body=%([\w.\-]+)", text).group(1)
    start = text.index(f"\n%{body} ") + 1
    end = re.compile(r"\n(?=%|ENTRY)").search(text, start + 1)
    return text[start:end.start() if end else len(text)]


def _block_passes(body, m_blk, n_blk):
    """Copies, transposes and pads in ``body`` whose f32 result holds at
    least half a (m_blk, n_blk) block."""
    found = re.finditer(r"\n\s*(?:ROOT )?%\S+ = f32\[(\d+),(\d+)\]\S* "
                        r"(copy|transpose|pad)\(", body)
    return [m.group(0).strip() for m in found
            if int(m.group(1)) * int(m.group(2)) * 2 >= m_blk * n_blk]


def test_mg_mesh_program_at_the_cell_size(mesh):
    """The MG solve of the ``mg-mesh2x2-12800x19200`` cell, compiled for
    a described v5e 2x2: it fits a chip's 16 GB by XLA's own count (printed
    per chip); the rule puts the shards' levels 0 and 1 on the strip
    kernels, each call carrying its level's ``mg_level`` tag; every halo
    permute of the sharded levels 0-2 and the gather at level 3 carry
    theirs; no gather op; and the loop body makes no more block-sized
    copies than the XLA cycle's did."""
    from poisson_tpu.mg import DEFAULT_MG
    from poisson_tpu.mg.hierarchy import mesh_kernel_levels
    from poisson_tpu.parallel import mg_sharded

    problem = Problem(M=12800, N=19200)
    plan = mg_sharded.plan_mesh(problem, 2, 2)
    assert plan.replicated_from == 3
    strips = mesh_kernel_levels("tpu", "float32", plan)
    assert strips == 2
    compiled = mg_sharded._solve_mg_sharded.lower(
        problem, mesh, plan, DEFAULT_MG, True,
        *_mg_mesh_operands(mesh, problem, plan, strips),
        interpret=False).compile()
    stats = compiled.memory_analysis()
    per_chip = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes)
    print(f"mg mesh 12800x19200, XLA's memory estimate a chip: "
          f"{per_chip / 1e9:.2f} GB (operands "
          f"{stats.argument_size_in_bytes / 1e9:.2f}, output "
          f"{stats.output_size_in_bytes / 1e9:.2f}, temporaries "
          f"{stats.temp_size_in_bytes / 1e9:.2f})")
    assert per_chip < 16e9
    text = compiled.as_text()
    calls = _custom_calls(text)
    assert {re.search(r'"kernel"\s*:\s*"(\w+)"', c).group(1)
            for c in calls} == {"mg_presmooth_residual", "mg_postsmooth"}
    levels = [re.findall(r'mg_level="(\d+)"', c) for c in calls]
    assert all(len(found) == 1 for found in levels)
    assert {int(found[0]) for found in levels} == set(range(strips))
    assert " gather(" not in text
    tags = {}
    for line in text.splitlines():
        op = re.search(r" (collective-permute-start|all-gather)\(", line)
        if op and line.lstrip().startswith("%"):
            tag = re.search(r'mg_level="(\d+)"', line)
            tags.setdefault(op.group(1), []).append(
                None if tag is None else int(tag.group(1)))
    # Four untagged permutes: the CG body's exchange of p.
    assert tags["collective-permute-start"].count(None) == 4
    assert ({t for t in tags["collective-permute-start"] if t is not None}
            == {0, 1, 2})
    assert 3 in tags["all-gather"]
    passes = _block_passes(_loop_body(text), plan.m_blk, plan.n_blk)
    print(f"level-0-block-sized copies, transposes and pads in the loop "
          f"body: {len(passes)}")
    assert len(passes) <= MESH_BLOCK_PASSES_ON_XLA
