"""Compile the main path's programs and the Pallas kernels for a described
TPU v5e (``v5e:2x2``), with no chip attached.

The TPU compiler refuses here what it would refuse on the chip: tiles that
are not aligned, kernels that need more fast memory than they may use, and
programs that do not fit the device's 16 GB.  Nothing runs, so these tests
say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a test file that decided at
import whether its tests exist would give pytest-xdist workers different
collections.  Keep every such compile in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9        # one v5e chip
DECODE_BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mamba_decode():
    """(step, abstract args) of the full-width mamba2-2.7b bf16 decode step."""
    from repro.configs.registry import get
    from repro.models.model import abstract_cache, build_forward, init_abstract

    cfg = get("mamba2-2.7b")
    decode = build_forward(cfg, "decode")

    def step(params, cache, batch, pos):
        return decode(params, cache, batch, pos, cfg)

    args = (init_abstract(cfg), abstract_cache(cfg, DECODE_BATCH, 1),
            {"tokens": jax.ShapeDtypeStruct((DECODE_BATCH, 1), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.int32))
    return step, args


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_mamba2_decode_step_fits_one_chip(one_chip, mamba_decode):
    step, args = mamba_decode
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        *_on(one_chip, args)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_proxy_group_executable_compiles(one_chip, mamba_decode):
    from repro.core.replay import init_replay_state
    from repro.core.synthesize import synthesize
    from repro.sharding.collectives import LocalSim

    step, args = mamba_decode
    module = synthesize(step, *args, axis_sizes={}).proxy.module
    state = jax.eval_shape(lambda: init_replay_state(module))
    compiled = jax.jit(lambda s: module.run_rank(s, LocalSim(), 0)).lower(
        _on(one_chip, state)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_npb_mg_class_c_solve_fits_four_chips(topo):
    """NPB MG's class C solve over the 1 x 2 x 2 rank grid of the
    benchmark's cell: it fits each chip, and its halos are collectives."""
    from repro.compat import make_mesh
    from repro.models import npb_mg
    mesh = make_mesh((2, 2), ("z", "y"), devices=topo.devices[:4])
    n = npb_mg.CLASSES["C"]["n"]
    v = jax.ShapeDtypeStruct((n, n, n), jnp.float32,
                             sharding=npb_mg.sharding(mesh))
    compiled = npb_mg.build("C", mesh).lower(v).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert "collective-permute" in compiled.as_text()


def _branch_row_copies(hlo: str) -> list[str]:
    """Copies of a ``move_shift`` row buffer (``f32[rows,32768]``) inside
    the computations a conditional lists as its branches."""
    import re
    names = set()
    for m in re.finditer(r"branch_computations=\{([^}]*)\}", hlo):
        names |= {n.strip().lstrip("%") for n in m.group(1).split(",")}
    found, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
        elif comp in names and re.search(
                r"f32\[\d+,32768\]\{[^}]*\} copy\(", line):
            found.append(line.strip())
    return found


def test_program_table_switch_copies_no_row_buffer(one_chip):
    """A switched program table (a comm terminal, a compute terminal
    without ``move_shift``, ``move_shift`` at unrolls 8 and 1024, the
    latter past the 512-row cap) compiles with no row-buffer copy in any
    conditional branch: the row buffers stay out of the switch."""
    from repro.core import blocks
    from repro.core.progtable import ProgramTable
    from repro.sharding.collectives import LocalSim

    psum = dict(kind="psum", shape=(16,), dtype="float32", axes=("x",),
                detail=())
    terms = (("compute", (0, 0, 1, 0, 0, 0, 0, 0, 3, 0, 5), 8),
             ("compute", (0, 1, 0, 0, 0, 0, 0, 0, 0, 0, 1), 1),
             ("comm", "buf0", psum),
             ("compute", (0, 0, 0, 0, 0, 0, 0, 0, 2, 0, 2), 1024))
    prog = (("t", 0, 1), ("t", 2, 1), ("t", 1, 1), ("t", 3, 1), ("t", 2, 1),
            ("t", 0, 3), ("t", 1, 1), ("t", 2, 1))
    pt = ProgramTable(terms, {}, [prog])

    def state():
        st = blocks.init_state(0, (8, 1024))
        st["buf0"] = jnp.zeros((16,), jnp.float32)
        return st

    st = _on(one_chip, jax.eval_shape(state))
    hlo = jax.jit(lambda s: pt.run(0, s, LocalSim()),
                  donate_argnums=0).lower(st).compile().as_text()
    assert "branch_computations" in hlo
    assert {"rows8", "rows512"} <= set(st)
    assert _branch_row_copies(hlo) == []


def test_pgd_solver_compiles(one_chip):
    from repro.core import proxy_search

    # the zoo corpus: 8 compute terminals × the unroll grid, 6 metrics,
    # 11 substituted block columns
    n = 8 * len(proxy_search._UNROLLS)
    targets = jax.ShapeDtypeStruct((n, 6), jnp.float32, sharding=one_chip)
    mats = jax.ShapeDtypeStruct((n, 6, 11), jnp.float32, sharding=one_chip)
    proxy_search._pgd_solver(400).lower(targets, mats).compile()


def _ssd(one_chip):
    from repro.kernels.ssd.ops import ssd_diag_block
    # mamba2-2.7b: chunk q=256, state n=128, head dim p=64, one r-slab of 8
    b, c, q, g, r, p, n = 1, 2, 256, 1, 8, 64, 128
    shapes = [(b, c, q, g * r, p), (b, c, q, g * r), (b, c, q, g * r),
              (b, c, q, g, n), (b, c, q, g, n)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return lambda *a: ssd_diag_block(*a, r), args


def _flash(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention_fwd
    b, s, h, g, d = 1, 1024, 8, 2, 128
    args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
            for shape in [(b, s, h, d), (b, s, g, d), (b, s, g, d)]]
    return lambda q, k, v: flash_attention_fwd(q, k, v, cq=128, ck=128), args


def _mxu(one_chip):
    from repro.kernels.proxy_blocks.ops import mxu_block
    args = [jax.ShapeDtypeStruct((128, 128), jnp.bfloat16, sharding=one_chip)
            for _ in range(2)]
    return lambda a, b: mxu_block(a, b, 16), args


def _stream(one_chip):
    from repro.core import blocks
    from repro.kernels.proxy_blocks.ops import stream_block
    args = [jax.ShapeDtypeStruct((blocks._VEC,), jnp.float32,
                                 sharding=one_chip)]
    return lambda v: stream_block(v, 16), args


@pytest.mark.parametrize("kernel", [_ssd, _flash, _mxu, _stream],
                         ids=["ssd", "flash", "mxu_block", "stream_block"])
def test_pallas_kernel_compiles_for_tpu(one_chip, kernel):
    fn, args = kernel(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
