"""Deviceless TPU v5e compiles of what the chip path runs.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (`jax.experimental.topologies`).  The widths
are those of ``chip_smoke.py``: Graph500 rmat-20 (|V| = 2^20,
edgefactor 16, both directions) and a batch of 8 roots.  Nothing runs,
so these say nothing about results or times — only that the chip's
compiler accepts the program and that it fits the chip's HBM.

Three groups:

* the Pallas kernels the chip path launches compile (and are not
  interpreted: the lowered program holds a ``tpu_custom_call``);
* the steps of ``pipeline="xla"`` — the one the formats declare in
  ``tpu_pipelines`` — compile whole: the serve tick and the fused
  whole-search program, each step with two gathers and one scatter
  over the slot stream in the compiled program; so does the mesh
  program's per-chip step over the four chips of a v5e:2x2 host;
* every kernel refusal recorded in `repro.kernels.TPU_REFUSALS` is
  still what the compiler answers, so the declaration cannot go stale.

The topology is described inside a fixture (never at import) and the
tests skip where it cannot be described.  They trace with
``jax.default_backend`` reporting "tpu", as the program does on a chip.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.plan import _Executable
from repro.api.spec import TraversalSpec
from repro.core import engine
from repro.core.csr import padded_vertex_count
from repro.formats.csr_format import CsrFormat
from repro.formats.sell import SellFormat
from repro.kernels import (TPU_REFUSALS, compact, ops,
                           frontier_expand as fe, gather_expand as ge,
                           layer_fused as lf, sell_expand as se,
                           traversal_fused as tf)

V = 1 << 20
V_PAD = padded_vertex_count(V)
W = V_PAD // 32
B = 8
E = V * 16 * 2
TILE = 4096
N_BLOCKS = E // TILE
N_SLABS = 45_000            # SELL slabs of rmat-20: ~E/1024 + V/128
HBM_BYTES = 15.75 * 2**30   # what the v5e compiler reports it may use
U32 = jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # noqa: BLE001 — any failure means "no"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a deviceless compile writes cache entries it cannot read back
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    return lowered, lowered.compile()


# ---------------------------------------------------------------------------
# Kernels the chip path launches
# ---------------------------------------------------------------------------

KERNELS = {
    "popcount": lambda s: (ops.popcount, s((W,), U32)),
    "restoration": lambda s: (
        lambda p: ops.restore(p, n_vertices=V), s((V_PAD,))),
    "restoration_batched": lambda s: (
        lambda p: ops.restore(p, n_vertices=V), s((B, V_PAD))),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_chip_path_kernel_compiles(sds, on_tpu, name):
    fn, arg = KERNELS[name](sds)
    lowered, _ = _compile(fn, arg)
    assert "tpu_custom_call" in lowered.as_text()


# ---------------------------------------------------------------------------
# The xla pipeline's steps, whole
# ---------------------------------------------------------------------------

def _spec(policy):
    return TraversalSpec(policy=policy, algorithm="simd", pipeline="xla",
                         packed=True, tile=TILE, prefetch_depth=0,
                         max_layers=64, merge="packed")


def _state(s):
    return s((B, W), U32), s((B, W), U32), s((B, V_PAD))


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"


def _slot_stream_ops(hlo: str, n_slots: int):
    """(gathers, scatters) over the slot stream inside the loops of a
    compiled module: a gather that yields one value per slot, a
    scatter indexed by one per slot."""
    size = {m[1]: math.prod(int(d) for d in m[2].split(",") if d)
            for m in re.finditer(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo)}
    ops = {"gather": 0, "scatter": 0}
    for line in hlo.splitlines():
        m = re.search(r"%([\w.-]+) = \S+ (gather|scatter)\(%[\w.-]+, "
                      r"%([\w.-]+)", line)
        if m and "/while/body/" in line:
            n = size[m[1]] if m[2] == "gather" else size[m[3]]
            ops[m[2]] += n == n_slots
    return ops["gather"], ops["scatter"]


def test_csr_whole_search_compiles(sds, on_tpu):
    """`CompiledTraversal.run_batched`'s program: the Beamer layer loop
    over both xla steps (the policy ``auto`` picks on RMAT)."""
    fmt = CsrFormat(sds((V + 1,)), sds((E,)), V, E)
    ex = _Executable(_spec(engine.BeamerHybrid()))
    compiled = ex.run_jit.lower(fmt, sds((B,))).compile()
    _fits_hbm(compiled)
    # two steps (top-down, bottom-up), each 2 gathers + 1 scatter
    assert _slot_stream_ops(compiled.as_text(), E) == (4, 2)


def test_sell_serve_tick_compiles(sds, on_tpu):
    """`GraphEngine`'s tick (`CompiledTraversal.layer_step`) on the
    layout ``graph_format="auto"`` builds for RMAT."""
    slots = N_SLABS * se.W_QUANT * se.SLICE_C
    fmt = SellFormat(sds((N_SLABS, se.W_QUANT, se.SLICE_C)),
                     sds((N_SLABS, se.SLICE_C)), sds((V,)), V, E,
                     SellFormat.DEFAULT_SIGMA, slots)
    ex = _Executable(_spec(engine.TopDown()).replace(tile=1))
    compiled = ex.layer_jit.lower(fmt, *_state(sds)).compile()
    _fits_hbm(compiled)
    assert _slot_stream_ops(compiled.as_text(), slots) == (2, 1)


def test_mesh_search_compiles_with_owners_from_the_partition(topo,
                                                             on_tpu):
    """`plan(g, mesh)`'s per-root program (`bfs_distributed.run`) over
    the four chips of the described v5e:2x2 host, at rmat-16 (2^16
    vertices, edgefactor 16, both directions: 2^19 slots a chip).
    Over each chip's slot stream, inside the layer loop: two gathers
    (the owner's frontier word, the neighbor's visited word) and one
    scatter.  The owners come with the partition, so the loop holds
    neither their expansion (``jnp.repeat``: a marker scatter-add and a
    ``_take`` gather) nor any other slot gather."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import bfs_distributed as dist
    n_chips, v = 4, 1 << 16
    v_loc, e_loc = v // n_chips, 32 * v // n_chips
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("chips",))
    slots = jax.ShapeDtypeStruct((n_chips, e_loc), jnp.int32,
                                 sharding=NamedSharding(mesh, P("chips")))
    root = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    compiled = dist._search.lower(
        slots, slots, root, mesh=mesh, axis_names=("chips",), v_loc=v_loc,
        n_vertices=v, max_layers=64, merge="packed").compile()
    _fits_hbm(compiled)
    hlo = compiled.as_text()
    assert _slot_stream_ops(hlo, e_loc) == (2, 1)
    in_loop = [line for line in hlo.splitlines() if "/while/body/" in line]
    assert not [line for line in in_loop
                if "jit(_take)" in line or "scatter-add" in line]


# ---------------------------------------------------------------------------
# The recorded refusals
# ---------------------------------------------------------------------------

def _refused_calls(s):
    """Pallas kernel name -> (kernel call, argument shapes), compiled
    directly (no VMEM budget check in front of the compiler)."""
    v, b, w = V, B, W
    kw = dict(n_vertices=v, interpret=False)
    rows, cs = s((E,)), s((v + 1,))
    bbits, bpar = s((b, w), U32), s((b, V_PAD))
    cols = s((N_SLABS, se.W_QUANT, se.SLICE_C))
    slab_rows = s((N_SLABS, se.SLICE_C))
    return {
        "bfs_frontier_compact_batched": (
            lambda x: compact.frontier_compact_batched(
                x, size=V_PAD // b, fill=v, interpret=False), (bbits,)),
        "bfs_frontier_expand_batched": (
            lambda *a: fe.frontier_expand_batched(*a, tile=1024, **kw),
            (s((b, E // b)),) * 3 + (bbits, bbits, bbits, bpar)),
        "bfs_gather_expand_batched": (
            lambda *a: ge.gather_expand_batched(*a, tile=TILE, **kw),
            (s((b, N_BLOCKS)), s((b,)), rows, cs, bbits, bbits, bbits,
             bpar)),
        "bfs_gather_relax_batched": (
            lambda *a: ge.gather_relax_batched(*a, tile=TILE, **kw),
            (s((b, N_BLOCKS)), s((b,)), rows, cs, bbits, bpar)),
        "bfs_layer_fused_batched": (
            lambda *a: lf.layer_fused_batched(*a, tile=TILE, **kw),
            (rows, cs, bbits, bbits, bpar)),
        "bfs_traversal_fused": (
            lambda *a: tf.traversal_fused_batched(
                *a, tile=TILE, policy=engine.BeamerHybrid(), **kw),
            (rows, cs, bbits, bbits, bpar)),
        "bfs_sell_expand_batched": (
            lambda *a: se.sell_expand_batched(*a, **kw),
            (cols, slab_rows, s((b, N_SLABS)), s((b,)), bbits, bbits,
             bbits, bpar)),
        "bfs_sell_layer_fused_batched": (
            lambda *a: se.sell_layer_fused_batched(*a, **kw),
            (cols, slab_rows, bbits, bbits, bpar)),
        "bfs_sell_relax_batched": (
            lambda *a: se.sell_relax_batched(*a, **kw),
            (cols, slab_rows, s((b, N_SLABS)), s((b,)), bbits, bpar)),
        "bfs_sell_traversal_fused": (
            lambda *a: tf.sell_traversal_fused_batched(
                *a, policy=engine.BeamerHybrid(), **kw),
            (cols, slab_rows, s((v,)), bbits, bbits, bpar)),
    }


REFUSED = {k: r for kernels in TPU_REFUSALS.values()
           for k, r in kernels.items()}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_recorded_refusal_still_holds(sds, name):
    calls = _refused_calls(sds)
    assert set(calls) == set(REFUSED)
    fn, args = calls[name]
    with pytest.raises(Exception) as err:
        _compile(fn, *args)
    assert REFUSED[name] in str(err.value)
