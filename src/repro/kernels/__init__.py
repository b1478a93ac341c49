"""Pallas TPU kernels for the paper's vectorized BFS hot loops.

`VMEM_BYTES` is the one scoped-VMEM limit: every kernel passes it to
Mosaic through `compiler_params`, and the working-set predicates in
`ops` test against the same number, so a kernel the predicates admit
is never refused by the compiler for its VMEM use (and vice versa).

`TPU_REFUSALS` records, per pipeline, the Pallas kernels its steps
launch and what the TPU compiler answers for each
(tests/test_tpu_compile.py compiles each one for a described v5e and
checks the record still holds).  A request for such a pipeline on a
TPU backend raises `errors.KernelRefusedError` quoting this table.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

#: the scoped-VMEM limit handed to Mosaic — its own default (16 MiB),
#: not the chip's VMEM (v5e has 128 MiB per core).  Raising it admits
#: larger working sets; the kernels that compile for a v5e today
#: (restoration, popcount) use about a MiB of it.
VMEM_BYTES = 16 * 1024 * 1024

_BLOCK_SHAPE = ("The Pallas TPU lowering currently requires that the "
                "last two dimensions of your block shape are divisible "
                "by 8 and 128 respectively")
_GATHER = "Only 2D gather is supported"
_CUMSUM = ("Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: cumsum")
_SCATTER_ADD = ("Unimplemented primitive in Pallas TPU lowering for "
                "KernelType.TC: scatter-add")

#: pipeline (``"semiring"``: the algorithm portfolio's relax steps) ->
#: each Pallas kernel its steps launch on the csr and sell layouts ->
#: the start of the TPU compiler's refusal (jax 0.9.0 / libtpu 0.0.34,
#: v5e, Graph500 scale-20 widths).  The (1, W) row blocks of the
#: batched kernels are refused before their 1-D vector gathers are
#: reached.  A pipeline absent here launches no refused kernel.
TPU_REFUSALS = {
    "fused_gather": {"bfs_frontier_compact_batched": _CUMSUM,
                     "bfs_gather_expand_batched": _BLOCK_SHAPE,
                     "bfs_sell_expand_batched": _BLOCK_SHAPE},
    "materialized": {"bfs_frontier_compact_batched": _CUMSUM,
                     "bfs_frontier_expand_batched": _BLOCK_SHAPE,
                     "bfs_sell_expand_batched": _BLOCK_SHAPE},
    "megakernel": {"bfs_layer_fused_batched": _BLOCK_SHAPE,
                   "bfs_sell_layer_fused_batched": _BLOCK_SHAPE},
    "persistent": {"bfs_traversal_fused": _SCATTER_ADD,
                   "bfs_sell_traversal_fused": _GATHER},
    "semiring": {"bfs_frontier_compact_batched": _CUMSUM,
                 "bfs_gather_relax_batched": _BLOCK_SHAPE,
                 "bfs_sell_relax_batched": _BLOCK_SHAPE},
}


def interpret_mode() -> bool:
    """True where JAX has no TPU backend: every Pallas kernel then runs
    in the interpreter (the CPU test configuration).  The one place
    the program decides this."""
    return jax.default_backend() != "tpu"


def compiler_params(**kwargs) -> pltpu.CompilerParams:
    """`pltpu.CompilerParams` with the shared `VMEM_BYTES` limit."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES, **kwargs)
