"""Production mesh construction + sharding rules.

``make_production_mesh`` is a FUNCTION (module import never touches
jax device state).  Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips.  Generalizes to
N pods by growing the leading axis — the data-parallel axis is
(pod x data), so scaling pods scales global batch, the standard
1000+-node recipe.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.models.sharding import DEFAULT_RULES, SINGLE_POD_RULES


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def rules_for(mesh) -> dict:
    return DEFAULT_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# ---------------------------------------------------------------------------
# Parameter / batch shardings
# ---------------------------------------------------------------------------

_MODEL_DIM_BY_PATH = (
    # (path substring, candidate dims to cut over "model", priority
    #  order; indices are for the UNSTACKED leaf, negatives from the
    #  end).  First candidate divisible by the model-axis size wins;
    #  otherwise the leaf replicates (GQA head counts like 40 or kv=1
    #  fall back to the d_model / ff dim).
    ("moe/w_gate/w", (0,)), ("moe/w_up/w", (0,)),   # expert dim
    ("moe/w_down/w", (0,)),
    ("embed/emb", (0,)), ("lm_head/emb", (0,)),     # vocab dim
    ("wq/w", (1, 0)), ("wk/w", (1, 0)), ("wv/w", (1, 0)),
    ("wo/w", (0, -1)),
    ("w_gate/w", (-1,)), ("w_up/w", (-1,)), ("w_down/w", (-2,)),
    ("moe/router", ()),
    ("in_proj/w", (-1,)), ("out_proj/w", (-2,)),
    ("bc_proj/w", ()), ("dt_proj/w", (-1,)),
    ("time_mix/w_k/w", (-1,)), ("time_mix/w_v/w", (-1,)),
    ("time_mix/w_r/w", (-1,)), ("time_mix/w_g/w", (-1,)),
    ("time_mix/w_o/w", (-2,)),
    ("channel_mix/w_k/w", (-1,)), ("channel_mix/w_v/w", (-2,)),
)


# FSDP: giant parameter stacks additionally cut a SECOND dim over the
# DATA axis (fully-sharded weights, all-gathered per layer inside the
# scan by GSPMD).  Without this, the 400B-class MoE experts replicate
# 100+ GiB/chip across the data axis (observed in the first dry-run
# sweep) — with it they fit (EXPERIMENTS.md SDry-run).
_DATA_DIM_BY_PATH = (
    ("moe/w_gate/w", (-1,)), ("moe/w_up/w", (-1,)),   # expert ff dim
    ("moe/w_down/w", (-1,)),                          # expert out dim
)


def _spec_for_path(path: str, shape, stacked: bool, divisor: int,
                   data_divisor: int = 0) -> P:
    ndim = len(shape)
    spec = [None] * ndim
    for frag, dims in _MODEL_DIM_BY_PATH:
        if frag in path:
            for dim in dims:
                d = dim if dim >= 0 else ndim + dim
                if dim >= 0 and stacked:
                    d += 1        # skip the leading layer-stack axis
                if 0 <= d < ndim and shape[d] % divisor == 0 \
                        and shape[d] >= divisor:
                    spec[d] = "model"
                    break
            break
    if data_divisor > 1:
        for frag, dims in _DATA_DIM_BY_PATH:
            if frag in path:
                for dim in dims:
                    d = dim if dim >= 0 else ndim + dim
                    if dim >= 0 and stacked:
                        d += 1
                    if 0 <= d < ndim and spec[d] is None \
                            and shape[d] % data_divisor == 0 \
                            and shape[d] >= data_divisor:
                        spec[d] = "data"
                        break
                break
    return P(*spec)


def param_specs(params, model_divisor: int = 16,
                data_divisor: int = 0) -> dict:
    """PartitionSpec pytree mirroring a param pytree (path-rule based).

    Layer-stacked arrays (under 'layers'/'encoder') keep their leading
    L axis unsharded.  ``model_divisor`` is the model-axis size; dims
    that don't divide fall back through the candidates or replicate.
    ``data_divisor`` > 1 enables FSDP cuts for the paths in
    _DATA_DIM_BY_PATH (the MoE expert stacks).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat[0]:
        pstr = "/".join(getattr(k, "key", str(k)) for k in path)
        stacked = pstr.startswith(("layers/", "encoder/"))
        specs.append(_spec_for_path(pstr, leaf.shape, stacked,
                                    model_divisor, data_divisor))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def named_shardings(mesh, spec_tree):
    rules = rules_for(mesh)

    def resolve(spec: P):
        phys = tuple(rules.get(a) if isinstance(a, str) else a
                     for a in spec)
        return NamedSharding(mesh, P(*phys))

    return jax.tree.map(resolve, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(mesh, batch_tree):
    """Shard the leading (batch) dim of every batch leaf over data."""
    da = data_axes(mesh)
    return jax.tree.map(
        lambda x: NamedSharding(mesh, P(da, *[None] * (x.ndim - 1))),
        batch_tree)
