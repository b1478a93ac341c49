import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import — jax locks the device
count at first init, and the production meshes need 512 host devices.
This file (and only this file) may be the process entry point for the
dry-run; smoke tests and benches see the real 1-CPU device list.

Per cell:
    with mesh:
        lowered = jax.jit(step, in_shardings=..., out_shardings=...)\
            .lower(**input_specs(arch))
        compiled = lowered.compile()
        memory_analysis / cost_analysis / collective parse  -> JSON

Usage:
    python -m repro.launch.dryrun                      # all cells, both meshes
    python -m repro.launch.dryrun --arch qwen3 --shape train_4k --mesh multi
    python -m repro.launch.dryrun --bfs                # distributed BFS cells
    python -m repro.launch.dryrun --list
Artifacts: results/dryrun/<arch>__<shape>__<mesh>.json (cached by key).
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.bfs_graph500 import GRAPHS
from repro.launch import inputs
from repro.launch.mesh import (batch_specs, data_axes,
                               make_production_mesh, named_shardings,
                               param_specs, rules_for)
from repro.models import lm
from repro.models.config import param_count
from repro.models.sharding import logical_axis_rules
from repro.roofline.analysis import (model_flops_for, parse_collectives,
                                     Roofline)
from repro.train.train_step import (make_prefill_step, make_serve_step,
                                    make_train_step, TrainConfig)

RESULTS = Path(os.environ.get("DRYRUN_RESULTS", "results/dryrun"))


# ---------------------------------------------------------------------------
# Sharding policies for decode state pytrees
# ---------------------------------------------------------------------------

def decode_state_shardings(mesh, states, shape):
    """KV caches (L,B,S,K,hd): B over data when divisible, cache length
    S over model (sequence-parallel decode).  SSM/WKV states: B over
    data, last dim over model when divisible."""
    da = data_axes(mesh)
    d_batch = int(np.prod([mesh.shape[a] for a in da]))
    d_model = mesh.shape["model"]

    def one(leaf):
        dims = [None] * leaf.ndim
        if leaf.ndim >= 2 and leaf.shape[1] % d_batch == 0:
            dims[1] = da                       # batch dim (after L)
        if leaf.ndim >= 3 and leaf.shape[2] % d_model == 0 \
                and leaf.shape[2] >= 16:
            dims[2] = "model"                  # cache length / heads
        elif leaf.ndim >= 4 and leaf.shape[-1] % d_model == 0:
            dims[-1] = "model"
        if dims[1] is None and leaf.ndim >= 3 \
                and leaf.shape[2] % (d_batch * d_model) == 0 \
                and leaf.shape[2] >= 4096:
            dims[2] = (*da, "model")           # batch=1 long context
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(one, states)


def vector_sharding(mesh, n):
    da = data_axes(mesh)
    d_batch = int(np.prod([mesh.shape[a] for a in da]))
    return NamedSharding(mesh, P(da if n % d_batch == 0 else None))


# ---------------------------------------------------------------------------
# Cell runners
# ---------------------------------------------------------------------------

def _mesh(mesh_name: str):
    return make_production_mesh(multi_pod=(mesh_name == "multi"))


def lower_cell(arch: str, shape_name: str, mesh_name: str,
               extra_cfg=None):
    """Build + lower + compile one cell. Returns the result dict."""
    cfg = registry.get(arch)
    if extra_cfg:
        cfg = cfg.with_(**extra_cfg)
    shape = registry.SHAPES[shape_name]
    # 400B-class: bf16 master weights (fp32 master can't fit 16 GB HBM
    # at these param/chip ratios; standard production trade-off)
    from repro.models.config import param_count as _pc
    mesh_chips = 512 if mesh_name == "multi" else 256
    if shape.kind == "train" and _pc(cfg) * 4 > mesh_chips * 4e9:
        cfg = cfg.with_(param_dtype="bfloat16")
    status = registry.cell_status(cfg, shape)
    if status != "run":
        return {"arch": cfg.name, "shape": shape_name, "mesh": mesh_name,
                "status": status}

    mesh = _mesh(mesh_name)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rules = rules_for(mesh)
    params_shape = inputs.params_specs(cfg)
    d_batch = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    p_specs = param_specs(params_shape,
                          model_divisor=mesh.shape["model"],
                          data_divisor=d_batch)
    p_shardings = named_shardings(mesh, p_specs)
    t0 = time.time()

    with mesh:
        with logical_axis_rules(rules):
            if shape.kind == "train":
                # 400B-class cells need int8 optimizer state to fit a
                # single 256-chip pod (fp32 Adam alone exceeds HBM)
                from repro.models.config import param_count
                use_8bit = param_count(cfg) * 16 > n_chips * 12e9
                tcfg = TrainConfig(opt_8bit=use_8bit)
                tstep = make_train_step(cfg, tcfg)
                batch = inputs.train_batch_specs(cfg, shape)
                import repro.train.optimizer as opt
                opt_shape = jax.eval_shape(
                    opt.init_8bit if use_8bit else opt.init,
                    params_shape)
                o_shardings = jax.tree.map(
                    lambda _: NamedSharding(mesh, P()), opt_shape)
                # ZeRO-1: shard m/v over data (see optimizer.py)
                from repro.train.optimizer import zero1_specs
                z_specs = zero1_specs(p_specs, params_shape, d_batch)
                if use_8bit:
                    # {"q","s"} leaves: q shares the param's spec; the
                    # per-block scale keeps the last-dim axis only when
                    # the block count still divides it, else drops it
                    rules = rules_for(mesh)

                    def _axis_size(logical):
                        phys = rules.get(logical, logical)
                        names = (phys,) if isinstance(phys, str) \
                            else tuple(phys or ())
                        return int(np.prod([mesh.shape[a]
                                            for a in names]))

                    def qs_spec(spec, leaf):
                        dims = list(spec) + [None] * (
                            leaf.ndim - len(spec))
                        q_sp = P(*dims)
                        if not leaf.ndim:
                            return {"q": q_sp, "s": P()}
                        n = leaf.shape[-1]
                        s_dims = list(dims[:-1])
                        last = dims[-1]
                        if n % 128 == 0 and last is not None:
                            ax = ([last] if isinstance(last, str)
                                  else list(last))
                            div = int(np.prod([_axis_size(a)
                                               for a in ax]))
                            s_dims.append(
                                last if (n // 128) % div == 0
                                else None)
                        elif n % 128 == 0:
                            s_dims.append(None)
                        return {"q": q_sp, "s": P(*s_dims)}

                    m_specs = jax.tree.map(qs_spec, z_specs,
                                           params_shape,
                                           is_leaf=lambda x:
                                           isinstance(x, P))
                else:
                    m_specs = z_specs
                o_shardings = {
                    "m": named_shardings(mesh, m_specs),
                    "v": named_shardings(mesh, z_specs),
                    "step": NamedSharding(mesh, P()),
                }
                lowered = jax.jit(
                    tstep,
                    in_shardings=(p_shardings, o_shardings,
                                  batch_specs(mesh, batch)),
                    # params/opt-state update in place: halves peak HBM
                    donate_argnums=(0, 1),
                ).lower(params_shape, opt_shape, batch)
                n_tokens = shape.global_batch * shape.seq_len
            elif shape.kind == "prefill":
                pstep = make_prefill_step(cfg)
                batch = inputs.train_batch_specs(cfg, shape)
                batch.pop("labels")
                lowered = jax.jit(
                    pstep,
                    in_shardings=(p_shardings,
                                  batch_specs(mesh, batch)),
                ).lower(params_shape, batch)
                n_tokens = shape.global_batch * shape.seq_len
            else:  # decode
                sstep = make_serve_step(cfg)
                d = inputs.decode_input_specs(cfg, shape)
                st_shardings = decode_state_shardings(mesh, d["states"],
                                                      shape)
                args = [params_shape, d["states"], d["tokens"],
                        d["position"]]
                in_sh = [p_shardings, st_shardings,
                         vector_sharding(mesh, shape.global_batch),
                         vector_sharding(mesh, shape.global_batch)]
                if "memory" in d:
                    args.append(d["memory"])
                    in_sh.append(batch_specs(mesh, d["memory"]))
                lowered = jax.jit(
                    sstep, in_shardings=tuple(in_sh),
                    donate_argnums=(1,),   # KV cache updates in place
                ).lower(*args)
                n_tokens = shape.global_batch  # one token per sequence

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    # trip-count-aware analysis (cost_analysis counts scan bodies once)
    from repro.roofline.hlo_analyze import analyze
    acost = analyze(hlo, default_group=n_chips)

    n_embed = cfg.vocab_size * cfg.d_model \
        * (1 if cfg.tie_embeddings else 2)
    mf = model_flops_for(
        "train" if shape.kind == "train" else "serve",
        param_count(cfg, active_only=True), n_tokens, n_embed)
    roof = Roofline(
        flops=acost.flops,
        bytes_accessed=acost.bytes,
        wire_bytes=acost.wire_bytes,
        n_chips=n_chips,
        model_flops=mf,
    )
    result = {
        "arch": cfg.name, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "n_chips": n_chips,
        "opt_state": ("int8-blockwise"
                      if (shape.kind == "train"
                          and param_count(cfg) * 16 > n_chips * 12e9)
                      else "fp32"),
        "param_dtype": cfg.param_dtype,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                                  getattr(mem, "temp_size_in_bytes", 0)),
        },
        "collectives": {"ops": acost.coll_ops,
                        "payload_bytes": acost.coll_payload,
                        "wire_bytes": acost.wire_bytes},
        "xla_cost_analysis": {
            "flops_no_trips": float(cost.get("flops", 0.0)),
            "bytes_no_trips": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": roof.to_dict(),
        "hlo_bytes": len(hlo),
    }
    return result


def lower_bfs_cell(graph_name: str, mesh_name: str,
                   merge: str = "allreduce"):
    """Dry-run the paper's distributed BFS on the production mesh."""
    from repro.core.bfs_distributed import (partition_sizes,
                                            sharded_program)
    g = GRAPHS[graph_name]
    mesh = _mesh(mesh_name)
    axes = tuple(mesh.axis_names)
    n_chips = int(np.prod(list(mesh.shape.values())))
    v_loc, e_loc = partition_sizes(g.n_vertices, g.n_edges_directed,
                                   n_chips)
    # single_layer=True: the roofline terms below are EXACT per-layer
    # costs (the full while-loop's trip count is data-dependent; the
    # compile-success proof still uses the full program)
    shard = sharded_program(mesh, axes, v_loc, g.n_vertices, 64, merge,
                            single_layer=True)
    shard_full = sharded_program(mesh, axes, v_loc, g.n_vertices, 64,
                                 merge)
    # rows and their owner ids: one (n_chips, e_loc) shape
    slots_s = jax.ShapeDtypeStruct((n_chips, e_loc), jnp.int32)
    root_s = jax.ShapeDtypeStruct((), jnp.int32)
    t0 = time.time()
    with mesh:
        # full program must compile (the dry-run proof) ...
        jax.jit(shard_full).lower(slots_s, slots_s, root_s).compile()
        # ... the single-layer probe provides the roofline terms
        lowered = jax.jit(shard).lower(slots_s, slots_s, root_s)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    from repro.roofline.hlo_analyze import analyze
    acost = analyze(compiled.as_text(), default_group=n_chips)
    # single-layer probe => terms below are exact PER-LAYER costs
    roof = Roofline(
        flops=acost.flops,
        bytes_accessed=acost.bytes,
        wire_bytes=acost.wire_bytes, n_chips=n_chips,
        model_flops=0.0)
    return {
        "arch": f"bfs-{graph_name}", "shape": "graph500",
        "mesh": mesh_name, "status": "ok", "n_chips": n_chips,
        "merge": merge,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        },
        "collectives": {"ops": acost.coll_ops,
                        "payload_bytes": acost.coll_payload,
                        "wire_bytes": acost.wire_bytes},
        "roofline": roof.to_dict(),
        "bytes_per_chip_edges": 4 * e_loc,
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def cell_path(arch, shape, mesh) -> Path:
    return RESULTS / f"{arch}__{shape}__{mesh}.json"


def run_and_save(arch, shape, mesh_name, force=False):
    cfgname = registry.get(arch).name
    path = cell_path(cfgname, shape, mesh_name)
    if path.exists() and not force:
        print(f"[cached] {path.name}")
        return json.loads(path.read_text())
    path.parent.mkdir(parents=True, exist_ok=True)
    print(f"[dryrun] {cfgname} x {shape} x {mesh_name} ...", flush=True)
    try:
        res = lower_cell(arch, shape, mesh_name)
    except Exception as e:  # a failing cell is a bug: record it loudly
        res = {"arch": cfgname, "shape": shape, "mesh": mesh_name,
               "status": f"FAILED: {type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    path.write_text(json.dumps(res, indent=1))
    print(f"  -> {res['status']}"
          + (f" compile={res.get('compile_s')}s"
             f" bottleneck={res.get('roofline', {}).get('bottleneck')}"
             if res["status"] == "ok" else ""), flush=True)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=["single", "multi"])
    ap.add_argument("--bfs", action="store_true")
    ap.add_argument("--bfs-graph", default="rmat-24")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for cfg, shape, status in registry.all_cells():
            print(f"{cfg.name:28s} {shape.name:12s} {status}")
        return

    if args.bfs:
        for mesh_name in ([args.mesh] if args.mesh
                          else ["single", "multi"]):
            path = cell_path(f"bfs-{args.bfs_graph}", "graph500",
                             mesh_name)
            if path.exists() and not args.force:
                print(f"[cached] {path.name}")
                continue
            path.parent.mkdir(parents=True, exist_ok=True)
            print(f"[dryrun] BFS {args.bfs_graph} x {mesh_name}",
                  flush=True)
            try:
                res = lower_bfs_cell(args.bfs_graph, mesh_name)
            except Exception as e:
                res = {"arch": f"bfs-{args.bfs_graph}",
                       "shape": "graph500", "mesh": mesh_name,
                       "status": f"FAILED: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            path.write_text(json.dumps(res, indent=1))
            print(f"  -> {res['status']}", flush=True)
        return

    archs = [args.arch] if args.arch else sorted(registry.ARCHS)
    shapes = [args.shape] if args.shape else list(registry.SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                run_and_save(arch, shape, mesh_name, force=args.force)


if __name__ == "__main__":
    main()
