import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh, record memory/cost/roofline — NO device allocation
(everything flows through ShapeDtypeStruct).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, all_archs, get_config
from ..roofline import analysis as RA
from ..sharding.env import auto_axes, get_env, use_mesh
from ..serve import serve_step
from ..train.optimizer import AdamWConfig, OptState
from ..train.train_step import train_step
from . import mesh as M
from .specs import input_specs


def _is_spec_leaf(x) -> bool:
    """A spec leaf is a tuple of (None | logical-name | tuple of names);
    a tuple of specs (e.g. a KV-cache pair) is NOT a leaf."""
    if not isinstance(x, tuple):
        return False
    return all(e is None or isinstance(e, str)
               or (isinstance(e, tuple) and e
                   and all(isinstance(a, str) for a in e))
               for e in x)


def _resolve_tree(env, spec_tree):
    """Logical spec tree -> NamedSharding tree."""
    from ..sharding.env import _resolve

    def conv(s):
        phys = [_resolve(env, part) for part in s]
        return NamedSharding(env.mesh, P(*phys))

    return jax.tree.map(conv, spec_tree, is_leaf=_is_spec_leaf)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             perf: bool = False) -> dict:
    from ..models.perf import BASELINE, TUNED, set_perf
    set_perf(TUNED if perf else BASELINE)
    mesh = auto_axes(M.make_production_mesh(multi_pod=multi_pod))
    n_chips = mesh.size
    rec = {"arch": arch, "shape": shape_name, "perf": perf,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips}
    t0 = time.perf_counter()
    with use_mesh(mesh) as env:
        spec = input_specs(arch, shape_name)
        cfg, shape = spec["cfg"], spec["shape"]
        rec["params"] = cfg.param_count()
        rec["active_params"] = cfg.active_param_count()
        if spec["skip"]:
            rec["status"] = "skipped"
            rec["reason"] = spec["skip"]
            return rec

        p_structs, p_specs = spec["params"]
        p_shard = _resolve_tree(env, p_specs)

        if shape.kind == "train":
            b_structs, b_specs = spec["batch"]
            o_structs, o_specs = spec["opt"]
            b_shard = _resolve_tree(env, b_specs)
            o_shard = OptState(NamedSharding(mesh, P()),
                               _resolve_tree(env, o_specs.m),
                               _resolve_tree(env, o_specs.v))
            ocfg = AdamWConfig()
            fn = lambda p, o, b: train_step(cfg, ocfg, p, o, b)
            jfn = jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard),
                          out_shardings=(p_shard, o_shard, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(p_structs, o_structs, b_structs)
        elif shape.kind == "prefill":
            b_structs, b_specs = spec["batch"]
            b_shard = _resolve_tree(env, b_specs)
            fn = partial(serve_step.prefill, cfg)
            jfn = jax.jit(lambda p, b: fn(p, **b),
                          in_shardings=(p_shard, b_shard))
            lowered = jfn.lower(p_structs, b_structs)
        else:  # decode
            t_struct, t_spec = spec["token"]
            c_structs, c_specs = spec["caches"]
            t_shard = _resolve_tree(env, {"t": t_spec})["t"]
            c_shard = _resolve_tree(env, c_specs)
            args = [p_structs, t_struct, c_structs,
                    jax.ShapeDtypeStruct((), jnp.int32)]
            shards = [p_shard, t_shard, c_shard, NamedSharding(mesh, P())]
            if cfg.family == "encdec":
                x_structs, x_specs = spec["cross"]
                fn = lambda p, t, c, n, x: serve_step.decode(
                    cfg, p, t, c, n, cross_kvs=x)
                args.append(x_structs)
                shards.append(_resolve_tree(env, x_specs))
            else:
                fn = lambda p, t, c, n: serve_step.decode(cfg, p, t, c, n)
            jfn = jax.jit(fn, in_shardings=tuple(shards),
                          out_shardings=(None, c_shard),
                          donate_argnums=(2,))   # in-place cache update
            lowered = jfn.lower(*args)

        rec["lower_s"] = round(time.perf_counter() - t0, 1)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 1)

        mem = compiled.memory_analysis()
        try:
            rec["memory_analysis"] = {
                k: int(getattr(mem, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
        except Exception:
            rec["memory_analysis"] = {"repr": repr(mem)}
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # older jaxlib returns [dict]
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        roof = RA.analyze(hlo, cost, cfg, shape, n_chips)
        rec["roofline"] = roof.to_json()
        rec["hlo_bytes"] = len(hlo)
        rec["status"] = "ok"
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: "
              f"compile {rec['compile_s']}s, dominant={roof.dominant}, "
              f"compute={roof.compute_s:.4f}s mem={roof.memory_s:.4f}s "
              f"coll={roof.collective_s:.4f}s useful={roof.useful_ratio:.2f}",
              flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--perf", action="store_true",
                    help="use the TUNED perf profile (§Perf hillclimb)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = all_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
        out_path = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_path):
            with open(out_path) as f:
                if json.load(f).get("status") in ("ok", "skipped"):
                    print(f"[dryrun] {tag}: cached, skipping", flush=True)
                    continue
        try:
            rec = run_cell(arch, shape, mp, perf=args.perf)
        except Exception as e:
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": str(e),
                   "traceback": traceback.format_exc()}
            print(f"[dryrun] {tag}: ERROR {e}", flush=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
