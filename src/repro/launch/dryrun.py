import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

# Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.
#
# For each cell this builds the real step function (train_step / prefill /
# serve_step), the full sharding trees from the rule engine, lowers with
# ShapeDtypeStruct inputs (no allocation), compiles under the production
# mesh, and records memory/cost/collective analysis → experiments/dryrun/*.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
#         --shape train_4k --mesh single
#     PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

import argparse
import re
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_config, skip_reason
from repro.launch import roofline as rl
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   opt_shardings, param_shardings)
from repro.models import decode_step, init_caches, init_params, prefill
from repro.optim import adamw
from repro.train.step import init_train_state, make_train_step

# The chip the dry-run models: its peaks set the roofline terms, whatever
# (virtual CPU) devices the compile runs on.
TARGET_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def batch_struct(cfg, batch: int, seq: int):
    """ShapeDtypeStruct stand-ins for every model input (shardable,
    weak-type-correct, no device allocation)."""
    i32 = jnp.int32
    f32 = jnp.float32
    if cfg.frontend == "audio_frames":
        return {
            "frame_embeds": jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                                 f32),
            "labels": jax.ShapeDtypeStruct((batch, seq, cfg.n_codebooks),
                                           i32),
            "mask": jax.ShapeDtypeStruct((batch, seq), f32),
        }
    if cfg.frontend == "vision_patches":
        text = seq - cfg.n_patches
        return {
            "tokens": jax.ShapeDtypeStruct((batch, text), i32),
            "patch_embeds": jax.ShapeDtypeStruct(
                (batch, cfg.n_patches, cfg.d_model), f32),
            "labels": jax.ShapeDtypeStruct((batch, text), i32),
            "mask": jax.ShapeDtypeStruct((batch, text), f32),
        }
    return {"tokens": jax.ShapeDtypeStruct((batch, seq), i32),
            "labels": jax.ShapeDtypeStruct((batch, seq), i32),
            "mask": jax.ShapeDtypeStruct((batch, seq), f32)}


def decode_token_struct(cfg, batch: int):
    if cfg.frontend == "audio_frames":
        return {"frame_embeds": jax.ShapeDtypeStruct((batch, 1, cfg.d_model),
                                                     jnp.float32)}
    return {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}


def _replicated_bytes(tree) -> float:
    return sum(np.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def _sharded_bytes_per_device(tree, shardings, mesh) -> float:
    """Analytic per-device bytes given sharding specs."""
    total = 0.0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec"))):
        n_shards = 1
        for ax in sh.spec:
            if ax is None:
                continue
            axes = ax if isinstance(ax, (tuple, list)) else (ax,)
            for a in axes:
                n_shards *= mesh.shape[a]
        total += np.prod(leaf.shape) * leaf.dtype.itemsize / n_shards
    return total


def build_cell(cfg, shape, mesh):
    """Returns (jitted fn, arg structs, ShardingReport, byte accounting)."""
    key = jax.random.PRNGKey(0)
    params_s = _sds(jax.eval_shape(lambda: init_params(cfg, key)))
    p_shard, report = param_shardings(cfg, mesh, params_s)
    bytes_acct = {"params_per_device":
                  _sharded_bytes_per_device(params_s, p_shard, mesh)}

    if shape.kind == "train":
        train_s, frozen_s, opt_s = jax.eval_shape(
            lambda p: init_train_state(cfg, p), params_s)
        train_s, frozen_s, opt_s = map(_sds, (train_s, frozen_s, opt_s))
        t_shard, _ = param_shardings(cfg, mesh, train_s)
        f_shard, _ = param_shardings(cfg, mesh, frozen_s)
        o_shard = opt_shardings(mesh, opt_s, t_shard)
        batch_s = batch_struct(cfg, shape.global_batch, shape.seq_len)
        b_shard = batch_shardings(mesh, batch_s, shape.global_batch)
        bytes_acct["opt_per_device"] = _sharded_bytes_per_device(
            opt_s, o_shard, mesh)
        # min traffic: params fwd+bwd reads + grad write + moments r/w
        bytes_acct["ideal_step_bytes"] = (
            3 * bytes_acct["params_per_device"]
            + 2 * bytes_acct["opt_per_device"])
        step = make_train_step(cfg, adamw.AdamWConfig(), lambda s: 1.0)
        fn = jax.jit(step,
                     in_shardings=(t_shard, f_shard, o_shard, b_shard),
                     out_shardings=(t_shard, o_shard, None),
                     donate_argnums=(0, 2))
        return fn, (train_s, frozen_s, opt_s, batch_s), report, bytes_acct

    caches_s = _sds(jax.eval_shape(
        lambda _: init_caches(cfg, shape.global_batch, shape.seq_len),
        jnp.zeros(())))
    c_shard = cache_shardings(mesh, caches_s, shape.global_batch)
    bytes_acct["cache_per_device"] = _sharded_bytes_per_device(
        caches_s, c_shard, mesh)

    if shape.kind == "prefill":
        batch_s = batch_struct(cfg, shape.global_batch, shape.seq_len)
        batch_s.pop("labels", None)
        batch_s.pop("mask", None)
        b_shard = batch_shardings(mesh, batch_s, shape.global_batch)
        bytes_acct["ideal_step_bytes"] = (
            bytes_acct["params_per_device"] + bytes_acct["cache_per_device"])
        fn = jax.jit(
            lambda p, b: prefill(cfg, p, b, max_cache_len=shape.seq_len),
            in_shardings=(p_shard, b_shard))
        return fn, (params_s, batch_s), report, bytes_acct

    # decode: one new token against a seq_len-deep cache
    tok_s = decode_token_struct(cfg, shape.global_batch)
    t_shard_tok = batch_shardings(mesh, tok_s, shape.global_batch)
    pos_s = jax.ShapeDtypeStruct((), jnp.int32)
    bytes_acct["ideal_step_bytes"] = (
        bytes_acct["params_per_device"] + bytes_acct["cache_per_device"])
    fn = jax.jit(
        lambda p, tok, pos, c: decode_step(cfg, p, tok, pos, c),
        in_shardings=(p_shard, t_shard_tok, None, c_shard),
        out_shardings=(None, c_shard),
        donate_argnums=(3,))
    return fn, (params_s, tok_s, pos_s, caches_s), report, bytes_acct


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None, tag: str = "",
             out_dir: str = OUT_DIR) -> dict:
    shape = SHAPES[shape_name]
    reason = skip_reason(arch, shape_name)
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch}__{shape_name}__{mesh_kind}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, stem + ".json")
    if reason:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "reason": reason}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    flat = {k: v for k, v in (overrides or {}).items() if "." not in k}
    nested = {k: v for k, v in (overrides or {}).items() if "." in k}
    cfg = get_config(arch, **flat)
    for k, v in nested.items():           # e.g. --override moe.impl=gather
        head, leaf = k.split(".", 1)
        sub = getattr(cfg, head)
        cfg = dataclasses.replace(
            cfg, **{head: dataclasses.replace(sub, **{leaf: v})})
    custom = re.match(r"^(\d+)x(\d+)$", mesh_kind)
    if custom:                            # e.g. --mesh 64x4 (layout study)
        mesh = jax.make_mesh((int(custom.group(1)), int(custom.group(2))),
                             ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
           "devices": n_dev, "overrides": {k: str(v) for k, v in
                                           (overrides or {}).items()}}
    t0 = time.time()
    try:
        with mesh:
            fn, args, report, bytes_acct = build_cell(cfg, shape, mesh)
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            hlo = compiled.as_text()
            model_flops = rl.analytic_model_flops(
                cfg, shape.kind, shape.seq_len, shape.global_batch)
            roof, coll = rl.from_compiled(compiled, n_dev, model_flops,
                                          TARGET_KIND, hlo_text=hlo)
            try:
                mem = compiled.memory_analysis()
                mem_rec = {k: int(getattr(mem, k)) for k in
                           ("argument_size_in_bytes",
                            "output_size_in_bytes",
                            "temp_size_in_bytes",
                            "generated_code_size_in_bytes")
                           if hasattr(mem, k)}
            except Exception as e:                       # noqa: BLE001
                mem_rec = {"error": str(e)}
            rec.update({
                "status": "ok",
                "lower_s": round(t1 - t0, 2),
                "compile_s": round(t2 - t1, 2),
                "flops_per_device": roof.flops,
                "hbm_bytes_per_device": roof.hbm_bytes,
                "link_bytes_per_device": roof.link_bytes,
                "collectives": {k: v for k, v in coll.items()},
                "model_flops": model_flops,
                "t_compute": roof.t_compute,
                "t_memory": roof.t_memory,
                "t_collective": roof.t_collective,
                "bottleneck": roof.bottleneck,
                "roofline_fraction": roof.roofline_fraction,
                "flops_utilization": roof.flops_utilization,
                "bytes_accounting": {k: float(v)
                                     for k, v in bytes_acct.items()},
                "memory_fraction": float(
                    bytes_acct["ideal_step_bytes"]
                    / max(roof.hbm_bytes, 1.0)),
                # score-carrying fraction: ideal time (compute OR unavoidable
                # memory, whichever binds) over the achieved bound
                "roofline_fraction_cell": float(
                    max(model_flops / n_dev / roof.peaks.flops,
                        bytes_acct["ideal_step_bytes"] / roof.peaks.hbm_bw)
                    / max(roof.bound_time, 1e-30)),
                "memory_analysis": mem_rec,
                "sharding_report": {
                    "matched": report.matched,
                    "fallback_replicated": report.fallback_replicated[:20],
                    "degraded_dims": [list(map(str, d))
                                      for d in report.degraded_dims[:20]],
                },
            })
    except Exception as e:                                # noqa: BLE001
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-3000:]})
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _parse_overrides(pairs):
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        out[k] = v
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    help="single | multi | both | <data>x<model> (e.g. 64x4)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--override", action="append",
                    help="cfg field override, e.g. --override remat=false")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    overrides = _parse_overrides(args.override)

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                stem = f"{arch}__{shape}__{mesh_kind}" \
                    + (f"__{args.tag}" if args.tag else "")
                path = os.path.join(args.out, stem + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip-existing] {stem}")
                    continue
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_kind, overrides, args.tag,
                               args.out)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"bottleneck={rec['bottleneck']} "
                             f"frac={rec['roofline_fraction_cell']:.3f} "
                             f"compile={rec['compile_s']}s")
                elif status == "error":
                    extra = rec["error"][:160]
                else:
                    extra = rec["reason"][:60]
                print(f"[{status:7s}] {stem} ({time.time()-t0:.0f}s) {extra}",
                      flush=True)


if __name__ == "__main__":
    main()
