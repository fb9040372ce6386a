import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

MUST be run as its own process (``python -m repro.launch.dryrun``): the two
lines above run before any other import so the 512 placeholder devices exist
before jax locks the device count. Nothing here allocates a real tensor —
params, optimizer state, caches and batches are all ShapeDtypeStructs.

Per combo it records: compile wall-time, cost_analysis (FLOPs / bytes),
memory_analysis (per-device bytes), the collective-byte census parsed from
the compiled HLO, and the derived three-term roofline (launch/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod] [--mode pard_verify]
  python -m repro.launch.dryrun --all --both-meshes --out benchmarks/results
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh, replica_submeshes
from repro.launch.steps import (SHAPES, input_specs, make_decode_step,
                                make_prefill_step, make_train_step,
                                make_verify_step, opt_state_shapes,
                                param_shapes)
from repro.sharding.specs import data_spec, paged_cache_specs, param_specs
from repro.training.optimizer import AdamW

# long_500k policy (DESIGN.md §4): runs natively for SSM/hybrid; gemma2 runs
# the all-local windowed serving variant; pure full-attention archs skip.
LONG_OK = {"mamba2-130m": "native", "jamba-1.5-large-398b": "windowed",
           "gemma2-27b": "windowed"}
LONG_WINDOW = 4096


def _skip_reason(arch: str, shape: str) -> str | None:
    if shape == "long_500k" and arch not in LONG_OK:
        return ("pure full-attention architecture — long_500k requires "
                "sub-quadratic attention (DESIGN.md §4)")
    return None


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_one(arch: str, shape: str, *, multi_pod: bool = False,
              mode: str = "default", mesh=None,
              variant: str = "baseline") -> Dict[str, Any]:
    """``variant`` selects a §Perf hillclimb configuration:

      baseline         — paper-faithful defaults
      pard_verify      — (via mode) K+1-token PARD verification step
      kv8              — int8 KV cache (beyond-paper: halves the decode
                         memory term; real deployment adds scale tensors)
      replicated       — no model-axis weight sharding for serving (kills
                         weight all-gathers for small models where
                         collectives dominate)
      expert_parallel  — MoE experts sharded over the model axis
                         (all-to-all dispatch)
      no_remat         — training without activation checkpointing

    The serving steps lower as one data-parallel replica serves them
    (DESIGN.md §12): its share of the global batch, over its own KV pool
    (``paged_cache_specs``: KV heads over "model", the rest replicated)
    with per-row block tables in the batch, on one row of "model" devices.
    Replicas run no collective, so per-device numbers are one replica's;
    ``rec["mesh"]`` stays the whole mesh for the roofline's chip count.
    """
    cfg = get_config(arch)
    sh = SHAPES[shape]
    kind = sh["kind"]
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    window = 0
    if shape == "long_500k" and LONG_OK.get(arch) == "windowed":
        window = LONG_WINDOW

    rec: Dict[str, Any] = dict(arch=arch, shape=shape, mode=mode,
                               multi_pod=multi_pod, variant=variant,
                               mesh=list(mesh.devices.shape), window=window)
    t0 = time.perf_counter()

    ep = variant == "expert_parallel"
    if kind == "train":
        opt = AdamW(lr=1e-4)
        step = make_train_step(cfg, opt, remat=variant != "no_remat")
        params = param_shapes(cfg)                      # fp32 master
        opt_state = opt_state_shapes(cfg, opt)
        pspec = param_specs(params, mesh, fsdp=True, expert_parallel=ep)
        # optimizer state shards exactly like params (mu/nu mirror the tree)
        from repro.training.optimizer import AdamWState
        ospec = AdamWState(P(), pspec, pspec)
        ins = input_specs(cfg, shape)
        bspec = {k: data_spec(mesh, v.shape[0], len(v.shape))
                 for k, v in ins["batch"].items()}
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(_named(mesh, pspec), _named(mesh, ospec),
                              _named(mesh, bspec)),
            ).lower(params, opt_state, ins["batch"])
    else:
        params = param_shapes(cfg, dtype=jnp.bfloat16)  # serving weights
        if variant == "replicated":
            pspec = jax.tree.map(lambda s: P(*([None] * len(s.shape))), params)
        else:
            pspec = param_specs(params, mesh, fsdp=False, expert_parallel=ep)
        cache_dtype = jnp.int8 if variant == "kv8" else jnp.bfloat16
        tp = mesh.shape["model"]
        replicas = mesh.devices.size // tp
        mesh = replica_submeshes(
            Mesh(mesh.devices.reshape(replicas, tp), ("data", "model")))[0]
        ins = input_specs(cfg, shape, mode=mode, cache_dtype=cache_dtype,
                          replicas=replicas)
        caches = ins["caches"]
        cspec = paged_cache_specs(caches, mesh)
        bspec = {k: data_spec(mesh, v.shape[0], len(v.shape))
                 for k, v in ins["batch"].items()}
        if kind == "prefill":
            step = make_prefill_step(cfg)
        elif mode == "pard_verify":
            step = make_verify_step(cfg, window=window)
        else:
            step = make_decode_step(cfg, window=window)
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(_named(mesh, pspec), _named(mesh, cspec),
                              _named(mesh, bspec)),
            ).lower(params, caches, ins["batch"])

    rec["lower_s"] = round(time.perf_counter() - t0, 2)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t1, 2)

    ca = compiled.cost_analysis() or {}
    rec["flops"] = float(ca.get("flops", 0.0))
    rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["memory"] = dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
            code_bytes=int(ma.generated_code_size_in_bytes),
        )
    hlo = compiled.as_text()
    rec["collectives"] = roofline.collective_census(hlo)
    rec["roofline"] = roofline.roofline_terms(rec, cfg, shape)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="default",
                    choices=["default", "pard_verify"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    # shape-major, cheap shapes first: training compiles are 10-100x slower
    # (superlinear GSPMD propagation with depth), so serving combos bank
    # first and an interrupted sweep still covers the full serving grid
    shape_order = [s for s in ("prefill_32k", "decode_32k", "long_500k",
                               "train_4k") if s in shapes]
    for shape in shape_order:
        for arch in archs:
            reason = _skip_reason(arch, shape)
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}" + \
                    ("" if args.mode == "default" else f"__{args.mode}") + \
                    ("" if args.variant == "baseline" else f"__{args.variant}")
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip-done] {tag}")
                    continue
                if reason:
                    rec = dict(arch=arch, shape=shape, multi_pod=mp,
                               skipped=reason)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"[skip] {tag}: {reason}")
                    continue
                try:
                    rec = lower_one(arch, shape, multi_pod=mp, mode=args.mode,
                                    variant=args.variant)
                    status = "OK"
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = dict(arch=arch, shape=shape, multi_pod=mp,
                               error=f"{type(e).__name__}: {e}",
                               traceback=traceback.format_exc()[-4000:])
                    failures += 1
                    status = "FAIL"
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                msg = rec.get("error", "")[:120]
                extra = ""
                if "roofline" in rec:
                    r = rec["roofline"]
                    extra = (f" compute={r['compute_s']:.2e}s "
                             f"mem={r['memory_s']:.2e}s "
                             f"coll={r['collective_s']:.2e}s "
                             f"dom={r['dominant']}")
                print(f"[{status}] {tag} "
                      f"lower={rec.get('lower_s')}s "
                      f"compile={rec.get('compile_s')}s{extra} {msg}",
                      flush=True)
    print(f"dryrun complete, failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
