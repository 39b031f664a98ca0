import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape x mesh) cell this lowers + compiles
the real step function (train_step / prefill_step / decode_step) against
ShapeDtypeStruct inputs on the production mesh — 16x16 single-pod and
2x16x16 multi-pod — and records:

* ``memory_analysis()``  (per-device bytes: proves the cell fits a v5e),
* ``cost_analysis()``    (HLO FLOPs / bytes accessed),
* collective wire bytes parsed from the partitioned HLO
  (launch/hlo_analysis.py, loop-trip-count aware),
* the three roofline terms (DESIGN.md §6).

Artifacts land in ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``; the
roofline table in EXPERIMENTS.md §Roofline is generated from them by
``benchmarks/roofline.py``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out artifacts/dryrun
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ALL_SHAPES, RunConfig
from repro.configs.registry import (ARCH_IDS, get_config,
                                    shape_applicability)
from repro.distributed import sharding as SH
from repro.launch import hlo_analysis as HA
from repro.launch import specs as SPEC
from repro.launch.mesh import make_production_mesh
from repro.runtime import steps as ST

# TPU v5e-class hardware model (assignment constants)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link
DCN_BW = 25e9                # bytes/s / host (assumed; pod-crossing)


def _state_shardings(mesh, state_specs, cfg, run):
    return SH.make_state_shardings(mesh, state_specs, cfg, run)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               run_overrides=None):
    """Build + lower + compile one cell; returns (compiled, meta)."""
    cfg, run = get_config(arch)
    if run_overrides:
        plain = {k: v for k, v in run_overrides.items()
                 if not k.startswith("_")}
        if plain:
            run = dataclasses.replace(run, **plain)
    from repro.models import common as _C
    from repro.models import moe as _M
    _C.SEQ_PARALLEL = run.seq_parallel
    _M.EXPERT_PARALLEL = run.expert_parallel
    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state_specs, batch = SPEC.input_specs(cfg, run, shape)
            state_sh = _state_shardings(mesh, state_specs, cfg, run)
            batch_sh = SH.make_batch_shardings(mesh, batch)
            if run_overrides and run_overrides.get("_podwise"):
                # explicit shard_map over the pod axis (hillclimb #1):
                # the cross-pod all-reduce is a visible lax.pmean over
                # either raw grads or the DWT-compressed slice.  The batch
                # sharding stays unspecified at the jit level (shard_map
                # splits pod; GSPMD infers data from the constraints).
                fn = ST.make_train_step_podwise(mesh, cfg, run)
                jitted = jax.jit(fn, in_shardings=(state_sh, None),
                                 out_shardings=(state_sh, None),
                                 donate_argnums=0)
            else:
                fn = functools.partial(ST.train_step, cfg=cfg, run=run)
                jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                                 out_shardings=(state_sh, None),
                                 donate_argnums=0)
            lowered = jitted.lower(state_specs, batch)
        elif shape.kind == "prefill":
            params, batch = SPEC.input_specs(cfg, run, shape)
            p_sh = SH.make_param_shardings(mesh, params, cfg, run)
            batch_sh = SH.make_batch_shardings(mesh, batch)
            fn = functools.partial(ST.prefill_step, cfg=cfg,
                                   max_len=shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(p_sh, batch_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            params, cache, tokens = SPEC.input_specs(cfg, run, shape)
            p_sh = SH.make_param_shardings(mesh, params, cfg, run)
            c_sh = SH.make_cache_shardings(mesh, cache, cfg, run)
            t_sh = SH.make_batch_shardings(mesh, {"t": tokens})["t"]
            fn = functools.partial(ST.decode_step, cfg=cfg)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=1)
            lowered = jitted.lower(params, cache, tokens)
        compiled = lowered.compile()

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "multi_pod": multi_pod,
            "n_chips": n_chips, "kind": shape.kind,
            "seq_len": shape.seq_len, "global_batch": shape.global_batch}
    return compiled, meta, cfg, shape


def analyse(compiled, meta, cfg, shape) -> dict:
    out = dict(meta)
    ma = compiled.memory_analysis()
    out["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_device_bytes": (ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
        "fits_16GB": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        < 16e9,
    }
    ca = compiled.cost_analysis() or {}
    out["cost_analysis_raw"] = {
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_accessed_per_device": float(ca.get("bytes accessed", 0.0)),
        "note": "XLA:CPU counts while bodies once; see cost (loop-aware)",
    }

    hlo = compiled.as_text()
    # pod-crossing collectives: replica groups spanning >= half the device
    # ids (the pod axis is the outermost mesh dim); single-pod meshes have
    # no DCN traffic by construction
    n_chips = meta.get("n_chips", 512)
    multi_pod = meta.get("multi_pod",
                         meta.get("mesh", "").count("x") >= 2)
    span = n_chips // 2 if multi_pod else n_chips + 1
    coll = HA.parse_collectives(hlo, pod_span_threshold=span)
    out["collectives"] = coll.as_dict()
    cost = HA.parse_costs(hlo)
    flops_dev = cost.flops
    # memory term: fusion-optimistic major-op traffic (dots, slices,
    # gathers) — models TPU fusion; bytes_accessed is the CPU-fusion
    # upper bound, kept for reference.
    bytes_dev = cost.bytes_major
    out["cost"] = {"flops_per_device": flops_dev,
                   "bytes_major_per_device": cost.bytes_major,
                   "bytes_accessed_per_device": cost.bytes_accessed,
                   "method": "loop-aware HLO parse (launch/hlo_analysis.py)"}
    del hlo

    # roofline terms (seconds, per device == per step for SPMD)
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll.wire_bytes_ici / ICI_BW + coll.wire_bytes_dcn / DCN_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    out["roofline"] = terms
    out["dominant"] = max(terms, key=terms.get)

    # MODEL_FLOPS (whole step, all chips)
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        d_tokens = shape.global_batch * (
            cfg.max_target_len if cfg.family == "encdec" else shape.seq_len)
        model_flops = 6 * n_active * d_tokens
    elif shape.kind == "prefill":
        d_tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * d_tokens
    else:
        model_flops = 2 * n_active * shape.global_batch
    hlo_flops_total = flops_dev * meta["n_chips"]
    out["model_flops"] = model_flops
    out["hlo_flops_total"] = hlo_flops_total
    out["useful_flops_ratio"] = (model_flops / hlo_flops_total
                                 if hlo_flops_total else 0.0)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             run_overrides=None, tag_suffix: str = "") -> dict:
    mesh_tag = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_tag}{tag_suffix}"
    skip = shape_applicability(arch, shape_name_to_shape(shape_name))
    if skip:
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "SKIP", "reason": skip}
    else:
        t0 = time.time()
        try:
            compiled, meta, cfg, shape = lower_cell(
                arch, shape_name, multi_pod, run_overrides)
            res = analyse(compiled, meta, cfg, shape)
            res["status"] = "OK"
            res["compile_seconds"] = round(time.time() - t0, 1)
            del compiled
        except Exception as e:  # a failure here is a bug in the system
            res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:],
                   "compile_seconds": round(time.time() - t0, 1)}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{tag}.json").write_text(json.dumps(res, indent=1))
    return res


def shape_name_to_shape(name: str):
    return next(s for s in ALL_SHAPES if s.name == name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="", help="artifact name suffix")
    ap.add_argument("--podwise", action="store_true",
                    help="explicit shard_map over the pod axis")
    ap.add_argument("--set", action="append", default=[],
                    help="RunConfig override key=value (repeatable)")
    args = ap.parse_args()

    overrides = {}
    if args.podwise:
        overrides["_podwise"] = True
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v in ("true", "false"):
            v = v == "true"
        elif v.isdigit():
            v = int(v)
        overrides[k] = v

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = ([s.name for s in ALL_SHAPES] if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = (f"{arch}__{shape}__{'multi' if mp else 'single'}"
                       f"{args.tag}")
                if args.skip_existing and (out_dir / f"{tag}.json").exists():
                    prev = json.loads((out_dir / f"{tag}.json").read_text())
                    if prev.get("status") in ("OK", "SKIP"):
                        continue
                res = run_cell(arch, shape, mp, out_dir,
                               run_overrides=overrides or None,
                               tag_suffix=args.tag)
                status = res["status"]
                extra = ""
                if status == "OK":
                    mem = res["memory"]["peak_device_bytes"] / 1e9
                    extra = (f" peak={mem:.2f}GB dom={res['dominant']}"
                             f" compile={res['compile_seconds']}s")
                elif status == "FAIL":
                    extra = " " + res["error"][:120]
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
