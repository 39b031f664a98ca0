import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"

"""Hillclimb #1: DWT gradient compression of the cross-pod all-reduce
(minitron-8b, train_4k, multi-pod).

Compares three variants of the multi-pod train step on a (pod=2, data=8,
model=8) mesh:

  baseline  — pjit: GSPMD inserts the cross-pod grad all-reduce
  podwise   — explicit shard_map over 'pod': lax.pmean(raw grads)
  poddwt    — shard_map + DWT:2 compression: lax.pmean(LL-slice), 16x
              fewer DCN bytes, error feedback keeps training exact-in-
              expectation (tests/test_compression.py)

NOTE: mixing a Manual 'pod' axis with an Auto 'model' axis trips an
XLA:CPU SPMD partitioner check-failure (spmd_partitioner_util.cc:504, a
native abort) on the full-size model at any multi-pod mesh — an XLA bug
(the same code compiles with the smoke config, and pure-DP meshes work
at every size).  The comparison therefore runs on a (pod=2, data=32)
pure-DP mesh, which isolates exactly the traffic the compression
targets: the cross-pod gradient exchange.  Per-device DCN bytes depend
on the pod count (2 in all cases), not the intra-pod topology, so the
ratio transfers to the (2,16,16) production mesh.
"""
import dataclasses
import json
from pathlib import Path

import jax

from repro.configs.base import TRAIN_4K
from repro.configs.registry import get_config
from repro.distributed import sharding as SH
from repro.launch import dryrun as DR
from repro.launch import specs as SPEC
from repro.runtime import steps as ST

OUT = Path(__file__).resolve().parent.parent / "artifacts" / "dryrun"


def lower_variant(tag, podwise, compression):
    cfg, run = get_config("minitron-8b")
    run = dataclasses.replace(run, grad_compression=compression)
    mesh = jax.make_mesh((2, 32), ("pod", "data"))
    with jax.set_mesh(mesh):
        state_specs, batch = SPEC.input_specs(cfg, run, TRAIN_4K)
        state_sh = SH.make_state_shardings(mesh, state_specs, cfg, run)
        if podwise:
            fn = ST.make_train_step_podwise(mesh, cfg, run)
            jitted = jax.jit(fn, in_shardings=(state_sh, None),
                             out_shardings=(state_sh, None),
                             donate_argnums=0)
        else:
            import functools
            batch_sh = SH.make_batch_shardings(mesh, batch)
            fn = functools.partial(ST.train_step, cfg=cfg, run=run)
            jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=0)
        compiled = jitted.lower(state_specs, batch).compile()
    meta = {"arch": "minitron-8b", "shape": "train_4k", "mesh": "2x32", "multi_pod": True,
            "n_chips": 64, "kind": "train", "seq_len": 4096,
            "global_batch": 256}
    res = DR.analyse(compiled, meta, cfg, TRAIN_4K)
    res["status"] = "OK"
    res["variant"] = tag
    (OUT / f"h1_{tag}.json").write_text(json.dumps(res, indent=1))
    c = res["collectives"]
    print(f"{tag:10s} dcn={c['wire_bytes_dcn']/1e9:8.3f}GB "
          f"ici={c['wire_bytes_ici']/1e9:8.1f}GB "
          f"coll_s={res['roofline']['collective_s']:.3f}", flush=True)
    return res


def main():
    import sys
    if len(sys.argv) > 1:   # subprocess mode: one variant per process
        tag = sys.argv[1]
        podwise = tag != "pjit_base"
        compression = "dwt:2" if tag == "poddwt" else "none"
        lower_variant(tag, podwise, compression)
        return
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    for tag in ("pjit_base", "podraw", "poddwt"):
        subprocess.run([sys.executable, __file__, tag], env=env,
                       timeout=540)
    rows = {}
    for tag in ("pjit_base", "podraw", "poddwt"):
        p = OUT / f"h1_{tag}.json"
        if p.exists():
            rows[tag] = json.loads(p.read_text())
    if "pjit_base" in rows and "poddwt" in rows:
        b = rows["pjit_base"]["collectives"]["wire_bytes_dcn"]
        d = rows["poddwt"]["collectives"]["wire_bytes_dcn"]
        print(f"\nDCN bytes/device: pjit {b/1e9:.3f}GB -> podwise-dwt "
              f"{d/1e9:.3f}GB  ({b / max(d, 1):.1f}x reduction)")


if __name__ == "__main__":
    main()
