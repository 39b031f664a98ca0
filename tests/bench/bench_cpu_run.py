"""Drive a cell of the chip benchmark at a tiny size on the CPU.

The harness's look for a TPU is skipped and the configuration is cut
to the few kilopixels its file states under ``cpu`` (a shape and a
level count); everything else (data from the seed, warm-up, window,
output check) is the benchmark's own.

    python tests/bench/bench_cpu_run.py <cell> [--control] [--fault F]
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as R  # noqa: E402
from bench.harness import GcPauses  # noqa: E402

def small_config(name: str) -> dict:
    """Configuration ``name`` at the size its ``cpu`` key states (the
    published sizes are chip-sized)."""
    cfg = R._json("configs", name)
    if "cpu" not in cfg:
        raise R.BenchError(f"configuration {name!r} states no 'cpu' key "
                           f"(the shape and levels of a CPU run)")
    cfg.update(cfg["cpu"])
    return cfg


def _perturb_pyramid(pyr, step: float):
    pyr.ll = pyr.ll.at[(0,) * pyr.ll.ndim].add(step)
    return pyr


@contextlib.contextmanager
def armed(fault: str, step: float):
    """Break the timed path underneath the harness while inside:
    ``"answer"`` moves one output value of every call by ``step``."""
    from repro.engine import plan as PL
    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "answer":
        fwd, inv = PL.DwtPlan.execute, PL.DwtPlan.execute_inverse
        patch(PL.DwtPlan, "execute",
              lambda self, x: _perturb_pyramid(fwd(self, x), step))
        patch(PL.DwtPlan, "execute_inverse",
              lambda self, p: inv(self, p).at[
                  (0,) * len(self.key.shape)].add(step))
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)


def run_cell(name: str, monkeypatch, *, seconds: float = 0.5,
             seed: int = 2 ** 33 + 7, control: bool = False,
             fault: str = "") -> dict:
    """One run in this process; returns the result line's object.
    ``monkeypatch`` (pytest's) undoes the stand-ins: the CPU devices for
    the TPU, the small configuration for the published one.  The
    ``"answer"`` fault moves one value by 1/4096 of the sample range
    (one step of a 12-bit sample), the same share at every bit depth."""
    import jax
    from repro import compile_cache
    config = R.find_cell(name)["config"]
    step = 2.0 ** (small_config(config)["bit_depth"] - 12)
    argv = ["--workload", name, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"] + (["--control"] if control
                                             else [])
    monkeypatch.setattr(R, "_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(R, "load_config", small_config)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: str(R.CACHE_DIR))
    for env in ("JAX_COMPILATION_CACHE_DIR", "REPRO_TELEMETRY",
                "REPRO_TELEMETRY_JAX", "REPRO_BLOCK_TABLE",
                "REPRO_PROFILE_STORE"):
        monkeypatch.delenv(env, raising=False)   # restored afterwards
    try:
        with armed(fault, step):
            return R.run(R.parse_args(argv))
    finally:
        gc.callbacks[:] = [cb for cb in gc.callbacks
                           if not isinstance(getattr(cb, "__self__", None),
                                             GcPauses)]


if __name__ == "__main__":
    import pytest
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="")
    a = ap.parse_args()
    with pytest.MonkeyPatch.context() as mp:
        print(json.dumps(run_cell(a.cell, mp, control=a.control,
                                  fault=a.fault)))
