"""Sharding rules: parameter/optimizer/cache PartitionSpecs for the
production mesh.

Logical layout (DESIGN.md §5):

* FSDP over the ``data`` axis on one matrix dim of every weight (ZeRO-3:
  optimizer state inherits the same specs);
* tensor parallelism over the ``model`` axis on heads / d_ff / vocab /
  experts;
* the ``pod`` axis (multi-pod mesh) is pure data parallelism: parameters
  are replicated across pods and gradients all-reduce over DCN — the
  collective whose bytes the DWT compression shrinks.

Rules are name+shape based over the parameter pytree, so they apply to
every architecture family uniformly.  Head dims shard over ``model`` only
when divisible (phi-4's 24 heads would force GSPMD padding; we replicate
instead and record the trade-off in EXPERIMENTS.md).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig


def make_tile_mesh(rows: int, cols: int,
                   axes: Tuple[str, str] = ("tr", "tc")) -> Mesh:
    """2-D device mesh for the tiling subsystem's shard_map transport:
    one tile per device, mesh axes sized like the tile grid (see
    :mod:`repro.tiling.exchange`)."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < rows * cols:
        raise ValueError(
            f"tile mesh {rows}x{cols} needs {rows * cols} devices, "
            f"have {len(devs)}")
    arr = np.asarray(devs[:rows * cols]).reshape(rows, cols)
    return Mesh(arr, axes)


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _div(n: int, mesh: Mesh, axis: str) -> Optional[str]:
    """Return the axis name if it exists in the mesh and n is divisible
    by its size, else None."""
    if axis not in mesh.axis_names:
        return None
    return axis if n % _axis_size(mesh, axis) == 0 else None


def param_spec(path: str, shape: Tuple[int, ...], cfg: ModelConfig,
               run: RunConfig, mesh: Mesh, fsdp: bool = True) -> P:
    """PartitionSpec for one parameter leaf.

    ``path`` is the '/'-joined key path; a leading layer-stack dim (from
    scan stacking) is detected by shape arity and never sharded.
    ``fsdp=False`` (ZeRO-2 compute params) drops the 'data'-axis sharding
    while keeping TP — optimizer state keeps fsdp=True.
    """
    name = path.split("/")[-1]

    def d(n, mesh_, axis):
        if axis == "data" and not fsdp:
            return None
        return _div(n, mesh_, axis)

    def base(spec_dims):
        """Prepend Nones for any leading stack dims."""
        pad = len(shape) - len(spec_dims)
        return P(*([None] * pad + list(spec_dims)))

    # --- embeddings ---
    if name == "tok":
        return P(d(shape[0], mesh, "model"), d(shape[1], mesh, "data"))
    if name == "head":
        return P(d(shape[0], mesh, "data"), d(shape[1], mesh, "model"))

    in_attn = "attn" in path or "xattn" in path

    def _q_heads_ax(n: int):
        """Q heads shard over model even when not divisible (GSPMD pads:
        qwen2 14->16, phi4 24->32; padding waste <= 2x beats 16x
        replication).  Tiny head counts replicate."""
        if "model" not in mesh.axis_names or not run.attn_tp:
            return None
        ax = _axis_size(mesh, "model")
        if n % ax == 0 or n >= 0.75 * ax:
            return "model"
        return None

    # --- attention (q/k/v stored (d, n, hd); wo (n, hd, d)) ---
    if name in ("wq", "wk", "wv") and len(shape) >= 3 and in_attn:
        n = shape[-2]
        if name == "wq":
            h_ax = _q_heads_ax(n)
        else:
            h_ax = ("model" if (run.attn_tp
                                and "model" in mesh.axis_names
                                and n % _axis_size(mesh, "model") == 0)
                    else None)
        # MQA (kv=1): shard the head_dim instead — scores become a sharded
        # contraction (partial-sum all-reduce), and the decode KV cache
        # shards 16-way rather than replicating (granite-34b).
        hd_ax = None
        if h_ax is None and n == 1 and run.attn_tp and name in ("wk", "wv"):
            hd_ax = d(shape[-1], mesh, "model")
        return base([d(shape[-3], mesh, "data"), h_ax, hd_ax])
    if name == "wo" and len(shape) >= 3 and in_attn:
        h_ax = _q_heads_ax(shape[-3])
        return base([h_ax, None, d(shape[-1], mesh, "data")])
    if name in ("bq", "bk", "bv"):
        n = shape[-2]
        if name == "bq":
            h_ax = _q_heads_ax(n)
        else:
            h_ax = ("model" if (run.attn_tp
                                and "model" in mesh.axis_names
                                and n % _axis_size(mesh, "model") == 0)
                    else None)
        return base([h_ax, None])

    # --- MoE experts (e, d, f) / (e, f, d); router (d, e) ---
    if name == "router":
        return base([d(shape[-2], mesh, "data"), None])
    if name in ("gate", "up", "down") and len(shape) >= 3 and cfg.is_moe \
            and shape[-3] == cfg.n_experts:
        e_ax = ("model" if run.expert_parallel
                and "model" in mesh.axis_names
                and cfg.n_experts % _axis_size(mesh, "model") == 0 else None)
        if name == "down":  # (e, f, d)
            f_ax = None if e_ax else d(shape[-2], mesh, "model")
            return base([e_ax, f_ax, d(shape[-1], mesh, "data")])
        f_ax = None if e_ax else d(shape[-1], mesh, "model")
        return base([e_ax, d(shape[-2], mesh, "data"), f_ax])

    # --- dense MLP ---
    if name in ("gate", "up", "ck", "decay_w1"):
        return base([d(shape[-2], mesh, "data"),
                     d(shape[-1], mesh, "model")
                     if name != "decay_w1" else None])
    if name in ("down", "cv"):
        return base([d(shape[-2], mesh, "model"), d(shape[-1], mesh, "data")])
    if name == "up_b":
        return base([d(shape[-1], mesh, "model")])

    # --- mamba ---
    if name == "in_proj":
        return base([d(shape[-2], mesh, "data"), d(shape[-1], mesh, "model")])
    if name == "out_proj":
        return base([d(shape[-2], mesh, "model"), d(shape[-1], mesh, "data")])
    if name in ("conv_w",):
        return base([None, d(shape[-1], mesh, "model")])
    if name in ("conv_b", "norm"):
        return base([d(shape[-1], mesh, "model")])

    # --- rwkv square projections (paths contain 'rwkv', not 'attn') ---
    if name in ("wr", "wg", "cr", "wk", "wv", "wq", "wo"):
        return base([d(shape[-2], mesh, "data"), d(shape[-1], mesh, "model")])
    if name == "decay_w2":
        return base([None, d(shape[-1], mesh, "model")])

    # everything else (norm scales, biases, mixing coefficients) replicates
    return P()


def make_state_shardings(mesh: Mesh, state_specs, cfg: ModelConfig,
                         run: RunConfig):
    """TrainState shardings: ZeRO-3 shards compute params over 'data';
    ZeRO-2 keeps compute params TP-only and shards just optimizer state
    (+ error feedback) — one param gather per step instead of per
    microbatch."""
    from repro.runtime.steps import TrainState
    repl = NamedSharding(mesh, P())
    fsdp_params = run.zero >= 3
    return TrainState(
        params=make_param_shardings(mesh, state_specs.params, cfg, run,
                                    fsdp=fsdp_params),
        opt=type(state_specs.opt)(
            count=repl,
            mu=make_param_shardings(mesh, state_specs.opt.mu, cfg, run),
            nu=make_param_shardings(mesh, state_specs.opt.nu, cfg, run)),
        efb=make_param_shardings(mesh, state_specs.efb, cfg, run),
        step=repl,
    )


def make_param_shardings(mesh: Mesh, params_shape: Any, cfg: ModelConfig,
                         run: RunConfig, fsdp: bool = True) -> Any:
    """NamedSharding pytree matching ``params_shape`` (from eval_shape)."""
    def one(path, leaf):
        keys = "/".join(
            k.key if hasattr(k, "key") else str(k) for k in path)
        return NamedSharding(mesh, param_spec(keys, leaf.shape, cfg, run,
                                              mesh, fsdp=fsdp))
    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_axes(mesh: Mesh, batch: int):
    """Shard batch over (pod, data) when divisible; fall back gracefully."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    size = 1
    for a in axes:
        size *= _axis_size(mesh, a)
    if batch % size == 0:
        return tuple(axes) if len(axes) > 1 else axes[0]
    if "data" in mesh.axis_names and batch % _axis_size(mesh, "data") == 0:
        return "data"
    return None


def make_batch_shardings(mesh: Mesh, batch_shape: Any) -> Any:
    def one(leaf):
        b_ax = batch_axes(mesh, leaf.shape[0])
        return NamedSharding(mesh, P(b_ax, *([None] * (len(leaf.shape) - 1))))
    return jax.tree_util.tree_map(one, batch_shape)


def make_cache_shardings(mesh: Mesh, cache_shape: Any, cfg: ModelConfig,
                         run: RunConfig) -> Any:
    """Decode caches: batch over (pod,data); kv-head dim over model when
    divisible.  Cache leaves are (L, B, ...) or (B, ...) for scalars."""
    def one(path, leaf):
        keys = "/".join(
            k.key if hasattr(k, "key") else str(k) for k in path)
        shp = leaf.shape
        if not shp:  # pos scalar
            return NamedSharding(mesh, P())
        # find the batch dim: first dim not equal to a layer-stack prefix
        specs = [None] * len(shp)
        # caches are stacked (L_or_groups, B, ...); top-level whisper cross
        # and plain kv leaves too — batch is dim 1 whenever stacked
        bdim = 1 if ("kv" in keys or "cross" in keys or "rwkv" in keys
                     or "mamba" in keys) and len(shp) >= 3 else 0
        specs[bdim] = batch_axes(mesh, shp[bdim])
        # kv heads (k/v caches are (..., len, kv, hd))
        if keys.endswith("/k") or keys.endswith("/v"):
            kv = shp[-2]
            if "model" not in mesh.axis_names:
                return NamedSharding(mesh, P(*specs))
            ax = _axis_size(mesh, "model")
            if run.attn_tp and kv % ax == 0:
                specs[-2] = "model"
            elif run.attn_tp and kv == 1 and shp[-1] % ax == 0:
                specs[-1] = "model"  # MQA: shard head_dim (granite)
            elif run.attn_tp and len(shp) >= 4 and shp[-3] % ax == 0:
                # GQA with kv not divisible (kv=8 on 16-way): shard the
                # cache LENGTH — sequence-parallel decode attention; the
                # softmax/PV reductions over length become collectives of
                # (B, heads)-sized partials, while the cache shards 16-way
                specs[-3] = "model"
        if "wkv" in keys and len(shp) >= 4:  # (L,B,nh,hd,hd)
            if _div(shp[2], mesh, "model"):
                specs[2] = "model"
        if "ssm" in keys and len(shp) >= 4:  # (L,B,nh,hd,ds)
            if _div(shp[2], mesh, "model"):
                specs[2] = "model"
        return NamedSharding(mesh, P(*specs))
    return jax.tree_util.tree_map_with_path(one, cache_shape)
