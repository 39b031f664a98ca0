"""Train / prefill / decode step functions — the units the dry-run lowers.

``train_step``: loss -> grads -> (optional DWT-compressed cross-pod
all-reduce with error feedback) -> AdamW.  Cross-entropy is computed in
sequence chunks so the (B, S, vocab) logits tensor is never materialized
(200k-class vocabs at 4k sequence would otherwise dominate memory).

``train_step_podwise`` is the multi-pod variant: the ``pod`` mesh axis is
*manual* (shard_map) so the cross-pod gradient all-reduce is an explicit
``lax.pmean`` — over raw gradients, or over the 4^-L-sized DWT subband
when compression is on.  ``data``/``model`` axes stay auto (GSPMD).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.core import compression as CMP
from repro.models import common as C
from repro.models import lm
from repro.optim import adamw

CE_CHUNK = 256
AUX_COEF = 0.01


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    efb: Any            # error-feedback state ({} when compression off)
    step: jax.Array


def init_train_state(rng, cfg: ModelConfig, run: RunConfig) -> TrainState:
    params = lm.init_params(rng, cfg)
    opt = adamw.init(params)
    efb = (CMP.init_error_feedback(params)
           if run.grad_compression.startswith("dwt") else {})
    return TrainState(params, opt, efb, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Chunked cross-entropy
# ---------------------------------------------------------------------------

def chunked_ce(embed_params, hidden: jax.Array, labels: jax.Array,
               mask: jax.Array, cfg: ModelConfig,
               chunk: int = CE_CHUNK) -> jax.Array:
    """Mean CE over masked positions; vocab projection done per chunk.

    hidden: (B, S, D); labels/mask: (B, S).
    """
    b, s, d = hidden.shape
    ch = min(chunk, s)
    while s % ch:
        ch -= 1
    nc = s // ch

    hs = jnp.moveaxis(hidden.reshape(b, nc, ch, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, nc, ch), 1, 0)
    ms = jnp.moveaxis(mask.reshape(b, nc, ch), 1, 0)

    def body(carry, inp):
        h, l, m = inp
        logits = C.unembed(embed_params, h, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(m)), None

    # never keep per-chunk logits as scan residuals (B*chunk*vocab each)
    body = jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable)
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            run: RunConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE (+ MoE aux) for every family."""
    if cfg.family == "encdec":
        hidden, aux = lm.whisper_hidden(
            params, batch["enc_embeds"], batch["dec_tokens"], cfg,
            remat=(run.remat != "none"))
        tokens = batch["dec_tokens"]
    else:
        hidden, aux = lm.forward_hidden(
            params, batch["tokens"], cfg,
            embeds=batch.get("patch_embeds"),
            remat=(run.remat != "none"))
        tokens = batch["tokens"]
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:], jnp.float32),
         jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
    ce = chunked_ce(params["embed"], hidden, labels, mask, cfg)
    loss = ce + AUX_COEF * aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Train steps
# ---------------------------------------------------------------------------

def _grads(params, batch, cfg, run):
    if run.grad_accum <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, batch, cfg, run)
        return grads, metrics
    # microbatch accumulation via scan (batch dim split)
    n = run.grad_accum

    def micro(b):
        return jax.tree_util.tree_map(
            lambda a: a.reshape(n, a.shape[0] // n, *a.shape[1:]), b)

    def body(acc, mb):
        (loss, metrics), g = jax.value_and_grad(
            lm_loss, has_aux=True)(params, mb, cfg, run)
        acc = jax.tree_util.tree_map(
            lambda x, y: x + y.astype(jnp.float32) / n, acc, g)
        return acc, metrics

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    grads, metrics = jax.lax.scan(body, zeros, micro(batch))
    metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
    return grads, metrics


def _compression_levels(run: RunConfig) -> int:
    return int(run.grad_compression.split(":")[1]) \
        if ":" in run.grad_compression else 2


def train_step(state: TrainState, batch, cfg: ModelConfig, run: RunConfig
               ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Single-program train step (pjit; collectives inserted by GSPMD)."""
    grads, metrics = _grads(state.params, batch, cfg, run)
    efb = state.efb
    if run.grad_compression.startswith("dwt"):
        grads, efb = CMP.compress_with_feedback(
            grads, efb, state.step, _compression_levels(run),
            run.compression_wavelet)
    params, opt, om = adamw.apply(grads, state.opt, state.params, run)
    metrics.update(om)
    return TrainState(params, opt, efb, state.step + 1), metrics


def make_train_step_podwise(mesh, cfg: ModelConfig, run: RunConfig):
    """Multi-pod train step: explicit (compressed) cross-pod all-reduce.

    Each pod computes gradients on its batch shard; the only cross-pod
    gradient traffic is the pmean over either raw grads or the DWT
    subband slice (4^-L bytes).  ``data``/``model`` stay auto (GSPMD).

    Structure: the model forward/backward contains ``lax.scan`` (layer
    stacks, chunked CE), which XLA cannot partition inside a
    partially-manual shard_map region on the jax versions we support, so
    the pod axis rides an explicit leading batch dimension through a
    vmapped gradient computation (no automatic cross-pod all-reduce is
    ever inserted: there is no contraction over that dim).  Only the
    scan-free compressed exchange runs inside the manual-``pod``
    shard_map.

    Known caveat (pre-existing design): the error-feedback residual is
    genuinely pod-local state (standard distributed EF keeps local error
    memories) but is carried under a replicated-out spec with the
    replication check disabled — each device physically retains its pod's
    residual.  Checkpointing/resharding ``efb`` would collapse it to one
    pod's copy; averaging it instead would cost a full-size DCN
    all-reduce, defeating the compression.
    """
    compress = run.grad_compression.startswith("dwt")
    levels = _compression_levels(run)
    from repro.distributed.sharding import _axis_size
    n_pods = _axis_size(mesh, "pod")

    def exchange(grads_p, efb, step_count):
        """Manual-pod region: per-pod grads -> reduced grads + efb."""
        g = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0), grads_p)
        if compress:
            return CMP.compress_with_feedback(
                g, efb, step_count, levels, run.compression_wavelet,
                reduce_fn=lambda x: jax.lax.pmean(x, "pod"))
        return jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "pod"), g), efb

    exchange_sm = jax.shard_map(
        exchange, mesh=mesh, in_specs=(P("pod"), P(), P()),
        out_specs=(P(), P()), axis_names={"pod"}, check_vma=False)

    def step(state: TrainState, batch):
        # (B, ...) -> (n_pods, B/n_pods, ...): pod becomes a vmapped
        # leading dim, sharded over the pod axis
        def split(a):
            a = a.reshape(n_pods, a.shape[0] // n_pods, *a.shape[1:])
            spec = P("pod", *([None] * (a.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                a, jax.sharding.NamedSharding(mesh, spec))

        batch_p = jax.tree_util.tree_map(split, batch)
        grads_p, metrics_p = jax.vmap(
            lambda b: _grads(state.params, b, cfg, run))(batch_p)
        grads, efb = exchange_sm(grads_p, state.efb, state.step)
        metrics = jax.tree_util.tree_map(
            lambda m: jnp.mean(m, axis=0), metrics_p)
        params, opt, om = adamw.apply(grads, state.opt, state.params, run)
        metrics.update(om)
        return TrainState(params, opt, efb, state.step + 1), metrics

    return step


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

def prefill_step(params, batch, cfg: ModelConfig, max_len: int):
    """Full-context prefill -> (last logits, populated decode cache)."""
    if cfg.family == "encdec":
        cache = lm.whisper_prefill(params, batch["enc_embeds"], cfg,
                                   batch["enc_embeds"].shape[0])
        return jnp.zeros((batch["enc_embeds"].shape[0],
                          C.pad_vocab(cfg.vocab_size)), jnp.float32), cache
    return lm.prefill(params, batch["tokens"], cfg, max_len,
                      embeds=batch.get("patch_embeds"))


def decode_step(params, cache, tokens, cfg: ModelConfig):
    """One new token against the cache (the serve_step of decode cells)."""
    if cfg.family == "encdec":
        return lm.whisper_decode_step(params, cache, tokens, cfg)
    return lm.decode_step(params, cache, tokens, cfg)
