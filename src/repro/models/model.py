"""Top-level model API: init / loss / prefill / decode for every assigned
architecture, driven entirely by :class:`ModelConfig`.

Batches are plain dicts:
    tokens          (B, S)   int32
    labels          (B, S)   int32            (training)
    loss_mask       (B, S)   bool, optional
    encoder_embeds  (B, enc_seq, frontend_dim)  — whisper STUB frontend
    image_embeds    (B, n_patches, frontend_dim) — internvl2 STUB frontend

Frontends are STUBS per the assignment carve-out: ``input_specs`` provides
precomputed frame/patch embeddings; this module only owns the projector that
maps them into d_model and the decoder that consumes them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.attention import AttnCache, PagedAttnCache, PagedView
from repro.models.common import Param, param, truncated_normal, unzip, values_of
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_norm,
    cross_entropy_parts,
    embed_tokens,
    init_embedding,
    init_norm,
    logits_sharded,
    sinusoidal_positions,
)
from repro.models.rglru import RGLRUCache
from repro.models.ssd import SSDCache, d_inner, num_heads_ssm
from repro.parallel.sharding import ShardCtx

PyTree = Any

LOSS_CHUNK = 2048  # seq chunk for the memory-bounded LM loss


def encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(
        cfg,
        num_layers=cfg.num_encoder_layers,
        attn_pattern=("encoder",),
        arch_type="dense",
        use_rope=False,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: ModelConfig) -> PyTree:
    """GLOBAL-shape Param tree (use common.unzip to split values/specs)."""
    cfg.validate()
    ks = jax.random.split(key, 5)
    p: dict = {
        "embed": init_embedding(ks[0], cfg),
        "stack": tfm.init_stack(ks[1], cfg, cross=cfg.is_encoder_decoder),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if cfg.is_encoder_decoder:
        ecfg = encoder_cfg(cfg)
        p["encoder"] = tfm.init_stack(ks[2], ecfg)
        p["enc_norm"] = init_norm(cfg, cfg.d_model)
        if cfg.frontend_dim and cfg.frontend_dim != cfg.d_model:
            p["enc_proj"] = param(
                truncated_normal(
                    ks[3], (cfg.frontend_dim, cfg.d_model),
                    1.0 / math.sqrt(cfg.frontend_dim), jnp.dtype(cfg.dtype),
                ),
                "fsdp", None,
            )
    if cfg.frontend == "vision":
        p["projector"] = param(
            truncated_normal(
                ks[4], (cfg.frontend_dim, cfg.d_model),
                1.0 / math.sqrt(cfg.frontend_dim), jnp.dtype(cfg.dtype),
            ),
            "fsdp", None,
        )
    return p


# ---------------------------------------------------------------------------
# Forward pieces
# ---------------------------------------------------------------------------


def encode(p: PyTree, cfg: ModelConfig, encoder_embeds: jax.Array, ctx: ShardCtx) -> jax.Array:
    """Whisper encoder over STUB frame embeddings."""
    x = encoder_embeds
    if "enc_proj" in p:
        x = x @ ctx.gather_param(p["enc_proj"], axis=0)
    x = x.astype(jnp.dtype(cfg.dtype))
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    ecfg = encoder_cfg(cfg)
    x, _, _ = tfm.apply_stack(p["encoder"], ecfg, x, ctx, kinds=("encoder",))
    return apply_norm(p["enc_norm"], x)


def embed_input(
    p: PyTree, cfg: ModelConfig, batch: dict, ctx: ShardCtx
) -> tuple[jax.Array, jax.Array | None]:
    """Token (+frontend) embedding. Returns (x, loss_mask_extra)."""
    tokens = batch["tokens"]
    x = embed_tokens(p["embed"], cfg, tokens, ctx)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    mask_extra = None
    if cfg.frontend == "vision" and "image_embeds" in batch:
        img = batch["image_embeds"] @ ctx.gather_param(p["projector"], axis=0)
        img = img.astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        b = tokens.shape[0]
        mask_extra = jnp.concatenate(
            [
                jnp.zeros((b, img.shape[1]), bool),
                jnp.ones((b, tokens.shape[1]), bool),
            ],
            axis=1,
        )
    if not cfg.use_rope:  # absolute sinusoidal positions (whisper decoder)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    return x, mask_extra


def _lm_loss(
    p: PyTree, cfg: ModelConfig, x: jax.Array, labels: jax.Array,
    mask: jax.Array | None, ctx: ShardCtx,
) -> jax.Array:
    """Chunked-over-sequence LM loss: never materializes (B, S, V) logits."""
    b, s, d = x.shape
    if s <= LOSS_CHUNK or s % LOSS_CHUNK:
        logits = logits_sharded(p["embed"], cfg, x, ctx)
        nll, cnt = cross_entropy_parts(logits, labels, cfg, ctx, mask)
        return nll / jnp.maximum(cnt, 1.0)
    nc = s // LOSS_CHUNK
    xc = x.reshape(b, nc, LOSS_CHUNK, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, LOSS_CHUNK).transpose(1, 0, 2)
    mc = mask.reshape(b, nc, LOSS_CHUNK).transpose(1, 0, 2) if mask is not None else None

    def body(carry, inp):
        if mc is None:
            xi, li = inp
            mi = None
        else:
            xi, li, mi = inp
        logits = logits_sharded(p["embed"], cfg, xi, ctx)
        nll, cnt = cross_entropy_parts(logits, li, cfg, ctx, mi)
        # (nll, count) summed as one (2,) carry: the same float ops as two
        # scalar carries
        return carry + jnp.stack([nll, cnt]), None

    xs = (xc, lc) if mc is None else (xc, lc, mc)
    sums, _ = jax.lax.scan(
        body, jnp.zeros((2,)), xs, unroll=cfg.unroll_scans
    )
    return sums[0] / jnp.maximum(sums[1], 1.0)


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------


def loss_fn(
    params: PyTree, cfg: ModelConfig, batch: dict, ctx: ShardCtx, rng: jax.Array | None = None
) -> tuple[jax.Array, dict]:
    """Next-token LM loss (+ MoE aux).  ``params`` is a VALUE tree."""
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["encoder_embeds"], ctx)

    x, mask_extra = embed_input(params, cfg, batch, ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, _, aux = tfm.apply_stack(
        params["stack"], cfg, x, ctx, positions=positions, enc_out=enc_out
    )
    x = apply_norm(params["final_norm"], x)

    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask_extra is not None:
        # frontend tokens predict nothing; align labels with text positions
        pad = jnp.zeros((labels.shape[0], mask_extra.shape[1] - labels.shape[1]), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
        mask = mask_extra if mask is None else jnp.concatenate([pad.astype(bool), mask], axis=1)

    loss = _lm_loss(params, cfg, x, labels, mask, ctx)
    total = loss + aux
    return total, {"lm_loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Caches / serving
# ---------------------------------------------------------------------------


def _mixer_cache(cfg: ModelConfig, kind: str, batch: int, length: int):
    """Param-annotated cache for one layer (GLOBAL shapes; logical specs:
    "dp" batch dim, "seq_kv" sequence dim, "tp" width/head dims)."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    if kind in ("global", "local"):
        size = min(length, cfg.sliding_window) if kind == "local" else length
        seq_logical = "seq_kv" if kind == "global" else None
        return AttnCache(
            k=param(jnp.zeros((batch, size, kv, hd), dt), "dp", seq_logical, None, None),
            v=param(jnp.zeros((batch, size, kv, hd), dt), "dp", seq_logical, None, None),
            index=param(jnp.zeros((), jnp.int32)),
        )
    if kind == "rglru":
        w = cfg.lru_width or cfg.d_model
        return RGLRUCache(
            conv=param(jnp.zeros((batch, 3, w), dt), "dp", None, "tp"),
            h=param(jnp.zeros((batch, w), jnp.float32), "dp", "tp"),
        )
    if kind == "ssd":
        h = num_heads_ssm(cfg)
        return SSDCache(
            conv=param(jnp.zeros((batch, cfg.ssm_conv_width - 1, d_inner(cfg)), dt), "dp", None, "tp"),
            state=param(
                jnp.zeros((batch, h, cfg.ssm_head_dim, cfg.ssm_state_dim), jnp.float32),
                "dp", "tp", None, None,
            ),
        )
    raise ValueError(kind)  # pragma: no cover


def init_cache_tree(cfg: ModelConfig, batch: int, length: int) -> dict:
    """Param-annotated cache tree mirroring the stack structure."""
    period, n_full, rem = tfm.layer_plan(cfg)
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)

    def one(kind):
        mixer = _mixer_cache(cfg, kind, batch, length)
        cross = None
        if cfg.is_encoder_decoder:
            cross = AttnCache(
                k=param(jnp.zeros((batch, cfg.encoder_seq, kv, hd), dt), "dp", None, None, None),
                v=param(jnp.zeros((batch, cfg.encoder_seq, kv, hd), dt), "dp", None, None, None),
                index=param(jnp.zeros((), jnp.int32)),
            )
        return (mixer, cross)

    caches: dict = {"scan": [], "rem": []}
    for pos, kind in enumerate(period):
        layers = [one(kind) for _ in range(n_full)]
        caches["scan"].append(tfm._stack_trees(layers) if n_full else None)
    for j in range(rem):
        caches["rem"].append(one(period[j]))
    return caches


def prefill(
    params: PyTree, cfg: ModelConfig, batch: dict, caches: PyTree, ctx: ShardCtx
) -> tuple[jax.Array, PyTree]:
    """Fill caches from a full prompt; returns (last-position hidden, caches)."""
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["encoder_embeds"], ctx)
    x, _ = embed_input(params, cfg, batch, ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, new_caches, _ = tfm.apply_stack(
        params["stack"], cfg, x, ctx, positions=positions,
        caches=caches, enc_out=enc_out,
    )
    x = apply_norm(params["final_norm"], x)
    return x[:, -1:], new_caches


def init_paged_cache_tree(
    cfg: ModelConfig, num_slots: int, num_pages: int, page_size: int
) -> dict:
    """Serving cache tree: paged K/V pools for attention layers (shared
    across request slots, + trash page), per-slot recurrent state for
    RG-LRU/SSD layers.  Plain arrays (single-host serving — no shard specs).

    Encoder-decoder and vision-frontend archs are not servable through the
    paged engine (their prompts are not plain token streams)."""
    if cfg.is_encoder_decoder or cfg.frontend == "vision":
        raise ValueError(
            "paged serving supports decoder-only token models; "
            f"got frontend={cfg.frontend!r} enc-dec={cfg.is_encoder_decoder}"
        )
    period, n_full, rem = tfm.layer_plan(cfg)
    dt = jnp.dtype(cfg.dtype)

    def one(kind):
        if kind in ("global", "local"):
            mixer = PagedAttnCache.init(cfg, num_pages, page_size)
        elif kind == "rglru":
            mixer = RGLRUCache.init(cfg, num_slots, cfg.lru_width or cfg.d_model, dt)
        elif kind == "ssd":
            mixer = SSDCache.init(cfg, num_slots, d_inner(cfg), num_heads_ssm(cfg), dt)
        else:  # pragma: no cover
            raise ValueError(kind)
        return (mixer, None)

    caches: dict = {"scan": [], "rem": []}
    for pos, kind in enumerate(period):
        layers = [one(kind) for _ in range(n_full)]
        caches["scan"].append(tfm._stack_trees(layers) if n_full else None)
    for j in range(rem):
        caches["rem"].append(one(period[j]))
    return caches


def paged_prefill(
    params: PyTree, cfg: ModelConfig, tokens: jax.Array, caches: PyTree,
    view: PagedView, ctx: ShardCtx,
) -> tuple[jax.Array, PyTree]:
    """Prefill ONE request (tokens (1, S)) into the paged caches.

    ``view.block_tables`` is the single (1, MB) row of the slot being filled;
    attention scatters every prompt token's K/V into those pages while the
    attention itself runs over the fresh K/V (dispatched flash kernel,
    canonical positions).  Recurrent caches in ``caches`` must be batch-1
    scratch (the engine merges the final states into the slot afterwards).
    Returns (vocab-LOCAL logits of the last prompt position (1, 1, V/tp),
    new caches)."""
    x = embed_tokens(params["embed"], cfg, tokens, ctx)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if not cfg.use_rope:
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, new_caches, _ = tfm.apply_stack(
        params["stack"], cfg, x, ctx, positions=positions,
        caches=caches, paged=view,
    )
    x = apply_norm(params["final_norm"], x)
    return logits_sharded(params["embed"], cfg, x[:, -1:], ctx), new_caches


def paged_prefill_chunk(
    params: PyTree, cfg: ModelConfig, tokens: jax.Array, caches: PyTree,
    view: PagedView, ctx: ShardCtx, *, lengths: jax.Array, collect: bool = False,
) -> tuple[jax.Array, PyTree]:
    """One CHUNK of prefill for all R slots at once: tokens (R, C), with slot
    r's chunk starting at absolute position ``view.positions[r]`` and only its
    first ``lengths[r]`` tokens real (ragged tails scatter to the trash page
    and compute discarded garbage).  Recurrent caches must carry the states
    as of position ``view.positions[r]`` — chunk boundaries resume exactly.

    One fixed-C program serves every prompt-length mix; the engine walks long
    prompts through repeated calls, bumping ``view.positions`` by ``lengths``.

    ``collect=False`` (prefill): returns (vocab-LOCAL logits of each slot's
    LAST VALID position (R, 1, V/tp), new caches with carried final states).
    ``collect=True`` (speculative verify): attention + recurrences run
    per-token BITWISE-identical to decode steps, and returns (logits for all
    C positions (R, C, V/tp), caches whose recurrent leaves carry the full
    per-token state trajectory (B, C, ...) for accept-prefix selection)."""
    x = embed_tokens(params["embed"], cfg, tokens, ctx)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    positions = view.positions[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    if not cfg.use_rope:
        table = sinusoidal_positions(2**15, cfg.d_model).astype(x.dtype)
        x = x + jnp.take(table, jnp.clip(positions, 0, 2**15 - 1), axis=0)
    x, new_caches, _ = tfm.apply_stack(
        params["stack"], cfg, x, ctx, positions=positions,
        caches=caches, paged=view, chunk_lengths=lengths, chunk_exact=collect,
    )
    x = apply_norm(params["final_norm"], x)
    if collect:
        return logits_sharded(params["embed"], cfg, x, ctx), new_caches
    sel = jnp.clip(lengths - 1, 0, x.shape[1] - 1)[:, None, None]
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to(sel, (x.shape[0], 1, x.shape[2])), axis=1
    )
    return logits_sharded(params["embed"], cfg, x_last, ctx), new_caches


def paged_decode_step(
    params: PyTree, cfg: ModelConfig, tokens: jax.Array, caches: PyTree,
    view: PagedView, ctx: ShardCtx,
) -> tuple[jax.Array, PyTree]:
    """One decode step for ALL request slots at once: tokens (R, 1), per-slot
    positions/activity in ``view``.  Inactive slots compute garbage that goes
    to the trash page / gets overwritten at admission — no conditionals in
    the hot path.  Returns (vocab-LOCAL logits (R, 1, V/tp), new caches)."""
    x = embed_tokens(params["embed"], cfg, tokens, ctx)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if not cfg.use_rope:
        table = sinusoidal_positions(2**15, cfg.d_model).astype(x.dtype)
        rows = jnp.take(table, jnp.clip(view.positions, 0, 2**15 - 1), axis=0)
        x = x + rows[:, None]
    x, new_caches, _ = tfm.apply_stack(
        params["stack"], cfg, x, ctx, positions=view.positions[:, None],
        caches=caches, decode=True, paged=view,
    )
    x = apply_norm(params["final_norm"], x)
    return logits_sharded(params["embed"], cfg, x, ctx), new_caches


def decode_step(
    params: PyTree, cfg: ModelConfig, tokens: jax.Array, index: jax.Array,
    caches: PyTree, ctx: ShardCtx,
) -> tuple[jax.Array, PyTree]:
    """One-token decode: tokens (B, 1), index = #tokens already in cache.
    Returns (vocab-LOCAL logits (B, 1, V/tp), new caches)."""
    x = embed_tokens(params["embed"], cfg, tokens, ctx)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if not cfg.use_rope:
        table = sinusoidal_positions(2**15, cfg.d_model).astype(x.dtype)
        row = jax.lax.dynamic_slice_in_dim(table, jnp.clip(index, 0, 2**15 - 1), 1, 0)
        x = x + row[None]
    positions = index[None] if index.ndim == 0 else index
    x, new_caches, _ = tfm.apply_stack(
        params["stack"], cfg, x, ctx, positions=positions,
        caches=caches, decode=True,
    )
    x = apply_norm(params["final_norm"], x)
    return logits_sharded(params["embed"], cfg, x, ctx), new_caches
