"""Composable decoder (and encoder-decoder) LM assembly for all 10 assigned
architectures.

Layers are grouped into *pattern cycles* (e.g. gemma3's [5x local, 1x global])
and scanned with ``jax.lax.scan`` + ``jax.checkpoint`` — one traced instance
per pattern position regardless of depth, which keeps 512-device dry-run
compiles tractable and makes per-layer HLO collective accounting exact.
Layers that do not fill a whole cycle ("rest") run unscanned.

Params layout:
    embed                (V, D)
    dense                leading dense layers (cfg.first_dense_layers; only
                         present when there are any), unscanned
    cycles               list over pattern positions; leaves stacked (NC, ...)
    rest                 list of per-layer params (len = num_layers % len(pattern))
    final_norm
    unembed              (D, V) unless cfg.tie_embeddings
    encoder              same structure again for enc-dec archs (whisper)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN_CROSS,
    ATTN_GLOBAL,
    ATTN_LOCAL,
    ATTN_MLA,
    MLSTM,
    RGLRU,
    SLSTM,
    ArchConfig,
)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, dense, embed_init, init_mlp, init_norm

PyTree = Any

_ATTN_KINDS = (ATTN_GLOBAL, ATTN_LOCAL, ATTN_CROSS, ATTN_MLA)


def _pin_spec(x: jax.Array, batch_axes, spec_tail) -> jax.Array:
    """with_sharding_constraint(P(batch_axes, *spec_tail)) when axes are set."""
    if batch_axes is None:
        return x
    from jax.sharding import PartitionSpec as P_

    return jax.lax.with_sharding_constraint(x, P_(batch_axes, *spec_tail))


def _pin_batch(x: jax.Array, batch_axes, seq_axis=None, seq_axis_size=0) -> jax.Array:
    """Pin dim 0 (batch) — and optionally dim 1 (sequence) — of an activation.

    GSPMD propagation can drop the batch sharding through the vocab-sharded
    embedding gather (observed: fully replicated (B,S,D) activations on the
    16x16 mesh); pinning at block boundaries keeps every layer's activations
    batch-sharded.  ``seq_axis`` additionally applies sequence parallelism to
    the residual stream.  No-op when batch_axes is None (single-device tests).
    """
    if batch_axes is None:
        return x
    from jax.sharding import PartitionSpec as P_

    tail = [None] * (x.ndim - 1)
    if (
        seq_axis is not None
        and x.ndim >= 3
        and seq_axis_size > 1
        and x.shape[1] % seq_axis_size == 0
    ):
        tail[0] = seq_axis
    spec = P_(batch_axes, *tail)
    return jax.lax.with_sharding_constraint(x, spec)


# ===========================================================================
# blocks
# ===========================================================================
def _has_mlp(kind: str, cfg: ArchConfig) -> bool:
    return kind in _ATTN_KINDS and cfg.d_ff > 0


def init_block(rng, kind: str, cfg: ArchConfig, dtype, *, decoder_cross: bool = False,
               dense_mlp: bool = False) -> Dict:
    """One block's params; ``dense_mlp`` gives an MoE config's leading dense
    layer its dense MLP (width ``d_ff``)."""
    r1, r2, r3, r4, r5 = jax.random.split(rng, 5)
    p: Dict = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind == ATTN_MLA:
        p["mixer"] = attn.init_mla(r1, cfg, dtype)
    elif kind in _ATTN_KINDS:
        p["mixer"] = attn.init_attention(r1, cfg, dtype)
    elif kind == MLSTM:
        p["mixer"] = ssm_mod.init_mlstm(r1, cfg, dtype)
    elif kind == SLSTM:
        p["mixer"] = ssm_mod.init_slstm(r1, cfg, dtype)
    elif kind == RGLRU:
        p["mixer"] = rglru_mod.init_rglru(r1, cfg, dtype)
    else:
        raise ValueError(f"unknown block kind {kind}")
    if decoder_cross:
        p["norm_x"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["cross"] = attn.init_attention(r4, cfg, dtype, cross=True)
    if _has_mlp(kind, cfg):
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if cfg.moe is not None and not dense_mlp:
            p["mlp"] = moe_mod.init_moe(r3, cfg, dtype)
        else:
            p["mlp"] = init_mlp(r3, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def _is_moe(mlp_params) -> bool:
    return "router" in mlp_params


def apply_block_train(
    params: Dict,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    encoder_out: Optional[jax.Array] = None,
    causal: bool = True,
    moe_capacity_factor: float | None = 1.25,
    moe_group_size: int | None = None,
    batch_axes=None,
    moe_expert_axis=None,
    mlstm_chunk: int = 256,
    mlstm_inner_axis=None,
    with_counts: bool = False,
):
    """Pre-norm residual block.  Returns (x, moe aux loss), and with
    ``with_counts`` the block's per-expert count of routed assignments
    (E,) (zeros where the block has no dropless MoE)."""
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((cfg.moe.num_experts if cfg.moe else 0,), jnp.float32)
    h = apply_norm(cfg.norm, params["norm1"], x)
    if kind == ATTN_MLA:
        mix = attn.mla_block(params["mixer"], h, positions, cfg)
    elif kind in _ATTN_KINDS:
        if causal:
            mix = attn.attention_block(params["mixer"], h, positions, cfg, local=(kind == ATTN_LOCAL))
        else:  # encoder self-attention (bidirectional)
            b, s = h.shape[:2]
            q, k, v = attn._project_qkv(params["mixer"], h, h, cfg, cross=False)
            q = attn.apply_rope(q, positions, cfg.rope_theta)
            k = attn.apply_rope(k, positions, cfg.rope_theta)
            out = attn.chunked_attention(q, k, v, positions, positions, causal=False, window=0)
            mix = out.reshape(b, s, -1) @ params["mixer"]["wo"]
    elif kind == MLSTM:
        mix = ssm_mod.apply_mlstm(params["mixer"], h, cfg, chunk=mlstm_chunk,
                                  inner_axis=mlstm_inner_axis, batch_axes=batch_axes)
    elif kind == SLSTM:
        mix = ssm_mod.apply_slstm(params["mixer"], h, cfg)
    elif kind == RGLRU:
        mix = rglru_mod.apply_rglru(params["mixer"], h, cfg)
    else:
        raise ValueError(kind)
    x = x + mix
    if "cross" in params:
        hx = apply_norm(cfg.norm, params["norm_x"], x)
        x = x + attn.attention_block(
            params["cross"], hx, positions, cfg, local=False, encoder_out=encoder_out
        )
    if "mlp" in params:
        h2 = apply_norm(cfg.norm, params["norm2"], x)
        if not _is_moe(params["mlp"]):
            mlp_out = apply_mlp(params["mlp"], h2, cfg.act)
        elif moe_mod.is_dropless(cfg.moe, moe_capacity_factor):
            mlp_out, aux, counts = moe_mod.apply_moe_dropless(params["mlp"], h2, cfg)
        else:
            mlp_out, aux = moe_mod.apply_moe(
                params["mlp"], h2, cfg, capacity_factor=moe_capacity_factor,
                group_size=moe_group_size, batch_axes=batch_axes,
                expert_axis=moe_expert_axis,
            )
        x = x + mlp_out
    return (x, aux, counts) if with_counts else (x, aux)


# --- caches ----------------------------------------------------------------
def init_block_cache(kind: str, cfg: ArchConfig, batch: int, cache_len: int, dtype) -> Dict:
    if kind == ATTN_MLA:
        return attn.init_mla_cache(cfg, batch, cache_len, dtype)
    if kind in _ATTN_KINDS:
        length = min(cache_len, cfg.window) if (kind == ATTN_LOCAL and cfg.window) else cache_len
        return attn.init_kv_cache(cfg, batch, length, dtype)
    if kind == MLSTM:
        return ssm_mod.init_mlstm_cache(cfg, batch)
    if kind == SLSTM:
        return ssm_mod.init_slstm_cache(cfg, batch)
    if kind == RGLRU:
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def apply_block_decode(
    params: Dict,
    kind: str,
    x_t: jax.Array,
    cache: Dict,
    position: jax.Array,
    cfg: ArchConfig,
    *,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Dict]:
    h = apply_norm(cfg.norm, params["norm1"], x_t)
    if kind == ATTN_MLA:
        mix, new_cache = attn.mla_decode_step(params["mixer"], h, cache, position, cfg)
    elif kind in _ATTN_KINDS:
        mix, new_cache = attn.attention_decode_step(
            params["mixer"], h, cache, position, cfg, local=(kind == ATTN_LOCAL)
        )
    elif kind == MLSTM:
        mix, new_cache = ssm_mod.mlstm_decode_step(params["mixer"], h, cache, cfg)
    elif kind == SLSTM:
        mix, new_cache = ssm_mod.slstm_decode_step(params["mixer"], h, cache, cfg)
    elif kind == RGLRU:
        mix, new_cache = rglru_mod.rglru_decode_step(params["mixer"], h, cache, cfg)
    else:
        raise ValueError(kind)
    x_t = x_t + mix
    if "cross" in params:
        hx = apply_norm(cfg.norm, params["norm_x"], x_t)
        out, _ = attn.attention_decode_step(
            params["cross"], hx, cache, position, cfg, local=False, cross_kv=cross_kv
        )
        x_t = x_t + out
    if "mlp" in params:
        h2 = apply_norm(cfg.norm, params["norm2"], x_t)
        if _is_moe(params["mlp"]):
            mlp_out, _ = moe_mod.apply_moe(params["mlp"], h2, cfg, capacity_factor=None)
        else:
            mlp_out = apply_mlp(params["mlp"], h2, cfg.act)
        x_t = x_t + mlp_out
    return x_t, new_cache


# ===========================================================================
# stack = scanned cycles + rest
# ===========================================================================
def _cycle_layout(num_layers: int, pattern: Tuple[str, ...]) -> Tuple[int, int]:
    plen = len(pattern)
    return num_layers // plen, num_layers % plen


def _init_stack(rng, cfg: ArchConfig, dtype, *, pattern, num_layers, decoder_cross=False,
                first_dense: int = 0) -> Dict:
    dense_params = [
        init_block(jax.random.fold_in(rng, 98_000 + i), pattern[i % len(pattern)], cfg, dtype,
                   decoder_cross=decoder_cross, dense_mlp=True)
        for i in range(first_dense)
    ]
    num_layers -= first_dense
    nc, rest = _cycle_layout(num_layers, pattern)
    cycles: List[PyTree] = []
    for pos, kind in enumerate(pattern):
        per_cycle = [
            init_block(
                jax.random.fold_in(rng, pos * 1000 + c), kind, cfg, dtype,
                decoder_cross=decoder_cross,
            )
            for c in range(nc)
        ]
        cycles.append(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_cycle)
            if nc > 0
            else None
        )
    rest_params = [
        init_block(
            jax.random.fold_in(rng, 99_000 + i), pattern[i], cfg, dtype,
            decoder_cross=decoder_cross,
        )
        for i in range(rest)
    ]
    out = {"cycles": cycles, "rest": rest_params}
    if first_dense:
        out["dense"] = dense_params
    return out


def _apply_stack_train(
    stack: Dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    pattern,
    causal: bool = True,
    encoder_out: Optional[jax.Array] = None,
    remat: bool = True,
    moe_capacity_factor: float | None = 1.25,
    moe_group_size: int | None = None,
    batch_axes=None,
    moe_expert_axis=None,
    mlstm_chunk: int = 256,
    mlstm_inner_axis=None,
    seq_axis=None,
    seq_axis_size=0,
    with_counts: bool = False,
):
    """Returns (x, aux loss), and with ``with_counts`` each layer's
    per-expert count of routed assignments, (num_layers, E), in the order
    the layers run (leading dense layers, scanned cycles, rest)."""
    aux_total = jnp.zeros((), jnp.float32)
    counts = []

    def block(pp, hh, kind):
        return apply_block_train(
            pp, kind, hh, positions, cfg, encoder_out=encoder_out, causal=causal,
            moe_capacity_factor=moe_capacity_factor,
            moe_group_size=moe_group_size, batch_axes=batch_axes,
            moe_expert_axis=moe_expert_axis, mlstm_chunk=mlstm_chunk,
            mlstm_inner_axis=mlstm_inner_axis, with_counts=with_counts,
        )

    def unscanned(p, kind):
        nonlocal x, aux_total
        blk = functools.partial(block, kind=kind)
        out = (jax.checkpoint(blk) if remat else blk)(p, x)
        x, aux_total = out[0], aux_total + out[1]
        if with_counts:
            counts.append(out[2][None])

    for i, p in enumerate(stack.get("dense", ())):
        unscanned(p, pattern[i % len(pattern)])

    def cycle_body(carry, cycle_params):
        h, aux = carry
        h = _pin_batch(h, batch_axes, seq_axis, seq_axis_size)
        cycle_counts = []
        for pos, kind in enumerate(pattern):
            out = block(cycle_params[pos], h, kind)
            h = _pin_batch(out[0], batch_axes, seq_axis, seq_axis_size)
            aux = aux + out[1]
            if with_counts:
                cycle_counts.append(out[2])
        return (h, aux), (jnp.stack(cycle_counts) if with_counts else None)

    body = jax.checkpoint(cycle_body) if remat else cycle_body
    if stack["cycles"] and stack["cycles"][0] is not None:
        (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), tuple(stack["cycles"]))
        if with_counts:
            counts.append(ys.reshape(-1, ys.shape[-1]))
    for i, p in enumerate(stack["rest"]):
        unscanned(p, pattern[i])
    if with_counts:
        return x, aux_total, jnp.concatenate(counts)
    return x, aux_total


def _init_stack_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype, *, pattern, num_layers,
                      first_dense: int = 0) -> Dict:
    dense_caches = [init_block_cache(pattern[i % len(pattern)], cfg, batch, cache_len, dtype)
                    for i in range(first_dense)]
    nc, rest = _cycle_layout(num_layers - first_dense, pattern)
    cycles = []
    for pos, kind in enumerate(pattern):
        per_cycle = [init_block_cache(kind, cfg, batch, cache_len, dtype) for _ in range(nc)]
        cycles.append(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_cycle) if nc else None
        )
    rest_caches = [init_block_cache(pattern[i], cfg, batch, cache_len, dtype) for i in range(rest)]
    out = {"cycles": cycles, "rest": rest_caches}
    if first_dense:
        out["dense"] = dense_caches
    return out


def _apply_stack_decode(
    stack: Dict,
    caches: Dict,
    x_t: jax.Array,
    position: jax.Array,
    cfg: ArchConfig,
    *,
    pattern,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Dict]:
    """cross_kv (enc-dec only, pattern length 1): (k, v) stacked (NC, B, F, H, hd)."""
    # start from the incoming cycles list so the [None]*len(pattern)
    # placeholders of a cycle-less stack (NC=0) survive and the returned cache
    # treedef always matches init_cache's
    new_caches = {"cycles": list(caches["cycles"]), "rest": []}
    if "dense" in stack:
        new_caches["dense"] = []
        for i, p in enumerate(stack["dense"]):
            x_t, nc_ = apply_block_decode(
                p, pattern[i % len(pattern)], x_t, caches["dense"][i], position, cfg
            )
            new_caches["dense"].append(nc_)

    if stack["cycles"] and stack["cycles"][0] is not None:
        have_cross = cross_kv is not None
        xs = (tuple(stack["cycles"]), tuple(caches["cycles"]))
        if have_cross:
            xs = xs + (cross_kv,)

        def cycle_body(h, xs_):
            if have_cross:
                cycle_params, cycle_cache, ckv_cycle = xs_
            else:
                cycle_params, cycle_cache = xs_
                ckv_cycle = None
            new_cc = []
            for pos, kind in enumerate(pattern):
                h, nc_ = apply_block_decode(
                    cycle_params[pos], kind, h, cycle_cache[pos], position, cfg,
                    cross_kv=ckv_cycle,
                )
                new_cc.append(nc_)
            return h, tuple(new_cc)

        x_t, new_cycle_caches = jax.lax.scan(cycle_body, x_t, xs)
        new_caches["cycles"] = list(new_cycle_caches)
    for i, p in enumerate(stack["rest"]):
        x_t, nc_ = apply_block_decode(
            p, pattern[i], x_t, caches["rest"][i], position, cfg, cross_kv=None
        )
        new_caches["rest"].append(nc_)
    return x_t, new_caches


# ===========================================================================
# the model
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class TransformerLM:
    cfg: ArchConfig
    remat: bool = True
    # MoE capacity factor for full-sequence (train/prefill) passes; the decode
    # path is always drop-free (capacity_factor=None).
    moe_capacity_factor: float | None = 1.25
    # MoE dispatch-group size (Switch/Mesh-TF-style).  Ungrouped (None)
    # dispatch is quadratic in per-device tokens — §Perf records the
    # catastrophic ungrouped baseline; 2048 is the production default.
    moe_group_size: int | None = 2048
    # expert-parallel pinning axis for MoE buffers (set with the matching
    # sharding-policy flag; requires num_experts % axis size == 0)
    moe_expert_axis: Optional[str] = None
    # chunkwise-mLSTM chunk length (state-op amortization vs quadratic term)
    mlstm_chunk: int = 256
    # mesh axis for the mLSTM matrix-memory v-side dim (see ssm.apply_mlstm)
    mlstm_inner_axis: Optional[str] = None
    # sequence-chunk size for the gather-free chunked cross-entropy
    loss_chunk: int = 256
    # mesh axes the batch dim of activations is pinned to via
    # with_sharding_constraint (None = no constraints; set by the launcher)
    batch_axes: Optional[Tuple[str, ...]] = None
    # Megatron-style sequence parallelism: shard the S dim of the residual
    # stream over this axis at block boundaries (scan carries shrink by the
    # axis size; blocks re-gather internally).  Applied only when S divides
    # seq_axis_size.  Set by the launcher for train/prefill.
    seq_axis: Optional[str] = None
    seq_axis_size: int = 0

    @property
    def dtype(self):
        return jnp.dtype(self.cfg.dtype)

    @property
    def act_dtype(self):
        """The residual stream's dtype (``cfg.residual_dtype``, else the
        weights')."""
        return jnp.dtype(self.cfg.residual_dtype or self.cfg.dtype)

    # -- params -------------------------------------------------------------
    def init(self, rng: jax.Array) -> PyTree:
        cfg = self.cfg
        dtype = self.dtype
        r_emb, r_dec, r_enc, r_un = jax.random.split(rng, 4)
        params: Dict = {"embed": embed_init(r_emb, cfg.vocab_size, cfg.d_model, dtype)}
        params["decoder"] = _init_stack(
            r_dec, cfg, dtype, pattern=cfg.pattern, num_layers=cfg.num_layers,
            decoder_cross=cfg.is_encdec, first_dense=cfg.first_dense_layers,
        )
        params["final_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(r_un, cfg.vocab_size, cfg.d_model, dtype).T
        if cfg.is_encdec:
            params["encoder"] = _init_stack(
                r_enc, cfg, dtype, pattern=(ATTN_GLOBAL,), num_layers=cfg.encoder_layers
            )
            params["encoder_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
        return params

    # -- encoder ------------------------------------------------------------
    def encode(self, params: PyTree, frames: jax.Array) -> jax.Array:
        """frames: (B, F, D) precomputed frontend embeddings (stub carve-out)."""
        cfg = self.cfg
        b, f, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(f)[None], (b, f))
        h, _ = _apply_stack_train(
            params["encoder"], _pin_batch(frames.astype(self.dtype), self.batch_axes), pos, cfg,
            pattern=(ATTN_GLOBAL,), causal=False, remat=self.remat,
            batch_axes=self.batch_axes,
            seq_axis=self.seq_axis, seq_axis_size=self.seq_axis_size,
        )
        return apply_norm(cfg.norm, params["encoder_norm"], h)

    # -- full-sequence forward (train / prefill) -----------------------------
    def hidden(self, params: PyTree, batch: Dict[str, jax.Array], *, with_counts: bool = False):
        """Final-norm hidden states (B, S_total, D) + moe aux loss, and with
        ``with_counts`` each decoder layer's per-expert count of routed
        assignments (num_layers, E)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b = tokens.shape[0]
        h = params["embed"][tokens].astype(self.act_dtype)
        if cfg.image_tokens and "image_emb" in batch:
            h = jnp.concatenate([batch["image_emb"].astype(self.act_dtype), h], axis=1)
        h = _pin_batch(h, self.batch_axes)
        s = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        encoder_out = None
        if cfg.is_encdec:
            encoder_out = self.encode(params, batch["frames"])
        out = _apply_stack_train(
            params["decoder"], h, positions, cfg,
            pattern=cfg.pattern, causal=True, encoder_out=encoder_out, remat=self.remat,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_group_size=self.moe_group_size, batch_axes=self.batch_axes,
            moe_expert_axis=self.moe_expert_axis, mlstm_chunk=self.mlstm_chunk,
            mlstm_inner_axis=self.mlstm_inner_axis,
            seq_axis=self.seq_axis, seq_axis_size=self.seq_axis_size,
            with_counts=with_counts,
        )
        h = apply_norm(cfg.norm, params["final_norm"], out[0])
        h = _pin_batch(h, self.batch_axes, self.seq_axis, self.seq_axis_size)
        return (h, *out[1:])

    def forward(self, params: PyTree, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
        """Returns (logits (B, S, V), moe aux loss).  Materializes full logits —
        fine at test scale; the training loss uses the chunked path instead."""
        h, aux = self.hidden(params, batch)
        logits = self.unembed(params, h)
        if self.cfg.image_tokens and "image_emb" in batch:
            logits = logits[:, batch["image_emb"].shape[1] :]
        return logits, aux

    def unembed(self, params: PyTree, h: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            return dense(h, params["embed"].T)
        return dense(h, params["unembed"])

    # -- loss ---------------------------------------------------------------
    def loss(self, params: PyTree, batch: Dict[str, jax.Array]) -> jax.Array:
        """Sequence-chunked softmax cross-entropy.

        Never materializes (B, S, V) logits: each S-chunk computes its
        vocab-sharded logits, reduces logsumexp over V (a psum under GSPMD —
        no all-gather), and contracts the gold logit with a one-hot instead of
        a gather along the sharded vocab axis (gathers along a sharded dim
        force replication; the one-hot contraction is a sharded reduction).
        ``jax.checkpoint`` on the chunk body keeps the backward pass at the
        same peak memory.
        """
        h, aux = self.hidden(params, batch)
        return self._chunked_nll(params, h, batch) + aux

    def loss_and_counts(self, params: PyTree, batch: Dict[str, jax.Array]):
        """``loss``, and each decoder layer's per-expert count of routed
        assignments (num_layers, E) (zero rows for layers without a
        dropless MoE)."""
        h, aux, counts = self.hidden(params, batch, with_counts=True)
        return self._chunked_nll(params, h, batch) + aux, counts

    def _chunked_nll(self, params: PyTree, h: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        if cfg.image_tokens and "image_emb" in batch:
            h = h[:, batch["image_emb"].shape[1] :]
        labels = batch["labels"].astype(jnp.int32)
        b, s, d = h.shape
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        chunk = min(self.loss_chunk, s)
        pad = (-s) % chunk
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        n_chunks = (s + pad) // chunk
        h_c = h.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
        y_c = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

        vocab_ax = "model" if cfg.vocab_size % 16 == 0 else None

        @jax.checkpoint
        def chunk_nll(carry, xs):
            hc, yc = xs
            hc = _pin_batch(hc, self.batch_axes)
            logits = dense(hc, w).astype(jnp.float32)              # (B, C, V)
            # keep the vocab axis model-sharded: logsumexp is then a sharded
            # reduction (psum under GSPMD), never an all-gather
            logits = _pin_spec(logits, self.batch_axes, (None, vocab_ax))
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            # gold logit via a row-gather from the unembedding instead of a
            # (B, C, V) one-hot: w.T is (V, D); the sharded-gather lowering is
            # mask+psum over the V shards at O(B*C*D) cost
            gold_rows = jnp.take(w.T, jnp.clip(yc, 0, cfg.vocab_size - 1), axis=0)
            gold = jnp.sum(hc.astype(jnp.float32) * gold_rows.astype(jnp.float32), axis=-1)
            valid = (yc >= 0).astype(jnp.float32)
            return carry + jnp.sum((logz - gold) * valid), None

        total, _ = jax.lax.scan(chunk_nll, jnp.zeros((), jnp.float32), (h_c, y_c))
        return total / (b * s)

    # -- decode ---------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int) -> PyTree:
        cfg = self.cfg
        cache = _init_stack_cache(
            cfg, batch, cache_len, self.dtype, pattern=cfg.pattern, num_layers=cfg.num_layers,
            first_dense=cfg.first_dense_layers,
        )
        return cache

    def make_cross_kv(self, params: PyTree, encoder_out: jax.Array):
        """Precompute per-layer cross-attention K/V from the encoder output.

        Enc-dec archs use a single-kind pattern (whisper), so the decoder
        stack is one scanned cycle group; returns (k, v) with leading dim NC.
        """
        cfg = self.cfg
        if len(cfg.pattern) != 1:
            raise NotImplementedError("enc-dec requires a single-kind pattern")
        h, hd = cfg.num_heads, cfg.resolved_head_dim  # cross attn is MHA
        b, f, _ = encoder_out.shape

        def per_block(bp):
            k = encoder_out @ bp["cross"]["wk"]
            v = encoder_out @ bp["cross"]["wv"]
            if "bk" in bp["cross"]:
                k = k + bp["cross"]["bk"]
                v = v + bp["cross"]["bv"]
            return k.reshape(b, f, h, hd), v.reshape(b, f, h, hd)

        return jax.vmap(per_block)(params["decoder"]["cycles"][0])

    def decode_step(
        self,
        params: PyTree,
        tokens: jax.Array,         # (B, 1)
        cache: PyTree,
        position: jax.Array,       # scalar int32
        cross_kv=None,
    ) -> Tuple[jax.Array, PyTree]:
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)
        x, new_cache = _apply_stack_decode(
            params["decoder"], cache, x, position, cfg,
            pattern=cfg.pattern, cross_kv=cross_kv,
        )
        x = apply_norm(cfg.norm, params["final_norm"], x)
        logits = self.unembed(params, x)
        return logits, new_cache
