"""Generic decoder stack: assembles any ArchConfig into init/forward/decode.

Handles every assigned architecture through three mechanisms:

* **block dispatch** — each layer is ``attn`` (GQA or MLA), ``ssm`` (Mamba2)
  or ``rglru`` (Griffin), chosen by ``cfg.block_kind(i)``; the MLP half is a
  dense MLP or an MoE depending on the layer index.

* **scan grouping** — layer stacks are compiled as
  ``prefix (unrolled) + lax.scan over n_groups × period + suffix``.
  The period is the architecture's repeating unit (gemma3: 6 = 5 local +
  1 global; recurrentgemma: 3 = 2 RG-LRU + attn; dsv3: prefix 3 dense then
  period 1 MoE).  This keeps HLO size O(period), not O(num_layers) — at 88
  layers (mistral-large) or 61 (dsv3) that is the difference between a
  30-second and a 30-minute 512-way SPMD compile.  ``jax.checkpoint`` on the
  group body gives standard per-layer activation rematerialisation.

* **cache pytrees** — decode caches mirror the same prefix/scan/suffix
  structure so one ``lax.scan`` carries both stacked params and stacked
  caches.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import griffin, layers, mla as mla_lib, moe as moe_lib, ssm as ssm_lib

__all__ = ["LayerPlan", "plan_layers", "init_model", "forward",
           "init_decode_caches", "Batch"]

Batch = dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Scan planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    prefix: int      # leading layers, unrolled
    period: int      # repeating-unit length
    n_groups: int    # scanned repetitions
    suffix: int      # trailing layers, unrolled

    @property
    def total(self) -> int:
        return self.prefix + self.period * self.n_groups + self.suffix


def _kind_key(cfg: ArchConfig, i: int) -> tuple:
    moe_layer = cfg.moe is not None and i >= cfg.moe.first_dense_layers
    return (cfg.block_kind(i), cfg.is_local_layer(i), moe_layer,
            cfg._layer_d_ff(i))


def plan_layers(cfg: ArchConfig, num_layers: int | None = None) -> LayerPlan:
    """Choose (prefix, period, n_groups, suffix) for the layer stack.

    Minimises (unrolled layers, period): e.g. gemma3 → period 6, dsv3 →
    prefix 3 + period 1, recurrentgemma 38L → period 3 with a 2-layer suffix.
    """
    n = num_layers if num_layers is not None else cfg.num_layers
    kinds = [_kind_key(cfg, i) for i in range(n)]
    best = LayerPlan(0, 1, 0, n)  # fully unrolled fallback
    best_score = (n, 99)
    for prefix in range(0, min(4, n)):
        for period in range(1, 9):
            if n - prefix < 2 * period:
                continue
            unit = kinds[prefix: prefix + period]
            i = prefix
            groups = 0
            while i + period <= n and kinds[i: i + period] == unit:
                groups += 1
                i += period
            if groups < 2:
                continue
            plan = LayerPlan(prefix, period, groups, n - i)
            score = (plan.prefix + plan.suffix, period)
            if score < best_score:
                best, best_score = plan, score
    assert best.total == n, (best, n)
    return best


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------


def init_block(key, cfg: ArchConfig, layer_idx: int,
               cross: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    d, dtype = cfg.d_model, cfg.param_dtype
    kind = cfg.block_kind(layer_idx)
    p: dict[str, Any] = {"norm1": layers.init_rms_norm(d, dtype)}

    if kind == "attn":
        if cfg.attention_kind == "mla":
            p["attn"] = mla_lib.init_mla(ks[0], d, cfg.num_heads, cfg.mla,
                                         dtype)
        else:
            p["attn"] = attn_lib.init_attention(
                ks[0], d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                bias=cfg.qkv_bias, dtype=dtype)
    elif kind == "ssm":
        p["mixer"] = ssm_lib.init_mamba2(ks[0], d, cfg.ssm, dtype)
        return p  # pure mamba stack: no separate MLP half
    elif kind == "rglru":
        p["mixer"] = griffin.init_rglru_block(ks[0], d, cfg.d_ff_rglru,
                                              dtype=dtype)
    else:
        raise ValueError(f"unknown block kind {kind!r}")

    if cross:
        p["cross_norm"] = layers.init_rms_norm(d, dtype)
        p["cross_attn"] = attn_lib.init_attention(
            ks[1], d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            dtype=dtype)

    p["norm2"] = layers.init_rms_norm(d, dtype)
    if cfg.moe is not None and layer_idx >= cfg.moe.first_dense_layers:
        p["moe"] = moe_lib.init_moe(ks[2], d, cfg.moe, dtype)
    else:
        p["mlp"] = layers.init_mlp(ks[2], d, cfg._layer_d_ff(layer_idx),
                                   cfg.mlp_kind, dtype)
    return p


def _layer_window(cfg: ArchConfig, layer_idx: int,
                  long_variant: bool) -> int:
    """Effective attention window for this layer (0 ⇒ full causal)."""
    if cfg.sliding_window > 0 and cfg.is_local_layer(layer_idx):
        return cfg.sliding_window
    if long_variant and cfg.long_context_window > 0:
        return cfg.long_context_window
    return 0


def apply_block(params: dict, x: jax.Array, positions: jax.Array,
                cfg: ArchConfig, layer_idx: int, *,
                mrope_positions: jax.Array | None = None,
                enc_out: jax.Array | None = None,
                cache: dict | None = None,
                long_variant: bool = False,
                causal: bool = True,
                impl: str = "xla"):
    """One block.  Returns (x, new_cache, aux_loss)."""
    kind = cfg.block_kind(layer_idx)
    cdt = cfg.compute_dtype
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    h = layers.rms_norm(params["norm1"], x, cfg.norm_eps)

    if kind == "attn":
        window = _layer_window(cfg, layer_idx, long_variant)
        self_cache = cache.get("self") if cache else None
        if cfg.attention_kind == "mla":
            y, c = mla_lib.mla_attention(
                params["attn"], h, positions, num_heads=cfg.num_heads,
                cfg=cfg.mla, rope_theta=cfg.rope_theta, window=window,
                cache=self_cache, tp_axis=cfg.tp_axis_name,
                batch_axis=cfg.batch_axis_name, compute_dtype=cdt)
        else:
            y, c = attn_lib.attention(
                params["attn"], h, positions, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, window=window,
                rope_kind=cfg.rope_kind, rope_theta=cfg.rope_theta,
                mrope_positions=mrope_positions, cache=self_cache,
                causal=causal, compute_dtype=cdt,
                weight_gather=cfg.attn_weight_gather,
                batch_axis=cfg.batch_axis_name,
                chunked_prefill=cfg.attn_chunked_prefill, impl=impl)
        if c is not None:
            new_cache["self"] = c
        x = x + y
    elif kind == "ssm":
        y, c = ssm_lib.mamba2_block(params["mixer"], h, cfg.ssm,
                                    cache=cache.get("self") if cache else None,
                                    compute_dtype=cdt,
                                    use_pallas=(impl == "pallas"),
                                    eps=cfg.norm_eps)
        if c is not None:
            new_cache["self"] = c
        return x + y, (new_cache or None), aux
    elif kind == "rglru":
        y, c = griffin.rglru_block(params["mixer"], h,
                                   cache=cache.get("self") if cache else None,
                                   compute_dtype=cdt,
                                   use_pallas=(impl == "pallas"))
        if c is not None:
            new_cache["self"] = c
        x = x + y

    if "cross_attn" in params:
        assert enc_out is not None
        hc = layers.rms_norm(params["cross_norm"], x, cfg.norm_eps)
        y, _ = attn_lib.attention(
            params["cross_attn"], hc, positions,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            rope_kind="none", kv_override=enc_out, causal=False,
            compute_dtype=cdt,
            chunked_prefill=cfg.attn_chunked_prefill)
        x = x + y

    h2 = layers.rms_norm(params["norm2"], x, cfg.norm_eps)
    if "moe" in params:
        y, aux_l = moe_lib.moe_layer(params["moe"], h2, cfg.moe,
                                     compute_dtype=cdt,
                                     ep_axis=cfg.tp_axis_name)
        aux = aux + aux_l
    else:
        y = layers.mlp(params["mlp"], h2, cfg.mlp_kind, compute_dtype=cdt)
    return x + y, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Layer stack (prefix + scan + suffix)
# ---------------------------------------------------------------------------


def _init_stack(key, cfg: ArchConfig, num_layers: int,
                cross: bool = False) -> dict:
    plan = plan_layers(cfg, num_layers)
    params: dict[str, Any] = {}
    keys = jax.random.split(key, num_layers)
    for i in range(plan.prefix):
        params[f"pre_{i}"] = init_block(keys[i], cfg, i, cross)
    if plan.n_groups:
        def init_group(gkey):
            gks = jax.random.split(gkey, plan.period)
            return {f"sub_{j}": init_block(gks[j], cfg, plan.prefix + j,
                                           cross)
                    for j in range(plan.period)}
        gkeys = jax.random.split(jax.random.fold_in(key, 1), plan.n_groups)
        params["scan"] = jax.vmap(init_group)(gkeys)
    for i in range(plan.suffix):
        li = plan.prefix + plan.period * plan.n_groups + i
        params[f"suf_{i}"] = init_block(keys[li], cfg, li, cross)
    return params


def _apply_stack(params: dict, x: jax.Array, positions: jax.Array,
                 cfg: ArchConfig, num_layers: int, *,
                 caches: dict | None = None,
                 mrope_positions=None, enc_out=None,
                 long_variant=False, causal=True, impl="xla",
                 remat: bool = True):
    plan = plan_layers(cfg, num_layers)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict[str, Any] = {}
    decode = caches is not None

    for i in range(plan.prefix):
        x, c, aux = apply_block(
            params[f"pre_{i}"], x, positions, cfg, i,
            mrope_positions=mrope_positions, enc_out=enc_out,
            cache=caches.get(f"pre_{i}") if decode else None,
            long_variant=long_variant, causal=causal, impl=impl)
        aux_total += aux
        if c is not None:
            new_caches[f"pre_{i}"] = c

    if plan.n_groups:
        def group_body(carry, scanned):
            xx = carry
            gparams, gcache = scanned
            gnew = {}
            gaux = jnp.zeros((), jnp.float32)
            for j in range(plan.period):
                li = plan.prefix + j  # kind-equivalent layer index
                xx, c, aux = apply_block(
                    gparams[f"sub_{j}"], xx, positions, cfg, li,
                    mrope_positions=mrope_positions, enc_out=enc_out,
                    cache=gcache[f"sub_{j}"] if decode else None,
                    long_variant=long_variant, causal=causal, impl=impl)
                gaux += aux
                if c is not None:
                    gnew[f"sub_{j}"] = c
            return xx, (gnew, gaux)

        body = jax.checkpoint(group_body) if remat and not decode \
            else group_body
        if not decode:
            x, (gc, gaux) = jax.lax.scan(
                lambda carry, p: body(carry, (p, None)), x, params["scan"])
        else:
            x, (gc, gaux) = jax.lax.scan(body, x,
                                         (params["scan"], caches["scan"]))
            if gc:
                new_caches["scan"] = gc
        aux_total += gaux.sum()

    for i in range(plan.suffix):
        li = plan.prefix + plan.period * plan.n_groups + i
        x, c, aux = apply_block(
            params[f"suf_{i}"], x, positions, cfg, li,
            mrope_positions=mrope_positions, enc_out=enc_out,
            cache=caches.get(f"suf_{i}") if decode else None,
            long_variant=long_variant, causal=causal, impl=impl)
        aux_total += aux
        if c is not None:
            new_caches[f"suf_{i}"] = c

    return x, (new_caches or None), aux_total


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def init_model(key, cfg: ArchConfig) -> dict:
    ke, ks, kh, kenc = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": layers.init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                       cfg.param_dtype),
        "stack": _init_stack(ks, cfg, cfg.num_layers,
                             cross=cfg.is_encoder_decoder),
        "final_norm": layers.init_rms_norm(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = layers.init_dense(
            kh, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
    if cfg.is_encoder_decoder:
        params["enc_stack"] = _init_stack(kenc, cfg, cfg.encoder_layers,
                                          cross=False)
        params["enc_norm"] = layers.init_rms_norm(cfg.d_model,
                                                  cfg.param_dtype)
    return params


def _embed_inputs(params, cfg: ArchConfig, batch: Batch) -> jax.Array:
    x = layers.embed(params["embed"], batch["tokens"],
                     compute_dtype=cfg.compute_dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype)
    if cfg.frontend == "vision" and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(cfg.compute_dtype)
        npos = fe.shape[1]
        x = jnp.concatenate([fe, x[:, npos:]], axis=1)
    return x


def _encode(params, cfg: ArchConfig, batch: Batch, impl: str):
    """Audio encoder (frontend-stub frame embeddings → encoder stack)."""
    enc_x = batch["enc_embeds"].astype(cfg.compute_dtype)
    pos = jnp.broadcast_to(jnp.arange(enc_x.shape[1])[None],
                           enc_x.shape[:2])
    enc_x, _, _ = _apply_stack(params["enc_stack"], enc_x, pos, cfg,
                               cfg.encoder_layers, causal=False, impl=impl)
    return layers.rms_norm(params["enc_norm"], enc_x, cfg.norm_eps)


def forward(params: dict, batch: Batch, cfg: ArchConfig, *,
            caches: dict | None = None,
            enc_out: jax.Array | None = None,
            long_variant: bool = False,
            impl: str = "xla",
            remat: bool = True):
    """Full forward pass.

    Args:
      batch: {'tokens' (B,S), 'positions' (B,S), optional 'mrope_positions'
        (3,B,S), 'frontend_embeds' (B,P,d), 'enc_embeds' (B,T,d)}.
      caches: decode caches (None ⇒ prefill/training).
      enc_out: precomputed encoder memory (decode); if None and the arch is
        enc-dec, the encoder runs here.

    Returns:
      (logits (B,S,V), aux_loss, new_caches, enc_out)
    """
    if cfg.is_encoder_decoder and enc_out is None:
        enc_out = _encode(params, cfg, batch, impl)

    x = _embed_inputs(params, cfg, batch)
    positions = batch["positions"]
    x, new_caches, aux = _apply_stack(
        params["stack"], x, positions, cfg, cfg.num_layers,
        caches=caches, mrope_positions=batch.get("mrope_positions"),
        enc_out=enc_out, long_variant=long_variant, impl=impl, remat=remat)
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.batch_axis_name is not None:
        # serving: re-pin the residual to batch-sharded/d-replicated before
        # the unembed — sharding churn from row-parallel attention outputs
        # otherwise leaves x d-sharded+batch-replicated here, and the head
        # dot partial-sums a full-batch f32 (B,S,V/16) tensor (67 GB/device
        # at a 256k vocab; §Perf iteration B4)
        from jax.sharding import PartitionSpec as _P
        x = jax.lax.with_sharding_constraint(
            x, _P(cfg.batch_axis_name, None, None))
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"]["table"].astype(x.dtype))
    else:
        logits = layers.unembed(params["head"], x,
                                compute_dtype=cfg.compute_dtype)
    if cfg.logit_softcap > 0:
        cap = cfg.logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits, aux, new_caches, enc_out


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def _block_cache(cfg: ArchConfig, layer_idx: int, batch: int, cache_len: int,
                 long_variant: bool, dtype) -> dict | None:
    kind = cfg.block_kind(layer_idx)
    if kind == "ssm":
        return {"self": ssm_lib.init_mamba2_cache(batch, cfg.d_model,
                                                  cfg.ssm)}
    if kind == "rglru":
        return {"self": griffin.init_rglru_cache(batch, cfg.d_ff_rglru)}
    window = _layer_window(cfg, layer_idx, long_variant)
    eff_len = min(cache_len, window) if window > 0 else cache_len
    if cfg.attention_kind == "mla":
        return {"self": mla_lib.init_mla_cache(batch, eff_len, cfg.mla,
                                               dtype)}
    return {"self": attn_lib.init_cache(batch, eff_len, cfg.num_kv_heads,
                                        cfg.head_dim, dtype)}


def init_decode_caches(cfg: ArchConfig, batch: int, cache_len: int, *,
                       long_variant: bool = False,
                       dtype=jnp.bfloat16) -> dict:
    """Build the cache pytree mirroring the stack's prefix/scan/suffix."""
    plan = plan_layers(cfg, cfg.num_layers)
    caches: dict[str, Any] = {}
    for i in range(plan.prefix):
        caches[f"pre_{i}"] = _block_cache(cfg, i, batch, cache_len,
                                          long_variant, dtype)
    if plan.n_groups:
        def one_group(_):
            return {f"sub_{j}": _block_cache(cfg, plan.prefix + j, batch,
                                             cache_len, long_variant, dtype)
                    for j in range(plan.period)}
        group = one_group(0)
        caches["scan"] = jax.tree.map(
            lambda leaf: jnp.broadcast_to(
                leaf[None], (plan.n_groups,) + leaf.shape).copy(), group)
    for i in range(plan.suffix):
        li = plan.prefix + plan.period * plan.n_groups + i
        caches[f"suf_{i}"] = _block_cache(cfg, li, batch, cache_len,
                                          long_variant, dtype)
    return caches
