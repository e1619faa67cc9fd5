"""Multi-exit decoder stack — the model substrate the SplitEE policy runs on.

Layers are *stacked* along a leading axis and iterated with ``lax.scan``
(O(1) HLO size in depth — mirrors the paper's "one hardware module reused
per layer" observation and keeps 512-device dry-run compiles tractable).

Per-layer exit observables are collected as scan outputs: the pooled hidden
state after every layer (tiny: (L, B, D)), from which exit confidences are
computed *post-scan* in one batched matmul / fused Pallas confidence call —
so SplitEE (single exit check) and SplitEE-S (all exits) share one forward.

Families: dense (llama/qwen/granite), moe (mixtral/phi), ssm (rwkv6),
hybrid (zamba2: mamba2 backbone + one shared attention block every k
layers). Enc-dec (seamless) wraps this module — see encdec.py.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.exit_confidence.ops import (exit_confidence,
                                               exit_confidence_fused)
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import mlp as ff
from repro.models import rwkv6 as rk
from repro.models.common import (apply_norm, cross_entropy, dense_init,
                                 embed_init, init_norm)
from repro.sharding import constrain

PyTree = Any

# Layer-scan unroll factor. 1 = rolled while-loop (production: O(1) HLO in
# depth). The dry-run's depth-fit sets this high so XLA's cost_analysis
# (which counts a while body ONCE) sees every layer — see launch/dryrun.py.
LAYER_SCAN_UNROLL = 1


def _unroll() -> int:
    return LAYER_SCAN_UNROLL


# ------------------------------------------------------------------- helpers

def _is_attn_layer(cfg: ModelConfig, i: int) -> bool:
    """Hybrid: shared attention block applied after layers k, 2k, ... ."""
    k = cfg.hybrid_attn_every
    return bool(k) and (i + 1) % k == 0


def head_out_dim(cfg: ModelConfig) -> int:
    return cfg.num_classes if cfg.num_classes else cfg.vocab_size


def pool_hidden(cfg: ModelConfig, x):
    """Exit-head pooling: CLS token for classification, last token for LM."""
    return x[:, 0, :] if cfg.num_classes else x[:, -1, :]


# ---------------------------------------------------------------------- init

def _init_layer(cfg: ModelConfig, key) -> PyTree:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)
    p: Dict[str, Any] = {}
    if cfg.family == "ssm":
        heads = cfg.ssm.num_heads or d // cfg.ssm.state_size
        p["ln1"] = init_norm(ks[0], d, cfg.norm, dt)
        p["tm"] = rk.init_rwkv6(ks[1], d, heads, cfg.d_ff, dt)
        p["ln2"] = init_norm(ks[2], d, cfg.norm, dt)
        p["cm"] = {k: v for k, v in rk.init_rwkv6(
            ks[3], d, heads, cfg.d_ff, dt).items()
            if k.startswith(("mu_cm", "cm_"))}
    elif cfg.family == "hybrid":
        p["ln1"] = init_norm(ks[0], d, cfg.norm, dt)
        p["mamba"] = m2.init_mamba2(ks[1], d, cfg.ssm.state_size,
                                    cfg.ssm.expand, dt)
    else:  # dense / moe / vlm / audio-decoder
        p["ln1"] = init_norm(ks[0], d, cfg.norm, dt)
        p["attn"] = attn.init_attention(
            ks[1], d, cfg.num_heads, cfg.num_kv_heads, hd,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm, dtype=dt)
        p["ln2"] = init_norm(ks[2], d, cfg.norm, dt)
        if cfg.family == "moe":
            p["moe"] = ff.init_moe(ks[3], d, cfg.d_ff,
                                   cfg.moe.num_experts, dt)
        else:
            p["mlp"] = ff.init_mlp(ks[3], d, cfg.d_ff, cfg.activation, dt)
    # exit head attachments (the paper's technique)
    p["exit_norm"] = init_norm(ks[6], d, cfg.norm, dt)
    if cfg.exits.enabled and not cfg.exits.share_head:
        p["exit_w"] = dense_init(ks[7], d, head_out_dim(cfg), dt)
    return p


def init_params(cfg: ModelConfig, key) -> PyTree:
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)
    layers = jax.vmap(lambda k: _init_layer(cfg, k))(layer_keys)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[1], cfg.vocab_size, cfg.d_model, dt),
        "layers": layers,
        "final_norm": init_norm(ks[2], cfg.d_model, cfg.norm, dt),
    }
    if cfg.exits.share_head or not cfg.exits.enabled:
        params["exit_w"] = dense_init(ks[3], cfg.d_model,
                                      head_out_dim(cfg), dt)
    if cfg.family == "hybrid":
        hd = cfg.resolved_head_dim
        kk = jax.random.split(ks[4], 4)
        params["shared_attn"] = {
            "ln1": init_norm(kk[0], cfg.d_model, cfg.norm, dt),
            "attn": attn.init_attention(
                kk[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd,
                qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm, dtype=dt),
            "ln2": init_norm(kk[2], cfg.d_model, cfg.norm, dt),
            "mlp": ff.init_mlp(kk[3], cfg.d_model, cfg.d_ff,
                               cfg.activation, dt),
        }
    return params


def abstract_params(cfg: ModelConfig) -> PyTree:
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0))


# -------------------------------------------------------------- embed inputs

def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, Any]):
    """tokens (B,S) i32 -> (B,S,D); modality stubs pass 'embeds' directly."""
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    return constrain(x, "batch", None, None)


def _positions(cfg: ModelConfig, b: int, s: int, offset=0):
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.mrope:
        return jnp.broadcast_to(pos[None], (3, b, s))   # text stream: t=h=w
    return pos


# ------------------------------------------------------------ full-seq layer

def _layer_full(cfg: ModelConfig, params, lp, x, positions, i, *,
                window: int, backend: str):
    """One layer over the full sequence. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        b = x.shape[0]
        heads = cfg.ssm.num_heads or cfg.d_model // cfg.ssm.state_size
        st = rk.init_rwkv_state(b, cfg.d_model, heads)
        h, _ = rk.time_mix(lp["tm"], apply_norm(x, lp["ln1"], cfg.norm),
                           (st["tm_last"], st["wkv"]), num_heads=heads,
                           backend=backend, chunk=cfg.ssm.chunk_size)
        x = x + h
        h, _ = rk.channel_mix(lp["cm"], apply_norm(x, lp["ln2"], cfg.norm),
                              st["cm_last"])
        x = x + h
    elif cfg.family == "hybrid":
        b = x.shape[0]
        st = m2.init_mamba2_state(b, cfg.d_model, cfg.ssm.state_size,
                                  cfg.ssm.expand)
        h, _ = m2.mamba2_forward(
            lp["mamba"], apply_norm(x, lp["ln1"], cfg.norm), st,
            state_size=cfg.ssm.state_size, expand=cfg.ssm.expand,
            chunk=cfg.ssm.chunk_size)
        x = x + h

        def shared_block(xx):
            sp = params["shared_attn"]
            h2 = attn.attn_prefill(
                sp["attn"], apply_norm(xx, sp["ln1"], cfg.norm), positions,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, causal=cfg.causal,
                window=window, rope_theta=cfg.rope_theta,
                qk_norm=cfg.qk_norm, backend=backend)
            xx = xx + h2
            h2 = ff.mlp_forward(sp["mlp"],
                                apply_norm(xx, sp["ln2"], cfg.norm),
                                cfg.activation)
            return xx + h2

        k = cfg.hybrid_attn_every
        x = jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0),
                         shared_block, lambda xx: xx, x)
    else:
        h = attn.attn_prefill(
            lp["attn"], apply_norm(x, lp["ln1"], cfg.norm), positions,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, causal=cfg.causal,
            window=window, rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
            mrope=cfg.mrope, backend=backend)
        x = x + h
        x2 = apply_norm(x, lp["ln2"], cfg.norm)
        if cfg.family == "moe":
            h, aux = ff.moe_forward(lp["moe"], x2,
                                    num_experts=cfg.moe.num_experts,
                                    top_k=cfg.moe.top_k,
                                    capacity_factor=cfg.moe.capacity_factor)
        else:
            h = ff.mlp_forward(lp["mlp"], x2, cfg.activation)
        x = x + h
    return constrain(x, "batch", None, None), aux


# -------------------------------------------------------------- train / eval

def _exit_w(params, lp):
    return lp["exit_w"] if "exit_w" in lp else params["exit_w"]


def train_loss(params, cfg: ModelConfig, batch: Dict[str, Any], *,
               backend: str = "ref", remat: bool = True,
               exit_loss_weight: float = 1.0, seq_parallel: bool = True):
    """Joint multi-exit loss (paper/ElasticBERT style): mean CE over exits
    + final-layer CE + MoE aux. LM when num_classes == 0 else classification.

    ``seq_parallel``: Megatron-style sequence-parallel residual boundary —
    the scan carry (and therefore the remat-saved activation stack, the
    dominant train-memory term) is sharded over the "model" axis on the
    sequence dim; attention/MLP re-gather as needed. Costs one
    all-gather/reduce-scatter pair per layer, saves ~model_parallelism x
    activation memory."""
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, b, s)
    window = cfg.effective_window(s)
    labels = batch["labels"]
    carry_spec = ("batch", "model", None) if seq_parallel \
        else ("batch", None, None)

    def exit_ce(params_exit_w, lp, xx):
        hn = apply_norm(xx, lp["exit_norm"], cfg.norm)
        w = _exit_w({"exit_w": params_exit_w}, lp)
        if cfg.num_classes:
            logits = pool_hidden(cfg, hn) @ w            # (B, C)
            return cross_entropy(logits, labels)
        logits = hn @ w                                  # (B, S, V)
        logits = constrain(logits, "batch", None, "model")
        return cross_entropy(logits[:, :-1], labels[:, 1:])

    def body(carry, inp):
        xx, aux = carry
        lp, i = inp
        xx, a = _layer_full(cfg, params, lp, xx, positions, i,
                            window=window, backend=backend)
        loss_i = exit_ce(params.get("exit_w"), lp, xx) \
            if cfg.exits.enabled else jnp.zeros((), jnp.float32)
        xx = constrain(xx, *carry_spec)
        return (xx, aux + a), loss_i

    body_fn = jax.checkpoint(body) if remat else body
    idx = jnp.arange(cfg.num_layers)
    (x, aux), exit_losses = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), (params["layers"], idx),
        unroll=_unroll())

    xf = apply_norm(x, params["final_norm"], cfg.norm)
    w = params.get("exit_w")
    if w is None:  # per-exit heads: final exit = last layer's head
        w = jax.tree.map(lambda l: l[-1], params["layers"])["exit_w"]
    if cfg.num_classes:
        final_logits = pool_hidden(cfg, xf) @ w
        final_loss = cross_entropy(final_logits, labels)
    else:
        logits = constrain(xf @ w, "batch", None, "model")
        final_loss = cross_entropy(logits[:, :-1], labels[:, 1:])

    loss = final_loss + 0.01 * aux / cfg.num_layers
    if cfg.exits.enabled:
        loss = loss + exit_loss_weight * jnp.mean(exit_losses)
    return loss


# ------------------------------------------------- streaming exit observables

def forward_exits(params, cfg: ModelConfig, batch: Dict[str, Any], *,
                  backend: str = "ref", conf_backend: str = "ref"):
    """Full forward collecting per-exit (confidence, prediction).

    Returns dict with conf (L, B) f32, pred (L, B) i32 — layer i's exit
    observables (1-indexed layer i = row i-1). This is the SplitEE-S
    observation vector; SplitEE indexes one row of it.
    """
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, b, s)
    window = cfg.effective_window(s)

    def body(carry, inp):
        xx, aux = carry
        lp, i = inp
        xx, a = _layer_full(cfg, params, lp, xx, positions, i,
                            window=window, backend=backend)
        pooled = pool_hidden(cfg, apply_norm(xx, lp["exit_norm"], cfg.norm))
        return (xx, aux + a), pooled

    idx = jnp.arange(cfg.num_layers)
    (x, _), pooled = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["layers"], idx),
        unroll=_unroll())
    # pooled: (L, B, D)
    conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                         conf_backend=conf_backend)
    return {"conf": conf, "pred": pred, "hidden": x}


def _exit_heads(params, cfg: ModelConfig):
    """Every exit's head: the shared (D, V) matrix, or the per-layer
    (L, D, V) stack."""
    if cfg.exits.share_head or not cfg.exits.enabled:
        return params["exit_w"]
    return params["layers"]["exit_w"]


def stacked_exit_confidence(params, cfg: ModelConfig, pooled, *,
                            conf_backend: str = "ref",
                            fused_exit: bool = False):
    """(conf, pred), each (L, B), of all L exits from their pooled hidden
    ``pooled (L, B, D)`` — one confidence launch for every exit. With
    ``fused_exit`` the pools are RAW and each layer's exit norm runs
    inside the launch; otherwise they are already normed."""
    ews = _exit_heads(params, cfg)
    if fused_exit:
        return exit_confidence_fused(pooled, params["layers"]["exit_norm"],
                                     ews, kind=cfg.norm,
                                     backend=conf_backend)
    return exit_confidence(pooled, ews, backend=conf_backend)


def forward_exits_masked(params, cfg: ModelConfig, batch: Dict[str, Any],
                         depths, *, backend: str = "ref",
                         conf_backend: str = "ref", window=None,
                         fused_exit: bool = False):
    """Depth-masked scan over layers: one program for every depth mix.

    ``depths`` is a (B,) int32 vector of 0-indexed split layers, one per
    sample. The layer loop is the same single ``lax.scan`` over the
    stacked layer params as `forward_exits`, but the carry freezes per
    sample once its own split layer has run (``jnp.where(i <= depths)``
    on the scan state), so the final carry is each sample's hidden
    activation *at its own split depth* — the offload payload. Exit
    observables are still collected for every layer and reduced
    post-scan by one fused confidence call; rows past a sample's depth
    are computed from its frozen carry and are simply unused by serving.

    This is the scan-over-layers serving forward: one compiled program
    covers every split depth a batch mixes (serving/scan_edge.py drives
    it), where the bucketed path compiles per (depth-bucket, row-count)
    launch shape.

    ``window`` overrides the attention window (the serving runtime
    passes 0, matching `EdgeCloudRuntime.edge_fn`); None derives it from
    the sequence length as the training/eval forwards do.

    Returns dict with conf (L, B) f32, pred (L, B) i32 — layer i's exit
    observables at row i-1 — and hidden (B, S, D) at per-sample depth.
    """
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, b, s)
    if window is None:
        window = cfg.effective_window(s)
    live = depths[:, None, None]            # (B, 1, 1) broadcast mask

    def body(carry, inp):
        xx = carry
        lp, i = inp
        xx2, _ = _layer_full(cfg, params, lp, xx, positions, i,
                             window=window, backend=backend)
        xx = jnp.where(i <= live, xx2, xx)
        # the fused epilogue norms inside the confidence program, so the
        # scan only pools the raw carry (pooling commutes with the norm)
        src = xx if fused_exit else apply_norm(xx, lp["exit_norm"], cfg.norm)
        return xx, pool_hidden(cfg, src)

    idx = jnp.arange(cfg.num_layers)
    x, pooled = jax.lax.scan(body, x, (params["layers"], idx),
                             unroll=_unroll())
    # pooled: (L, B, D) — per-layer exit pools, frozen past each depth
    conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                         conf_backend=conf_backend,
                                         fused_exit=fused_exit)
    return {"conf": conf, "pred": pred, "hidden": x}


# ----------------------------------------------------------- prefill / decode

def init_caches(cfg: ModelConfig, batch: int, seq_len: int):
    """Stacked per-layer caches for decode. Window-sized for SWA archs."""
    dt = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    window = cfg.effective_window(seq_len) or seq_len
    if cfg.family == "ssm":
        heads = cfg.ssm.num_heads or cfg.d_model // cfg.ssm.state_size
        st = rk.init_rwkv_state(batch, cfg.d_model, heads)
        return {"ssm": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape),
            st)}
    if cfg.family == "hybrid":
        st = m2.init_mamba2_state(batch, cfg.d_model, cfg.ssm.state_size,
                                  cfg.ssm.expand)
        n_attn = cfg.num_layers // cfg.hybrid_attn_every
        return {
            "ssm": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape),
                st),
            "attn": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_attn,) + a.shape),
                attn.init_cache(batch, window, cfg.num_kv_heads, hd, dt)),
        }
    c = attn.init_cache(batch, window, cfg.num_kv_heads, hd, dt)
    return {"attn": jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), c)}


def _layer_decode(cfg: ModelConfig, params, lp, x, cache_slice, cur_index, *,
                  window: int, occ_caches=None, occ_idx=None):
    """One-token decode through one layer. Returns (x, new_cache_slice,
    occ_caches) — occ_* used by hybrid shared attention."""
    if cfg.family == "ssm":
        st = cache_slice
        heads = cfg.ssm.num_heads or cfg.d_model // cfg.ssm.state_size
        h, (tm_last, wkv) = rk.time_mix(
            lp["tm"], apply_norm(x, lp["ln1"], cfg.norm),
            (st["tm_last"], st["wkv"]), num_heads=heads)
        x = x + h
        h, cm_last = rk.channel_mix(
            lp["cm"], apply_norm(x, lp["ln2"], cfg.norm), st["cm_last"])
        x = x + h
        return x, {"tm_last": tm_last, "cm_last": cm_last, "wkv": wkv}, None
    if cfg.family == "hybrid":
        st = cache_slice
        h, new_st = m2.mamba2_forward(
            lp["mamba"], apply_norm(x, lp["ln1"], cfg.norm), st,
            state_size=cfg.ssm.state_size, expand=cfg.ssm.expand)
        x = x + h
        return x, new_st, occ_caches
    h, new_cache = attn.attn_decode(
        lp["attn"], apply_norm(x, lp["ln1"], cfg.norm), cache_slice,
        cur_index, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, window=window,
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, mrope=cfg.mrope)
    x = x + h
    x2 = apply_norm(x, lp["ln2"], cfg.norm)
    if cfg.family == "moe":
        # decode is drop-free: capacity covers the all-tokens-to-one-expert
        # worst case (a dropped token at decode would corrupt the stream)
        h, _ = ff.moe_forward(lp["moe"], x2, num_experts=cfg.moe.num_experts,
                              top_k=cfg.moe.top_k,
                              capacity_factor=float(cfg.moe.num_experts))
    else:
        h = ff.mlp_forward(lp["mlp"], x2, cfg.activation)
    return x + h, new_cache, None


def decode_step(params, cfg: ModelConfig, caches, token_or_embed,
                cur_index, *, split_layer=None, all_exits: bool = False,
                window_seq_len: int = 0, conf_backend: str = "ref"):
    """SplitEE serve step: decode ONE token with per-layer pooled hiddens
    collected; exit confidence evaluated at ``split_layer`` (SplitEE) or at
    every exit (``all_exits`` — SplitEE-S). Returns (logits, conf, pred,
    new_caches).
    """
    if token_or_embed.ndim <= 1 or token_or_embed.dtype in (
            jnp.int32, jnp.int64):
        x = jnp.take(params["embed"],
                     token_or_embed.reshape(-1, 1), axis=0)
    else:
        x = token_or_embed.astype(jnp.dtype(cfg.dtype))
    b = x.shape[0]
    window = cfg.effective_window(window_seq_len)

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        sp = params["shared_attn"]

        def body(carry, inp):
            xx, occ = carry
            lp, st, i = inp
            xx, new_st, _ = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                          window=window)

            def with_attn(args):
                xx, occ = args
                oi = (i + 1) // k - 1
                sl = jax.tree.map(lambda a: a[oi], occ)
                h, new_sl = attn.attn_decode(
                    sp["attn"], apply_norm(xx, sp["ln1"], cfg.norm), sl,
                    cur_index, num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.resolved_head_dim, window=window,
                    rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)
                xx = xx + h
                xx = xx + ff.mlp_forward(
                    sp["mlp"], apply_norm(xx, sp["ln2"], cfg.norm),
                    cfg.activation)
                occ = jax.tree.map(
                    lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                        buf, ns, oi, 0), occ, new_sl)
                return xx, occ

            xx, occ = jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0),
                                   with_attn, lambda a: a, (xx, occ))
            pooled = pool_hidden(cfg, apply_norm(xx, lp["exit_norm"],
                                                 cfg.norm))
            return (xx, occ), (new_st, pooled)

        idx = jnp.arange(cfg.num_layers)
        (x, occ), (new_ssm, pooled) = jax.lax.scan(
            body, (x, caches["attn"]), (params["layers"], caches["ssm"], idx),
            unroll=_unroll())
        new_caches = {"ssm": new_ssm, "attn": occ}
    else:
        cache_key = "ssm" if cfg.family == "ssm" else "attn"

        def body(xx, inp):
            lp, st, i = inp
            xx, new_st, _ = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                          window=window)
            pooled = pool_hidden(cfg, apply_norm(xx, lp["exit_norm"],
                                                 cfg.norm))
            return xx, (new_st, pooled)

        idx = jnp.arange(cfg.num_layers)
        x, (new_st, pooled) = jax.lax.scan(
            body, x, (params["layers"], caches[cache_key], idx),
            unroll=_unroll())
        new_caches = {cache_key: new_st}

    # exit observables (post-scan: one gather + one fused confidence call)
    shared = cfg.exits.share_head or not cfg.exits.enabled
    if shared:
        ew = params["exit_w"]
    else:
        ew = params["layers"]["exit_w"][-1]              # final exit's head
    if all_exits:
        conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                             conf_backend=conf_backend)
    elif split_layer is not None:
        h_split = jax.lax.dynamic_index_in_dim(pooled, split_layer, 0,
                                               keepdims=False)
        w_split = ew if shared else jax.lax.dynamic_index_in_dim(
            params["layers"]["exit_w"], split_layer, 0, keepdims=False)
        conf, pred = exit_confidence(h_split, w_split, backend=conf_backend)
    else:
        conf = pred = None

    xf = apply_norm(x, params["final_norm"], cfg.norm)
    logits = constrain(xf[:, -1, :] @ ew, "batch", "model")
    return logits, conf, pred, new_caches


def _mask_rows(mask, new, old):
    """Per-sample cache merge: keep ``new`` where ``mask`` (B,) is set, else
    ``old``. Every cache leaf is batch-leading, so the mask broadcasts by
    appending singleton axes."""
    def sel(nw, od):
        m = mask.reshape(mask.shape + (1,) * (nw.ndim - 1))
        return jnp.where(m, nw, od)
    return jax.tree.map(sel, new, old)


def decode_step_masked(params, cfg: ModelConfig, caches, token_or_embed,
                       cur_index, depths, *, window_seq_len: int = 0,
                       conf_backend: str = "ref"):
    """Edge half of a decode-serving step: run layers ``0..depths[b]`` per
    sample, freezing both the hidden carry and the cache slots of deeper
    layers (a skipped attention layer simply leaves its ring-buffer slot
    unwritten; the ``pos`` validity mask excludes the hole at future reads,
    so no per-layer write indices are needed — ``cur_index`` stays global).

    Returns (logits, conf (L, B), pred (L, B), hidden (B, 1, D),
    new_caches): ``logits`` is the final LM head applied to the (masked)
    carry — meaningful for samples with depths[b] == L-1; ``conf``/``pred``
    are every exit head's observables as in ``decode_step(all_exits=True)``;
    ``hidden`` is the raw carry after each sample's own split layer, the
    payload a mid-generation offload ships to the cloud.
    """
    if token_or_embed.ndim <= 1 or token_or_embed.dtype in (
            jnp.int32, jnp.int64):
        x = jnp.take(params["embed"],
                     token_or_embed.reshape(-1, 1), axis=0)
    else:
        x = token_or_embed.astype(jnp.dtype(cfg.dtype))
    window = cfg.effective_window(window_seq_len)
    live = depths[:, None, None]

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        sp = params["shared_attn"]

        def body(carry, inp):
            xx, occ = carry
            lp, st, i = inp
            xx2, new_st, _ = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                           window=window)

            def with_attn(args):
                xx2, occ = args
                oi = (i + 1) // k - 1
                sl = jax.tree.map(lambda a: a[oi], occ)
                h, new_sl = attn.attn_decode(
                    sp["attn"], apply_norm(xx2, sp["ln1"], cfg.norm), sl,
                    cur_index, num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.resolved_head_dim, window=window,
                    rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)
                xx2 = xx2 + h
                xx2 = xx2 + ff.mlp_forward(
                    sp["mlp"], apply_norm(xx2, sp["ln2"], cfg.norm),
                    cfg.activation)
                # shared cache: advance only the samples whose depth reaches
                # this layer — frozen rows keep their old slot contents
                new_sl = _mask_rows(i <= depths, new_sl, sl)
                occ = jax.tree.map(
                    lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                        buf, ns, oi, 0), occ, new_sl)
                return xx2, occ

            xx2, occ = jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0),
                                    with_attn, lambda a: a, (xx2, occ))
            xx = jnp.where(i <= live, xx2, xx)
            new_st = _mask_rows(i <= depths, new_st, st)
            pooled = pool_hidden(cfg, apply_norm(xx, lp["exit_norm"],
                                                 cfg.norm))
            return (xx, occ), (new_st, pooled)

        idx = jnp.arange(cfg.num_layers)
        (x, occ), (new_ssm, pooled) = jax.lax.scan(
            body, (x, caches["attn"]), (params["layers"], caches["ssm"], idx),
            unroll=_unroll())
        new_caches = {"ssm": new_ssm, "attn": occ}
    else:
        cache_key = "ssm" if cfg.family == "ssm" else "attn"

        def body(xx, inp):
            lp, st, i = inp
            xx2, new_st, _ = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                           window=window)
            xx = jnp.where(i <= live, xx2, xx)
            new_st = _mask_rows(i <= depths, new_st, st)
            pooled = pool_hidden(cfg, apply_norm(xx, lp["exit_norm"],
                                                 cfg.norm))
            return xx, (new_st, pooled)

        idx = jnp.arange(cfg.num_layers)
        x, (new_st, pooled) = jax.lax.scan(
            body, x, (params["layers"], caches[cache_key], idx),
            unroll=_unroll())
        new_caches = {cache_key: new_st}

    conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                         conf_backend=conf_backend)
    ews = _exit_heads(params, cfg)
    ew = ews if ews.ndim == 2 else ews[-1]           # final exit's head

    xf = apply_norm(x, params["final_norm"], cfg.norm)
    logits = constrain(xf[:, -1, :] @ ew, "batch", "model")
    return logits, conf, pred, x, new_caches


def decode_step_resume(params, cfg: ModelConfig, caches, hidden,
                       cur_index, depths, active, *,
                       window_seq_len: int = 0):
    """Cloud half of a decode-serving step: resume from the shipped edge
    carry ``hidden`` (B, 1, D) and run layers ``depths[b]+1 .. L-1`` for the
    samples with ``active[b]`` set; everything else (inactive samples, and
    layers the edge already advanced) passes through untouched — the
    returned cache tree is bitwise the input tree at those coordinates, so
    merging it back re-syncs the edge cache.

    Returns (logits, new_caches).
    """
    x = hidden.astype(jnp.dtype(cfg.dtype))
    window = cfg.effective_window(window_seq_len)

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        sp = params["shared_attn"]

        def body(carry, inp):
            xx, occ = carry
            lp, st, i = inp
            m = active & (i > depths)
            xx2, new_st, _ = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                           window=window)

            def with_attn(args):
                xx2, occ = args
                oi = (i + 1) // k - 1
                sl = jax.tree.map(lambda a: a[oi], occ)
                h, new_sl = attn.attn_decode(
                    sp["attn"], apply_norm(xx2, sp["ln1"], cfg.norm), sl,
                    cur_index, num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.resolved_head_dim, window=window,
                    rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)
                xx2 = xx2 + h
                xx2 = xx2 + ff.mlp_forward(
                    sp["mlp"], apply_norm(xx2, sp["ln2"], cfg.norm),
                    cfg.activation)
                new_sl = _mask_rows(m, new_sl, sl)
                occ = jax.tree.map(
                    lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                        buf, ns, oi, 0), occ, new_sl)
                return xx2, occ

            xx2, occ = jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0),
                                    with_attn, lambda a: a, (xx2, occ))
            xx = jnp.where(m[:, None, None], xx2, xx)
            new_st = _mask_rows(m, new_st, st)
            return (xx, occ), new_st

        idx = jnp.arange(cfg.num_layers)
        (x, occ), new_ssm = jax.lax.scan(
            body, (x, caches["attn"]), (params["layers"], caches["ssm"], idx),
            unroll=_unroll())
        new_caches = {"ssm": new_ssm, "attn": occ}
    else:
        cache_key = "ssm" if cfg.family == "ssm" else "attn"

        def body(xx, inp):
            lp, st, i = inp
            m = active & (i > depths)
            xx2, new_st, _ = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                           window=window)
            xx = jnp.where(m[:, None, None], xx2, xx)
            new_st = _mask_rows(m, new_st, st)
            return xx, new_st

        idx = jnp.arange(cfg.num_layers)
        x, new_st = jax.lax.scan(
            body, x, (params["layers"], caches[cache_key], idx),
            unroll=_unroll())
        new_caches = {cache_key: new_st}

    ew = params["exit_w"] if "exit_w" in params \
        else params["layers"]["exit_w"][-1]
    xf = apply_norm(x, params["final_norm"], cfg.norm)
    logits = constrain(xf[:, -1, :] @ ew, "batch", "model")
    return logits, new_caches


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            backend: str = "ref", cache_seq_len: int = 0):
    """Process the prompt, build decode caches, return final logits.

    For attention archs the prefill recomputes K/V into the cache via a
    scan that mirrors the train-mode layer but returns (k, v) as ys.
    """
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, b, s)
    seq_total = cache_seq_len or s
    window = cfg.effective_window(seq_total)
    cache_window = window or seq_total

    if cfg.family == "ssm":
        def body(carry, inp):
            xx = carry
            lp, i = inp
            heads = cfg.ssm.num_heads or cfg.d_model // cfg.ssm.state_size
            st = rk.init_rwkv_state(b, cfg.d_model, heads)
            h, (tm_last, wkv) = rk.time_mix(
                lp["tm"], apply_norm(xx, lp["ln1"], cfg.norm),
                (st["tm_last"], st["wkv"]), num_heads=heads, backend=backend,
                chunk=cfg.ssm.chunk_size)
            xx = xx + h
            h, cm_last = rk.channel_mix(
                lp["cm"], apply_norm(xx, lp["ln2"], cfg.norm), st["cm_last"])
            xx = xx + h
            return xx, {"tm_last": tm_last, "cm_last": cm_last, "wkv": wkv}

        idx = jnp.arange(cfg.num_layers)
        x, states = jax.lax.scan(body, x, (params["layers"], idx),
                                 unroll=_unroll())
        caches = {"ssm": states}
    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        sp = params["shared_attn"]
        n_attn = cfg.num_layers // k
        occ0 = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_attn,) + a.shape),
            attn.init_cache(b, cache_window, cfg.num_kv_heads,
                            cfg.resolved_head_dim, jnp.dtype(cfg.dtype)))

        def body(carry, inp):
            xx, occ = carry
            lp, i = inp
            st = m2.init_mamba2_state(b, cfg.d_model, cfg.ssm.state_size,
                                      cfg.ssm.expand)
            h, new_st = m2.mamba2_forward(
                lp["mamba"], apply_norm(xx, lp["ln1"], cfg.norm), st,
                state_size=cfg.ssm.state_size, expand=cfg.ssm.expand,
                chunk=cfg.ssm.chunk_size)
            xx = xx + h

            def with_attn(args):
                xx, occ = args
                oi = (i + 1) // k - 1
                h2, (kk, vv) = attn.attn_prefill(
                    sp["attn"], apply_norm(xx, sp["ln1"], cfg.norm),
                    positions, num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.resolved_head_dim, causal=cfg.causal,
                    window=window, rope_theta=cfg.rope_theta,
                    qk_norm=cfg.qk_norm, backend=backend, return_kv=True)
                xx = xx + h2
                xx = xx + ff.mlp_forward(
                    sp["mlp"], apply_norm(xx, sp["ln2"], cfg.norm),
                    cfg.activation)
                sl = jax.tree.map(lambda a: a[oi], occ)
                sl = attn.fill_cache(sl, kk[:, -cache_window:],
                                     vv[:, -cache_window:],
                                     start=max(0, s - cache_window))
                occ = jax.tree.map(
                    lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                        buf, ns.astype(buf.dtype), oi, 0), occ, sl)
                return xx, occ

            xx, occ = jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0),
                                   with_attn, lambda a: a, (xx, occ))
            return (xx, occ), new_st

        idx = jnp.arange(cfg.num_layers)
        (x, occ), states = jax.lax.scan(body, (x, occ0),
                                        (params["layers"], idx),
                                        unroll=_unroll())
        caches = {"ssm": states, "attn": occ}
    else:
        def body(xx, inp):
            lp, i = inp
            h, (kk, vv) = attn.attn_prefill(
                lp["attn"], apply_norm(xx, lp["ln1"], cfg.norm), positions,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, causal=cfg.causal,
                window=window, rope_theta=cfg.rope_theta,
                qk_norm=cfg.qk_norm, mrope=cfg.mrope, backend=backend,
                return_kv=True)
            xx = xx + h
            x2 = apply_norm(xx, lp["ln2"], cfg.norm)
            if cfg.family == "moe":
                h, _ = ff.moe_forward(
                    lp["moe"], x2, num_experts=cfg.moe.num_experts,
                    top_k=cfg.moe.top_k,
                    capacity_factor=cfg.moe.capacity_factor)
            else:
                h = ff.mlp_forward(lp["mlp"], x2, cfg.activation)
            xx = constrain(xx + h, "batch", None, None)
            c = attn.init_cache(b, cache_window, cfg.num_kv_heads,
                                cfg.resolved_head_dim, jnp.dtype(cfg.dtype))
            c = attn.fill_cache(c, kk[:, -cache_window:],
                                vv[:, -cache_window:],
                                start=max(0, s - cache_window))
            return xx, c

        idx = jnp.arange(cfg.num_layers)
        x, caches_stacked = jax.lax.scan(body, x, (params["layers"], idx),
                                         unroll=_unroll())
        caches = {"attn": caches_stacked}

    ew = params["exit_w"] if "exit_w" in params \
        else params["layers"]["exit_w"][-1]
    xf = apply_norm(x, params["final_norm"], cfg.norm)
    logits = constrain(xf[:, -1, :] @ ew, "batch", "model")
    return logits, caches
