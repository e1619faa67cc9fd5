"""Multi-exit decoder stack — the model substrate the SplitEE policy runs on.

Layers are *stacked* along a leading axis and iterated with ``lax.scan``
(O(1) HLO size in depth — mirrors the paper's "one hardware module reused
per layer" observation and keeps 512-device dry-run compiles tractable).
The two halves of a decode-serving step iterate a traced layer range with
``lax.fori_loop`` instead, so the layers outside it are skipped.

Per-layer exit observables are collected as scan outputs: the pooled hidden
state after every layer (tiny: (L, B, D)), from which exit confidences are
computed *post-scan* in one batched matmul / fused Pallas confidence call —
so SplitEE (single exit check) and SplitEE-S (all exits) share one forward.

Families: dense (llama/qwen/granite), moe (mixtral/phi), ssm (rwkv6),
hybrid (zamba2: mamba2 backbone + one shared attention block every k
layers; granite-4.0-h: ``layer_types`` names each layer's mixer, Mamba2 or
attention, each layer with its own mixer and MLP). Enc-dec (seamless)
wraps this module — see encdec.py.

Each family's layer is written once per mode: `_layer_full` over a whole
sequence (train, eval, the masked forward and, returning each layer's
decode state, prefill) and `_layer_decode` over one token (`decode_step`
and both served halves, all through `_decode_layer_range`). zamba2's
shared block is written once per mode too: `_shared_seq` and
`_shared_step`, whose cache stack rides in the loop carry. The loops only
call these; `_final_head` picks the final exit's head for all of them.

Typed layers keep their mixers' weights and decode state once per type:
``params["mixers"]`` holds a stacked "mamba" and a stacked "attention"
tree, and the cache tree a stacked "ssm" and a stacked "attn" entry, each
as deep as its type has layers. Every layer loop runs the layers as runs
of one type (`_runs`), each run a loop of its own. A typed layer's mixer
reaches the layer functions under its type's key in the layer's
parameters ("mamba" or "attn"), which is how they pick it: statically, so
the program holds no branch over the types.
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

def _norm(cfg: ModelConfig, x, p):
    return apply_norm(x, p, cfg.norm, cfg.norm_eps)


def _head_in(cfg: ModelConfig, h):
    """A head's input: the normed hidden, divided by ``logits_scaling``
    where the model divides its logits (the same logits, and the exit
    confidence kernels keep their (h, W) interface)."""
    return h if cfg.logits_scaling == 1.0 else h / cfg.logits_scaling


def _head_norm(cfg: ModelConfig, x, p):
    return _head_in(cfg, _norm(cfg, x, p))


def _embed_scale(cfg: ModelConfig, x):
    m = cfg.embedding_multiplier
    return x if m == 1.0 else x * m


def _take(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


# a typed layer's mixer: its key in the layer's parameters, which the
# layer functions read the type from, and its state's key in the caches
_MIXER_KEY = {"mamba": "mamba", "attention": "attn"}
_STATE_KEY = {"mamba": "ssm", "attention": "attn"}


def _runs(cfg: ModelConfig):
    """The layer loops' runs, [(kind, start, stop, slot)]: one of every
    layer (kind None) where layers are untyped, else one per stretch of
    same-typed layers, ``slot`` its first layer's index in its type's
    stacks."""
    if not cfg.layer_types:
        return [(None, 0, cfg.num_layers, 0)]
    runs, seen = [], {}
    for i, kind in enumerate(cfg.layer_kinds()):
        if runs and runs[-1][0] == kind:
            runs[-1][2] = i + 1
        else:
            runs.append([kind, i, i + 1, seen.get(kind, 0)])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in runs]


def _state_key(cfg: ModelConfig, kind) -> str:
    if kind is not None:
        return _STATE_KEY[kind]
    return "ssm" if cfg.family in ("ssm", "hybrid") else "attn"


def _layer_params(params, kind, i, j):
    """Layer ``i``'s parameters, read by index; a typed layer's mixer (slot
    ``j`` of its type's stack) rides along under its type's key."""
    lp = _take(params["layers"], i)
    if kind is not None:
        lp[_MIXER_KEY[kind]] = _take(params["mixers"][kind], j)
    return lp


def _scan_layers(cfg: ModelConfig, params, body, carry, states=None):
    """``lax.scan`` of ``body(carry, (lp, st, i)) -> (carry, (new_st, y))``
    over every layer: ``lp`` is layer i's parameters and ``st`` its entry
    of its state stack in ``states`` (None without). Returns (carry, the
    ``new_st`` as a state tree or None, the ``y`` stacked over the layers).

    Untyped layers are scanned as their stacks. Typed ones are scanned run
    by run (`_runs`) over their indices, reading ``lp`` and ``st`` by index
    from the whole stacks (a slice of a stack would be a copy of it); each
    type's new states are joined over its runs."""
    news, ys = {}, []
    for kind, a, b, s in _runs(cfg):
        key = _state_key(cfg, kind)
        if kind is None:
            st = None if states is None else states[key]
            carry, (new, y) = jax.lax.scan(
                body, carry, (params["layers"], st, jnp.arange(b)),
                unroll=_unroll())
        else:
            def run(c, i, kind=kind, key=key, off=s - a):
                st = None if states is None else _take(states[key], i + off)
                return body(c, (_layer_params(params, kind, i, i + off), st,
                                i))

            carry, (new, y) = jax.lax.scan(run, carry, jnp.arange(a, b),
                                           unroll=_unroll())
        if new is not None:
            news.setdefault(key, []).append(new)
        ys.append(y)

    def join(parts):
        if len(parts) == 1:
            return parts[0]
        return jax.tree.map(lambda *p: jnp.concatenate(p), *parts)

    return carry, ({k: join(v) for k, v in news.items()} or None), join(ys)


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
    if cfg.layer_types:
        # the mixer's weights live in its type's stack (init_params)
        p["ln1"] = init_norm(ks[0], d, cfg.norm, dt)
        p["ln2"] = init_norm(ks[2], d, cfg.norm, dt)
        p["mlp"] = ff.init_mlp(ks[3], d, cfg.d_ff, cfg.activation, dt)
    elif cfg.family == "ssm":
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
    if cfg.layer_types:
        kinds = cfg.layer_kinds()
        n_attn = kinds.count("attention")
        km, ka = jax.random.split(ks[5])
        params["mixers"] = {
            "mamba": jax.vmap(lambda k: m2.init_mamba2(
                k, cfg.d_model, cfg.ssm.state_size, cfg.ssm.expand, dt))(
                jax.random.split(km, len(kinds) - n_attn)),
            "attention": jax.vmap(lambda k: attn.init_attention(
                k, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias,
                qk_norm=cfg.qk_norm, dtype=dt))(
                jax.random.split(ka, n_attn)),
        }
    elif cfg.family == "hybrid":
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
    return constrain(_embed_scale(cfg, x), "batch", None, None)


def _positions(cfg: ModelConfig, b: int, s: int, offset=0):
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.mrope:
        return jnp.broadcast_to(pos[None], (3, b, s))   # text stream: t=h=w
    return pos


# ------------------------------------------------------------------ layers

def _attn_kw(cfg: ModelConfig):
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim,
                rope_theta=cfg.attn_rope_theta, qk_norm=cfg.qk_norm,
                mrope=cfg.mrope, scale=cfg.attention_multiplier or None)


def _mamba_kw(cfg: ModelConfig):
    return dict(state_size=cfg.ssm.state_size, expand=cfg.ssm.expand,
                chunk=cfg.ssm.chunk_size, norm_eps=cfg.norm_eps)


def _shared_block(cfg: ModelConfig) -> bool:
    """zamba2's layout: a Mamba2 backbone with one shared attention block
    after every ``hybrid_attn_every``-th layer."""
    return cfg.family == "hybrid" and not cfg.layer_types


def _ring_fill(cache, k, v):
    """``cache`` with the last of a prompt's keys and values ``k``, ``v``
    (B, S, Hkv, hd) written at their ring slots."""
    w, s = cache["k"].shape[1], k.shape[1]
    return attn.fill_cache(cache, k[:, -w:], v[:, -w:], start=max(0, s - w))


def _new_cache(cfg: ModelConfig, b: int, w: int):
    return attn.init_cache(b, w, cfg.num_kv_heads, cfg.resolved_head_dim,
                           jnp.dtype(cfg.dtype))


def _rwkv_layer(cfg: ModelConfig, lp, x, st, **kw):
    """An RWKV6 layer from its state ``st`` (a zero state where None): time
    mix, then channel mix, each a residual. Returns (x, new state)."""
    heads = cfg.ssm.num_heads or cfg.d_model // cfg.ssm.state_size
    if st is None:
        st = rk.init_rwkv_state(x.shape[0], cfg.d_model, heads)
    h, (tm_last, wkv) = rk.time_mix(
        lp["tm"], _norm(cfg, x, lp["ln1"]), (st["tm_last"], st["wkv"]),
        num_heads=heads, **kw)
    x = x + h
    h, cm_last = rk.channel_mix(lp["cm"], _norm(cfg, x, lp["ln2"]),
                                st["cm_last"])
    return x + h, {"tm_last": tm_last, "cm_last": cm_last, "wkv": wkv}


def _typed_block(cfg: ModelConfig, lp, x, mixer):
    """One typed layer around its mixer: ``h, state = mixer(rms(x))``, then
    ``x + r h`` and ``x + r mlp(rms(x))`` (r: ``residual_multiplier``).
    Returns (x, state)."""
    r = cfg.residual_multiplier
    h, state = mixer(_norm(cfg, x, lp["ln1"]))
    x = x + r * h
    with jax.named_scope("splitee.mlp"):
        h = ff.mlp_forward(lp["mlp"], _norm(cfg, x, lp["ln2"]),
                           cfg.activation)
    return x + r * h, state


def _mixer_seq(cfg: ModelConfig, lp, xn, positions, *, window: int,
               backend: str, cache_window: int = 0):
    """A Mamba2 (``lp["mamba"]``) or typed attention (``lp["attn"]``) mixer
    over a whole sequence ``xn`` (B, S, D), Mamba2 from a zero state.
    Returns (h, state): Mamba2's final SSM and conv state, or, with a
    ``cache_window`` (prefill), the attention cache holding the last keys
    and values at their ring slots (else None)."""
    b = xn.shape[0]
    if "mamba" in lp:
        with jax.named_scope("splitee.mamba"):
            st = m2.init_mamba2_state(b, cfg.d_model, cfg.ssm.state_size,
                                      cfg.ssm.expand)
            return m2.mamba2_forward(lp["mamba"], xn, st, **_mamba_kw(cfg))
    with jax.named_scope("splitee.attn"):
        h, (k, v) = attn.attn_prefill(
            lp["attn"], xn, positions, causal=cfg.causal, window=window,
            backend=backend, return_kv=True, **_attn_kw(cfg))
        if not cache_window:
            return h, None
        return h, _ring_fill(_new_cache(cfg, b, cache_window), k, v)


def _mixer_step(cfg: ModelConfig, lp, xn, st, cur_index, *, window: int):
    """A Mamba2 (``lp["mamba"]``) or attention (``lp["attn"]``) mixer for
    one token ``xn`` (B, 1, D) from its state ``st``. Returns (h, new
    state)."""
    if "mamba" in lp:
        with jax.named_scope("splitee.mamba"):
            return m2.mamba2_forward(lp["mamba"], xn, st, **_mamba_kw(cfg))
    with jax.named_scope("splitee.attn"):
        return attn.attn_decode(lp["attn"], xn, st, cur_index,
                                window=window, **_attn_kw(cfg))


def _shared_seq(cfg: ModelConfig, params, x, positions, i, occ, *,
                window: int, backend: str):
    """zamba2's shared attention block over the sequence, run after layer
    ``i`` where ``(i + 1) % hybrid_attn_every == 0``. With ``occ`` (the
    block's stacked caches, prefill) its keys and values fill the cache of
    this occurrence. Returns (x, occ)."""
    k = cfg.hybrid_attn_every
    sp = params["shared_attn"]

    def block(args):
        xx, occ = args
        oi = (i + 1) // k - 1
        h, (kk, vv) = attn.attn_prefill(
            sp["attn"], _norm(cfg, xx, sp["ln1"]), positions,
            causal=cfg.causal, window=window, backend=backend,
            return_kv=True, **_attn_kw(cfg))
        xx = xx + h
        xx = xx + ff.mlp_forward(sp["mlp"], _norm(cfg, xx, sp["ln2"]),
                                 cfg.activation)
        if occ is not None:
            sl = _ring_fill(jax.tree.map(lambda a: a[oi], occ), kk, vv)
            occ = jax.tree.map(
                lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                    buf, ns.astype(buf.dtype), oi, 0), occ, sl)
        return xx, occ

    return jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0), block,
                        lambda a: a, (x, occ))


def _shared_step(cfg: ModelConfig, params, x, occ, i, live, cur_index, *,
                 window: int):
    """zamba2's shared attention block for one token, run after layer
    ``i`` where ``(i + 1) % hybrid_attn_every == 0``; its cache in ``occ``
    advances for the rows in ``live`` (B,) only. Returns (x, occ)."""
    k = cfg.hybrid_attn_every
    sp = params["shared_attn"]

    def block(args):
        xx, occ = args
        oi = (i + 1) // k - 1
        sl = jax.tree.map(lambda a: a[oi], occ)
        h, new_sl = attn.attn_decode(
            sp["attn"], _norm(cfg, xx, sp["ln1"]), sl, cur_index,
            window=window, **_attn_kw(cfg))
        xx = xx + h
        xx = xx + ff.mlp_forward(sp["mlp"], _norm(cfg, xx, sp["ln2"]),
                                 cfg.activation)
        new_sl = _mask_rows(live, new_sl, sl)
        occ = jax.tree.map(
            lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                buf, ns, oi, 0), occ, new_sl)
        return xx, occ

    return jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0), block,
                        lambda a: a, (x, occ))


def _layer_full(cfg: ModelConfig, params, lp, x, positions, i, *,
                window: int, backend: str, cache_window: int = 0, occ=None):
    """One layer over the full sequence. Returns (x, aux).

    With a ``cache_window`` (prefill) it returns (x, aux, state, occ):
    the layer's decode state (an attention layer's ring cache of that many
    slots holding the last keys and values, RWKV6's or Mamba2's final
    state) and ``occ``, zamba2's shared-block caches, filled where the
    block runs after this layer (None elsewhere)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.layer_types:
        x, st = _typed_block(cfg, lp, x, lambda xn: _mixer_seq(
            cfg, lp, xn, positions, window=window, backend=backend,
            cache_window=cache_window))
    elif cfg.family == "ssm":
        x, st = _rwkv_layer(cfg, lp, x, None, backend=backend,
                            chunk=cfg.ssm.chunk_size)
    elif cfg.family == "hybrid":
        h, st = _mixer_seq(cfg, lp, _norm(cfg, x, lp["ln1"]), positions,
                           window=window, backend=backend)
        x, occ = _shared_seq(cfg, params, x + h, positions, i, occ,
                             window=window, backend=backend)
    else:
        h, (k, v) = attn.attn_prefill(
            lp["attn"], _norm(cfg, x, lp["ln1"]), positions,
            causal=cfg.causal, window=window, backend=backend,
            return_kv=True, **_attn_kw(cfg))
        x = x + h
        x2 = _norm(cfg, x, lp["ln2"])
        if cfg.family == "moe":
            h, aux = ff.moe_forward(lp["moe"], x2,
                                    num_experts=cfg.moe.num_experts,
                                    top_k=cfg.moe.top_k,
                                    capacity_factor=cfg.moe.capacity_factor)
        else:
            h = ff.mlp_forward(lp["mlp"], x2, cfg.activation)
        x = x + h
        st = (_ring_fill(_new_cache(cfg, x.shape[0], cache_window), k, v)
              if cache_window else None)
    x = constrain(x, "batch", None, None)
    if not cache_window:
        return x, aux
    return x, aux, st, occ


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
        hn = _head_norm(cfg, xx, lp["exit_norm"])
        w = _exit_w({"exit_w": params_exit_w}, lp)
        if cfg.num_classes:
            logits = pool_hidden(cfg, hn) @ w            # (B, C)
            return cross_entropy(logits, labels)
        logits = hn @ w                                  # (B, S, V)
        logits = constrain(logits, "batch", None, "model")
        return cross_entropy(logits[:, :-1], labels[:, 1:])

    def body(carry, inp):
        xx, aux = carry
        lp, _, i = inp
        xx, a = _layer_full(cfg, params, lp, xx, positions, i,
                            window=window, backend=backend)
        loss_i = exit_ce(params.get("exit_w"), lp, xx) \
            if cfg.exits.enabled else jnp.zeros((), jnp.float32)
        xx = constrain(xx, *carry_spec)
        return (xx, aux + a), (None, loss_i)

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), _, exit_losses = _scan_layers(
        cfg, params, body_fn, (x, jnp.zeros((), jnp.float32)))

    xf = _head_norm(cfg, x, params["final_norm"])
    w = _final_head(params, cfg)
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
        lp, _, i = inp
        xx, a = _layer_full(cfg, params, lp, xx, positions, i,
                            window=window, backend=backend)
        pooled = pool_hidden(cfg, _head_norm(cfg, xx, lp["exit_norm"]))
        return (xx, aux + a), (None, pooled)

    (x, _), _, pooled = _scan_layers(cfg, params, body,
                                     (x, jnp.zeros((), jnp.float32)))
    # pooled: (L, B, D)
    conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                         conf_backend=conf_backend)
    return {"conf": conf, "pred": pred, "hidden": x}


def _shared_head(cfg: ModelConfig) -> bool:
    return cfg.exits.share_head or not cfg.exits.enabled


def _exit_heads(params, cfg: ModelConfig):
    """Every exit's head: the shared (D, V) matrix, or the per-layer
    (L, D, V) stack."""
    if _shared_head(cfg):
        return params["exit_w"]
    return params["layers"]["exit_w"]


def _final_head(params, cfg: ModelConfig):
    """The final exit's head: the shared one, or the last layer's."""
    if _shared_head(cfg):
        return params["exit_w"]
    return params["layers"]["exit_w"][-1]


def _last_logits(params, cfg: ModelConfig, x):
    """The final head on the last position of ``x`` (B, S, D): (B, V)."""
    ew = _final_head(params, cfg)
    xf = _head_norm(cfg, x, params["final_norm"])
    return constrain(xf[:, -1, :] @ ew, "batch", "model")


def _exit_pools(params, cfg: ModelConfig, hs):
    """Every exit's pooled, normed hidden (L, B, D) from the carries ``hs``
    (L, B, 1, D) its layers read."""
    return jax.vmap(lambda h, n: pool_hidden(cfg, _head_norm(cfg, h, n)))(
        hs, params["layers"]["exit_norm"])


def stacked_exit_confidence(params, cfg: ModelConfig, pooled, *,
                            conf_backend: str = "ref",
                            fused_exit: bool = False):
    """(conf, pred), each (L, B), of all L exits from their pooled hidden
    ``pooled (L, B, D)`` — one confidence launch for every exit. With
    ``fused_exit`` the pools are RAW and each layer's exit norm runs
    inside the launch; otherwise they are already normed."""
    ews = _exit_heads(params, cfg)
    if fused_exit:
        norms = params["layers"]["exit_norm"]
        if cfg.logits_scaling != 1.0:     # norm(h) s / c = norm(h) (s / c)
            norms = jax.tree.map(lambda a: a / cfg.logits_scaling, norms)
        return exit_confidence_fused(pooled, norms, ews, kind=cfg.norm,
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
        lp, _, i = inp
        xx2, _ = _layer_full(cfg, params, lp, xx, positions, i,
                             window=window, backend=backend)
        xx = jnp.where(i <= live, xx2, xx)
        # the fused epilogue norms inside the confidence program, so the
        # scan only pools the raw carry (pooling commutes with the norm)
        src = xx if fused_exit else _head_norm(cfg, xx, lp["exit_norm"])
        return xx, (None, pool_hidden(cfg, src))

    x, _, pooled = _scan_layers(cfg, params, body, x)
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
    if cfg.layer_types:
        # one stack per state type, as deep as its type has layers
        n_attn = cfg.layer_kinds().count("attention")
        st = m2.init_mamba2_state(batch, cfg.d_model, cfg.ssm.state_size,
                                  cfg.ssm.expand)
        return {
            "ssm": jax.tree.map(lambda a: jnp.broadcast_to(
                a, (cfg.num_layers - n_attn,) + a.shape), st),
            "attn": jax.tree.map(lambda a: jnp.broadcast_to(
                a, (n_attn,) + a.shape),
                attn.init_cache(batch, window, cfg.num_kv_heads, hd, dt)),
        }
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
                  window: int):
    """One-token decode through one layer. Returns (x, new_cache_slice).
    zamba2's shared block is not part of it (`_shared_step`)."""
    if cfg.layer_types:
        return _typed_block(cfg, lp, x, lambda xn: _mixer_step(
            cfg, lp, xn, cache_slice, cur_index, window=window))
    if cfg.family == "ssm":
        return _rwkv_layer(cfg, lp, x, cache_slice)
    h, new_cache = _mixer_step(cfg, lp, _norm(cfg, x, lp["ln1"]),
                               cache_slice, cur_index, window=window)
    x = x + h
    if cfg.family == "hybrid":
        return x, new_cache
    x2 = _norm(cfg, x, lp["ln2"])
    if cfg.family == "moe":
        # decode is drop-free: capacity covers the all-tokens-to-one-expert
        # worst case (a dropped token at decode would corrupt the stream)
        h, _ = ff.moe_forward(lp["moe"], x2, num_experts=cfg.moe.num_experts,
                              top_k=cfg.moe.top_k,
                              capacity_factor=float(cfg.moe.num_experts))
    else:
        h = ff.mlp_forward(lp["mlp"], x2, cfg.activation)
    return x + h, new_cache


def _embed_step(params, cfg: ModelConfig, token_or_embed):
    """One decode step's input (B, 1, D): token ids embedded, or an
    embedding passed in."""
    if token_or_embed.ndim <= 1 or token_or_embed.dtype in (
            jnp.int32, jnp.int64):
        x = jnp.take(params["embed"],
                     token_or_embed.reshape(-1, 1), axis=0)
    else:
        x = token_or_embed.astype(jnp.dtype(cfg.dtype))
    return _embed_scale(cfg, x)


def decode_step(params, cfg: ModelConfig, caches, token_or_embed,
                cur_index, *, split_layer=None, all_exits: bool = False,
                window_seq_len: int = 0, conf_backend: str = "ref"):
    """SplitEE serve step: decode ONE token through every layer, every row
    live (the layer loop of the served halves, `_decode_layer_range`), with
    exit confidence evaluated at ``split_layer`` (SplitEE) or at every exit
    (``all_exits`` — SplitEE-S). Returns (logits, conf, pred,
    new_caches).
    """
    x = _embed_step(params, cfg, token_or_embed)
    every = jnp.ones(x.shape[:1], bool)
    x, new_caches, buf = _decode_layer_range(
        params, cfg, caches, x, cur_index, 0, cfg.num_layers,
        lambda i: every, window=cfg.effective_window(window_seq_len),
        record=True)

    # exit observables (post-loop: one gather + one fused confidence call)
    pooled = _exit_pools(params, cfg, buf)
    if all_exits:
        conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                             conf_backend=conf_backend)
    elif split_layer is not None:
        h_split = jax.lax.dynamic_index_in_dim(pooled, split_layer, 0,
                                               keepdims=False)
        ews = _exit_heads(params, cfg)
        w_split = ews if _shared_head(cfg) else jax.lax.dynamic_index_in_dim(
            ews, split_layer, 0, keepdims=False)
        conf, pred = exit_confidence(h_split, w_split, backend=conf_backend)
    else:
        conf = pred = None
    return _last_logits(params, cfg, x), conf, pred, new_caches


def _mask_rows(mask, new, old):
    """Per-sample cache merge: keep ``new`` where ``mask`` (B,) is set, else
    ``old``. Every cache leaf is batch-leading, so the mask broadcasts by
    appending singleton axes."""
    def sel(nw, od):
        m = mask.reshape(mask.shape + (1,) * (nw.ndim - 1))
        return jnp.where(m, nw, od)
    return jax.tree.map(sel, new, old)


def _decode_layer_range(params, cfg: ModelConfig, caches, x, cur_index,
                        lo, hi, live, *, window, record: bool = False):
    """Run decode layers ``lo .. hi-1`` of one step over the stacked cache
    tree. ``live(i)`` gives the (B,) rows layer ``i`` advances; the others
    keep their carry and cache slices. Each layer's parameters and cache
    slice are read with a dynamic index and the slice is written back in
    place into the carried tree, so the loop neither computes nor touches
    the layers outside the range (the caches are not donated, so XLA
    copies the input tree into the carry once). Typed layers run one loop
    per run of one type (`_runs`), each over its part of the range.
    Traced bounds make the trip count dynamic; where both are Python ints
    the loops are scans unrolled by ``LAYER_SCAN_UNROLL``.

    Returns (x, new_caches, buf): with ``record``, ``buf`` (L, B, 1, D)
    holds the carry after each layer that ran (zeros from ``hi`` up).
    """
    take = functools.partial(jax.lax.dynamic_index_in_dim, keepdims=False)
    put = jax.lax.dynamic_update_index_in_dim
    static = isinstance(lo, int) and isinstance(hi, int)

    def run_body(kind, off):
        # layer i's state is entry i + off of its stack
        def body(i, carry):
            xx, stack, occ, buf = carry
            j = i if off == 0 else i + off
            lp = _layer_params(params, kind, i, j)
            st = jax.tree.map(lambda a: take(a, j, 0), stack)
            m = live(i)
            xx2, new_st = _layer_decode(cfg, params, lp, xx, st, cur_index,
                                        window=window)
            if occ is not None:
                xx2, occ = _shared_step(cfg, params, xx2, occ, i, m,
                                        cur_index, window=window)
            xx = jnp.where(m[:, None, None], xx2, xx)
            new_st = _mask_rows(m, new_st, st)
            stack = jax.tree.map(lambda a, s: put(a, s, j, 0), stack, new_st)
            if record:
                buf = put(buf, xx, i, 0)
            return xx, stack, occ, buf
        return body

    buf = (jnp.zeros((cfg.num_layers,) + x.shape, x.dtype) if record
           else None)
    occ = caches["attn"] if _shared_block(cfg) else None
    new_caches = dict(caches)
    with jax.named_scope("splitee.layers"):
        for kind, a, b, s in _runs(cfg):
            key = _state_key(cfg, kind)
            # a run of typed layers loops over its part of lo .. hi-1 (none
            # where they do not meet)
            if kind is None:
                bounds = (lo, hi)
            elif static:
                bounds = (min(max(lo, a), b), min(max(hi, a), b))
            else:
                bounds = (jnp.clip(lo, a, b), jnp.clip(hi, a, b))
            x, new_caches[key], occ, buf = jax.lax.fori_loop(
                *bounds, run_body(kind, s - a),
                (x, new_caches[key], occ, buf),
                unroll=_unroll() if static else None)
    if occ is not None:
        new_caches["attn"] = occ
    return x, new_caches, buf


def decode_step_masked(params, cfg: ModelConfig, caches, token_or_embed,
                       cur_index, depths, *, window_seq_len: int = 0,
                       conf_backend: str = "ref"):
    """Edge half of a decode-serving step: run layers ``0..depths[b]`` per
    sample. The layer loop stops at ``max(depths)``: layers above it are
    skipped, neither computed nor rewritten. Inside the loop a row whose
    depth is below the layer keeps its hidden carry and that layer's cache
    slot (a skipped attention layer simply leaves its ring-buffer slot
    unwritten; the ``pos`` validity mask excludes the hole at future reads,
    so no per-layer write indices are needed — ``cur_index`` stays global).

    Returns (logits, conf (L, B), pred (L, B), hidden (B, 1, D),
    new_caches): ``logits`` is the final LM head applied to the carry —
    meaningful for samples with depths[b] == L-1; ``conf``/``pred`` are
    every exit head's observables as in ``decode_step(all_exits=True)``,
    an exit above a row's depth reading that row's carry at its depth;
    ``hidden`` is the raw carry after each sample's own split layer, the
    payload a mid-generation offload ships to the cloud.
    """
    x = _embed_step(params, cfg, token_or_embed)
    L = cfg.num_layers
    hi = jnp.minimum(jnp.max(depths) + 1, L)
    x, new_caches, buf = _decode_layer_range(
        params, cfg, caches, x, cur_index, 0, hi, lambda i: i <= depths,
        window=cfg.effective_window(window_seq_len), record=True)

    # every exit's hidden: the carry after layer i up to a row's depth,
    # its final carry above it (where the masked rows stood still)
    ran = jnp.arange(L)[:, None] <= depths[None, :]
    pooled = _exit_pools(params, cfg,
                         jnp.where(ran[:, :, None, None], buf, x[None]))
    with jax.named_scope("splitee.exit_heads"):
        conf, pred = stacked_exit_confidence(params, cfg, pooled,
                                             conf_backend=conf_backend)
    with jax.named_scope("splitee.final_head"):
        logits = _last_logits(params, cfg, x)
    return logits, conf, pred, x, new_caches


def decode_step_resume(params, cfg: ModelConfig, caches, hidden,
                       cur_index, depths, active, *,
                       window_seq_len: int = 0):
    """Cloud half of a decode-serving step: resume from the shipped edge
    carry ``hidden`` (B, 1, D) and run layers ``depths[b]+1 .. L-1`` for the
    samples with ``active[b]`` set. The layer loop starts above the
    shallowest active depth (and runs nothing when no row is active):
    layers below it are skipped, neither computed nor rewritten. Inside
    the loop, inactive rows and layers the edge already advanced pass
    through untouched — the returned cache tree is bitwise the input tree
    at those coordinates, so merging it back re-syncs the edge cache.

    Returns (logits, new_caches).
    """
    x = hidden.astype(jnp.dtype(cfg.dtype))
    L = cfg.num_layers
    lo = jnp.min(jnp.where(active, depths, L - 1)) + 1
    x, new_caches, _ = _decode_layer_range(
        params, cfg, caches, x, cur_index, lo, L,
        lambda i: active & (i > depths),
        window=cfg.effective_window(window_seq_len))

    with jax.named_scope("splitee.final_head"):
        logits = _last_logits(params, cfg, x)
    return logits, new_caches


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            backend: str = "ref", cache_seq_len: int = 0):
    """Process the prompt, build decode caches, return final logits: the
    sequence layer (`_layer_full`) over every layer, each returning its
    decode state."""
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, b, s)
    seq_total = cache_seq_len or s
    window = cfg.effective_window(seq_total)
    cache_window = window or seq_total

    def body(carry, inp):
        xx, occ = carry
        lp, _, i = inp
        xx, _, st, occ = _layer_full(
            cfg, params, lp, xx, positions, i, window=window,
            backend=backend, cache_window=cache_window, occ=occ)
        return (xx, occ), (st, None)

    occ = init_caches(cfg, b, seq_total)["attn"] if _shared_block(cfg) \
        else None
    (x, occ), caches, _ = _scan_layers(cfg, params, body, (x, occ))
    if occ is not None:
        caches["attn"] = occ
    return _last_logits(params, cfg, x), caches
