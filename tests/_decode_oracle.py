"""The decode test oracle: one decode step as a full ``lax.scan`` over every
layer, rows outside ``live(i)`` masked, written apart from the model's own
layer loop (``transformer._decode_layer_range``) so that the served halves
and ``decode_step`` are checked against a loop that is not theirs. From
the model it takes the per-layer function (``_layer_decode``), the scan
over runs of layers (``_scan_layers``), the row merge, the embedding and
the exit heads.
"""
import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mlp as ff
from repro.models import transformer as tf
from repro.models.common import apply_norm
from repro.sharding import constrain


def shared_attn(cfg, params, xx2, occ, i, m, cur_index, window):
    """The hybrid family's shared attention block after every k-th layer,
    its cache advanced for the rows in ``m`` only."""
    k = cfg.hybrid_attn_every
    sp = params["shared_attn"]

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
            sp["mlp"], apply_norm(xx2, sp["ln2"], cfg.norm), cfg.activation)
        new_sl = tf._mask_rows(m, new_sl, sl)
        occ = jax.tree.map(
            lambda buf, ns: jax.lax.dynamic_update_index_in_dim(
                buf, ns, oi, 0), occ, new_sl)
        return xx2, occ

    return jax.lax.cond(jnp.equal(jnp.mod(i + 1, k), 0), with_attn,
                        lambda a: a, (xx2, occ))


def full_sweep(params, cfg, caches, x, cur_index, live, window):
    """Every layer, rows outside ``live(i)`` masked: (x, caches, pooled)."""
    hybrid = cfg.family == "hybrid" and not cfg.layer_types

    def body(carry, inp):
        xx, occ = carry
        lp, st, i = inp
        m = live(i)
        xx2, new_st = tf._layer_decode(cfg, params, lp, xx, st, cur_index,
                                       window=window)
        if hybrid:
            xx2, occ = shared_attn(cfg, params, xx2, occ, i, m, cur_index,
                                   window)
        xx = jnp.where(m[:, None, None], xx2, xx)
        new_st = tf._mask_rows(m, new_st, st)
        pooled = tf.pool_hidden(cfg, tf._head_norm(cfg, xx,
                                                   lp["exit_norm"]))
        return (xx, occ), (new_st, pooled)

    occ = caches["attn"] if hybrid else None
    (x, occ), new_caches, pooled = tf._scan_layers(cfg, params, body,
                                                   (x, occ), caches)
    if hybrid:
        new_caches["attn"] = occ
    return x, new_caches, pooled


def head(params, cfg, x):
    ews = tf._exit_heads(params, cfg)
    ew = ews if ews.ndim == 2 else ews[-1]
    xf = tf._head_norm(cfg, x, params["final_norm"])
    return constrain(xf[:, -1, :] @ ew, "batch", "model")


def plain_step(params, cfg, caches, token, cur_index, window, depths=None):
    """One step: (logits, conf (L, B), pred (L, B), caches), what
    ``decode_step(all_exits=True)`` returns. Every row is live at every
    layer; with ``depths`` (B,) the rows are masked as the served edge
    masks them, live at layer i where ``i <= depths``."""
    x = tf._embed_step(params, cfg, token)
    every = jnp.ones(x.shape[:1], bool)
    live = (lambda i: every) if depths is None else (lambda i: i <= depths)
    x, new_caches, pooled = full_sweep(params, cfg, caches, x, cur_index,
                                       live, window)
    conf, pred = tf.stacked_exit_confidence(params, cfg, pooled)
    return head(params, cfg, x), conf, pred, new_caches
