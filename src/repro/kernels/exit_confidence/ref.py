"""Pure-jnp oracle for the fused exit-confidence op.

Given pooled hidden states ``h (B, D)`` and an exit head ``w (D, V)``
(+ optional bias), return the paper's confidence ``C_i = max_c softmax(l)_c``
and the argmax class — materializing the full logits (the thing the Pallas
kernel avoids). A stack of heads is ``h (G, B, D)`` with ``w (G, D, V)``
or a shared ``(D, V)``.
"""
from __future__ import annotations

import jax.numpy as jnp


def exit_confidence_ref(h, w, bias=None):
    logits = jnp.asarray(h, jnp.float32) @ jnp.asarray(w, jnp.float32)
    if bias is not None:
        logits = logits + jnp.asarray(bias, jnp.float32)
    m = jnp.max(logits, axis=-1)
    s = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    conf = 1.0 / s  # exp(m - logsumexp) = 1 / sum exp(l - m)
    # jnp.argmax returns the FIRST maximal index on ties — the Pallas
    # kernel's cross-tile tie-break is pinned to match (lowest-index-wins)
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return conf, pred


def exit_confidence_fused_ref(x, norm_params, w, bias=None, *,
                              kind: str = "rmsnorm"):
    """Fused exit-epilogue oracle: norm -> exit_confidence, unfused.

    ``x (B, D)`` is the RAW pooled hidden, ``norm_params`` the exit-norm
    parameter dict (``{"scale"[, "bias"]}``, entries ``(D,)`` shared; for
    a stack ``x (G, B, D)``, ``(G, 1, D)`` per head). This is by
    construction the exact composition the
    serving paths run when not fusing (``apply_norm`` then
    ``exit_confidence_ref``), so it is the bitwise semantics anchor the
    fused Pallas kernel is validated against.
    """
    from repro.models.common import apply_norm

    return exit_confidence_ref(apply_norm(x, norm_params, kind), w, bias)
