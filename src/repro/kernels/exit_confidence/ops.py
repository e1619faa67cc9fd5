"""Backend dispatch for the exit-confidence ops.

``backend="ref"`` is the pure-jnp oracle, ``"pallas"`` the TPU kernel,
``"pallas_interpret"`` the same kernel under the Pallas interpreter (CPU
validation). Dispatch happens OUTSIDE any jit cache keyed on block sizes:
the ref path ignores ``block_b``/``block_v`` entirely, so it must not
recompile when a backend sweep varies them (it used to — the wrapper was
jitted with the block sizes as static args), and unknown backend strings
raise an actionable error instead of falling through to Pallas.

Both ops take one head, ``h (B, D)`` with ``w (D, V)``, or a stack of
heads, ``h (G, B, D)`` with ``w (G, D, V)`` per head or ``(D, V)``
shared. A stack is scored by ONE kernel launch (the multi-exit forwards
pass all L exit heads at once); results have shape ``h.shape[:-1]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.exit_confidence.kernel import (
    DEFAULT_BLOCK_B, DEFAULT_BLOCK_V, exit_confidence_heads_pallas, row_block)
from repro.kernels.exit_confidence.ref import (
    exit_confidence_fused_ref, exit_confidence_ref)

BACKENDS = ("ref", "pallas", "pallas_interpret")
NORM_KINDS = ("rmsnorm", "layernorm")

# jitted once per data shape — block sizes never enter these cache keys
_ref_jit = jax.jit(exit_confidence_ref)
_fused_ref_jit = jax.jit(exit_confidence_fused_ref, static_argnames=("kind",))


def _check_backend(backend: str) -> None:
    if backend not in BACKENDS:
        raise ValueError(
            f"exit_confidence backend={backend!r} is unknown; choose one of "
            f"{BACKENDS}: 'ref' (pure-jnp oracle), 'pallas' (TPU kernel), "
            f"'pallas_interpret' (kernel under the interpreter, for CPU "
            f"validation)")


def _run_kernel(x, w, bias, norm, *, kind, backend, block_b, block_v):
    """Shape the inputs for `exit_confidence_heads_pallas` and undo it:
    a leading head axis, the batch zero-padded to whole row tiles.

    ``norm`` is None or (gamma, nbias), each (D,) or (G, 1, D)."""
    stacked = x.ndim == 3
    x3 = x if stacked else x[None]
    w3 = w if w.ndim == 3 else w[None]
    b = x3.shape[1]
    bb = row_block(b, block_b)
    x3 = jnp.pad(x3, ((0, 0), (0, -b % bb), (0, 0)))
    v = w3.shape[-1]
    hbias = (jnp.zeros((1, v), jnp.float32) if bias is None
             else jnp.asarray(bias).reshape(1, v))
    kw = dict(kind=kind, block_b=block_b, block_v=block_v,
              interpret=(backend == "pallas_interpret"))
    if norm is not None:
        gamma, nbias = (p.reshape(-1, 1, p.shape[-1]) for p in norm)
        kw.update(gamma=gamma, nbias=nbias)
    conf, pred = exit_confidence_heads_pallas(x3, w3, hbias, **kw)
    conf, pred = conf[:, :b, 0], pred[:, :b, 0]
    return (conf, pred) if stacked else (conf[0], pred[0])


def exit_confidence(h, w, bias=None, *, backend: str = "ref",
                    block_b: int = DEFAULT_BLOCK_B,
                    block_v: int = DEFAULT_BLOCK_V):
    """Confidence + argmax of the exit head: h @ w [+ bias (V,)].

    Returns ``(conf f32, pred i32)`` of shape ``h.shape[:-1]``, where conf
    is the max softmax probability (the paper's C_i).
    """
    _check_backend(backend)
    if backend == "ref":
        return _ref_jit(h, w, bias)
    return _run_kernel(h, w, bias, None, kind=None, backend=backend,
                       block_b=block_b, block_v=block_v)


def exit_confidence_fused(x, norm_params, w, bias=None, *,
                          kind: str = "rmsnorm", backend: str = "ref",
                          block_b: int = DEFAULT_BLOCK_B,
                          block_v: int = DEFAULT_BLOCK_V):
    """Fused exit epilogue: exit-norm + head matmul + online softmax as
    ONE program (the unfused path launches the norm and the confidence
    kernel separately).

    ``x`` is the RAW pooled hidden (pooling selects a token and the norm
    is per-token, so pool and norm commute — the fused form is exact);
    ``norm_params`` is the exit-norm dict ``{"scale"[, "bias"]}`` with
    entries ``(D,)`` shared, or ``(G, D)`` per head for a stack
    ``x (G, B, D)``. ``bias`` is an optional (V,) head bias.
    """
    _check_backend(backend)
    if kind not in NORM_KINDS:
        raise ValueError(
            f"exit_confidence_fused kind={kind!r} is unknown; choose one of "
            f"{NORM_KINDS}")
    gamma = norm_params["scale"]
    nbias = norm_params.get("bias")
    if nbias is None:
        nbias = jnp.zeros_like(gamma)
    if gamma.ndim == 2:                        # per head: (G, D) -> (G, 1, D)
        if x.ndim != 3:
            raise ValueError(
                f"per-head norm params {gamma.shape} need a stack of heads "
                f"x (G, B, D), got x {x.shape}")
        gamma, nbias = gamma[:, None], nbias[:, None]
    if backend == "ref":
        return _fused_ref_jit(x, {"scale": gamma, "bias": nbias}, w, bias,
                              kind=kind)
    return _run_kernel(x, w, bias, (gamma, nbias), kind=kind,
                       backend=backend, block_b=block_b, block_v=block_v)
