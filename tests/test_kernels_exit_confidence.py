"""Kernel sweep: fused exit-confidence vs pure-jnp oracle (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.exit_confidence import ops
from repro.kernels.exit_confidence.ops import (exit_confidence,
                                               exit_confidence_fused)
from repro.kernels.exit_confidence.ref import (exit_confidence_fused_ref,
                                               exit_confidence_ref)
from repro.models.common import apply_norm

SHAPES = [
    (1, 32, 64), (4, 64, 100), (8, 128, 512), (3, 96, 1000),
    (128, 256, 2049), (16, 257, 777),
]


@pytest.mark.parametrize("b,d,v", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matches_oracle(b, d, v, dtype):
    key = jax.random.PRNGKey(b * 1000 + d + v)
    h = jax.random.normal(key, (b, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (d, v), jnp.float32)
         * 0.1).astype(dtype)
    c0, p0 = exit_confidence(h, w, backend="ref")
    c1, p1 = exit_confidence(h, w, backend="pallas_interpret",
                             block_b=64, block_v=256)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1),
                               rtol=tol, atol=tol)
    # bf16 ties can legitimately disagree on argmax; require agreement
    # wherever the two top logits are distinguishable
    if dtype == jnp.float32:
        assert (np.asarray(p0) == np.asarray(p1)).all()


def test_bias_folding():
    key = jax.random.PRNGKey(7)
    h = jax.random.normal(key, (4, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 65)) * 0.2
    bias = jax.random.normal(jax.random.fold_in(key, 2), (65,))
    c0, p0 = exit_confidence(h, w, bias, backend="ref")
    c1, p1 = exit_confidence(h, w, bias, backend="pallas_interpret",
                             block_v=32)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=2e-5,
                               atol=2e-6)
    assert (np.asarray(p0) == np.asarray(p1)).all()


def test_confidence_is_max_softmax_prob():
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (8, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 33))
    conf, pred = exit_confidence_ref(h, w)
    probs = jax.nn.softmax(h @ w, axis=-1)
    np.testing.assert_allclose(np.asarray(conf),
                               np.asarray(jnp.max(probs, -1)), rtol=1e-5)
    assert (np.asarray(pred) == np.asarray(jnp.argmax(probs, -1))).all()
    assert (np.asarray(conf) > 0).all() and (np.asarray(conf) <= 1).all()


# ------------------------------------------------- argmax tie semantics

def test_argmax_tie_break_lowest_index_across_vocab_tiles():
    """Regression: exact logit ties must resolve to the LOWEST index in
    both backends, including ties that straddle a block_v boundary (the
    online update may only take a later tile's max on a STRICT
    improvement). Integer-valued inputs make the tied dots bit-exact."""
    d, v, block_v = 8, 70, 32
    h = jnp.ones((3, d), jnp.float32)
    w_np = np.zeros((d, v), np.float32)
    # identical max columns at 10 (tile 0), 40 (tile 1) and 65 (tile 2)
    for j in (10, 40, 65):
        w_np[:, j] = 2.0
    c0, p0 = exit_confidence(jnp.asarray(h), jnp.asarray(w_np),
                             backend="ref")
    c1, p1 = exit_confidence(jnp.asarray(h), jnp.asarray(w_np),
                             backend="pallas_interpret", block_b=2,
                             block_v=block_v)
    assert (np.asarray(p0) == 10).all()        # first occurrence wins
    assert (np.asarray(p1) == 10).all()
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=1e-6)
    # tie WITHIN a later tile only: lowest index of that tile wins
    w2 = np.zeros((d, v), np.float32)
    w2[:, 40] = w2[:, 41] = 3.0
    for backend, kw in [("ref", {}),
                        ("pallas_interpret", dict(block_v=block_v))]:
        _, p = exit_confidence(h, jnp.asarray(w2), backend=backend, **kw)
        assert (np.asarray(p) == 40).all()


# --------------------------------------------------- dispatch contracts

def test_unknown_backend_raises_actionable_error():
    h = jnp.ones((2, 4))
    w = jnp.ones((4, 8))
    with pytest.raises(ValueError, match="pallas_interpret"):
        exit_confidence(h, w, backend="cuda")
    with pytest.raises(ValueError, match="backend='pallaz'"):
        exit_confidence(h, w, backend="pallaz")
    with pytest.raises(ValueError, match="pallas_interpret"):
        exit_confidence_fused(h, {"scale": jnp.ones((4,))}, w,
                              backend="bogus")
    with pytest.raises(ValueError, match="rmsnorm"):
        exit_confidence_fused(h, {"scale": jnp.ones((4,))}, w,
                              kind="batchnorm")


def test_ref_backend_ignores_block_sizes_no_recompile():
    """Regression: the ref path used to be jitted with block_b/block_v as
    static args, recompiling once per distinct block setting in a sweep.
    Dispatch now happens outside jit, so the cache is keyed on data shape
    only."""
    if not hasattr(ops._ref_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 33))
    exit_confidence(h, w, backend="ref", block_b=8, block_v=16)
    before = ops._ref_jit._cache_size()
    for bb, bv in [(16, 32), (32, 64), (64, 128), (128, 256)]:
        exit_confidence(h, w, backend="ref", block_b=bb, block_v=bv)
    assert ops._ref_jit._cache_size() == before


# ------------------------------------------------------- fused epilogue

FUSED_SHAPES = [(1, 32, 64), (4, 64, 100), (3, 96, 777), (16, 48, 513)]


def _norm_params(key, d, kind, *, rows=None):
    shape = (d,) if rows is None else (rows, d)
    p = {"scale": 1.0 + 0.1 * jax.random.normal(key, shape)}
    if kind == "layernorm":
        p["bias"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 9),
                                            shape)
    return p


@pytest.mark.parametrize("b,d,v", FUSED_SHAPES)
@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("with_bias", [False, True])
def test_fused_matches_ref(b, d, v, kind, with_bias):
    key = jax.random.PRNGKey(b + d + v)
    x = jax.random.normal(key, (b, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.1
    bias = (jax.random.normal(jax.random.fold_in(key, 2), (v,))
            if with_bias else None)
    npar = _norm_params(jax.random.fold_in(key, 3), d, kind)
    c0, p0 = exit_confidence_fused(x, npar, w, bias, kind=kind,
                                   backend="ref")
    c1, p1 = exit_confidence_fused(x, npar, w, bias, kind=kind,
                                   backend="pallas_interpret", block_b=8,
                                   block_v=128)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=2e-5,
                               atol=2e-6)
    assert (np.asarray(p0) == np.asarray(p1)).all()


@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
def test_fused_per_row_norm_params(kind):
    """Norm params of shape (B, D) apply row b's gamma/beta to row b when
    each row is its own head (x (B, 1, D)); a single head refuses them."""
    b, d, v = 6, 32, 65
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (b, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.2
    npar = _norm_params(jax.random.fold_in(key, 3), d, kind, rows=b)
    want = [exit_confidence_fused(x[i:i + 1],
                                  {k: a[i] for k, a in npar.items()}, w,
                                  kind=kind, backend="ref")
            for i in range(b)]
    c0 = np.concatenate([np.asarray(c) for c, _ in want])
    p0 = np.concatenate([np.asarray(p) for _, p in want])
    for backend in ("ref", "pallas_interpret"):
        c1, p1 = exit_confidence_fused(x[:, None], npar, w, kind=kind,
                                       backend=backend, block_v=32)
        np.testing.assert_allclose(c0, np.asarray(c1)[:, 0], rtol=2e-5,
                                   atol=2e-6)
        assert (p0 == np.asarray(p1)[:, 0]).all()
        with pytest.raises(ValueError, match="per-head norm params"):
            exit_confidence_fused(x, npar, w, kind=kind, backend=backend)


STACKED = [(3, 5, 32, 2), (4, 9, 48, 70), (2, 16, 64, 300)]


@pytest.mark.parametrize("g,b,d,v", STACKED)
@pytest.mark.parametrize("shared_head", [False, True])
@pytest.mark.parametrize("kind", [None, "rmsnorm", "layernorm"])
def test_stacked_heads_match_per_head_ref(g, b, d, v, shared_head, kind):
    """G exit heads scored by one launch equal G single-head oracle
    calls: per-head (G, D, V) or shared (D, V) weights, per-head (G, D)
    norm params, batches that are not a multiple of the 8-row tile."""
    key = jax.random.PRNGKey(g * 100 + b + v)
    x = jax.random.normal(key, (g, b, d))
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (d, v) if shared_head else (g, d, v)) * 0.2
    npar = (None if kind is None else
            _norm_params(jax.random.fold_in(key, 3), d, kind, rows=g))

    def one_head(i, backend, **kw):
        wi = w if shared_head else w[i]
        if kind is None:
            return exit_confidence(x[i], wi, backend=backend, **kw)
        pi = {k: a[i] for k, a in npar.items()}
        return exit_confidence_fused(x[i], pi, wi, kind=kind,
                                     backend=backend, **kw)

    def all_heads(backend, **kw):
        if kind is None:
            return exit_confidence(x, w, backend=backend, **kw)
        return exit_confidence_fused(x, npar, w, kind=kind,
                                     backend=backend, **kw)

    want = [one_head(i, "ref") for i in range(g)]
    c0 = np.stack([np.asarray(c) for c, _ in want])
    p0 = np.stack([np.asarray(p) for _, p in want])
    for backend, kw in (("ref", {}),
                        ("pallas_interpret", dict(block_b=8, block_v=128))):
        c1, p1 = all_heads(backend, **kw)
        assert c1.shape == p1.shape == (g, b)
        np.testing.assert_allclose(c0, np.asarray(c1), rtol=2e-5, atol=2e-6)
        assert (p0 == np.asarray(p1)).all()


def test_stacked_heads_are_one_launch():
    """The per-exit heads go through ONE pallas_call, not a vmap over it
    (which the TPU compiler refuses)."""
    x = jnp.ones((12, 4, 16))
    w = jnp.ones((12, 16, 2))
    jx = jax.make_jaxpr(lambda x, w: exit_confidence(
        x, w, backend="pallas_interpret"))(x, w).jaxpr
    assert _count(jx, "pallas_call") == 1


def test_fused_ref_equals_unfused_compose():
    """The fused oracle IS norm-then-confidence: bitwise the same ops."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (4, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 33))
    npar = _norm_params(jax.random.fold_in(key, 2), 16, "rmsnorm")
    c0, p0 = exit_confidence_fused_ref(x, npar, w, kind="rmsnorm")
    c1, p1 = exit_confidence_ref(apply_norm(x, npar, "rmsnorm"), w)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


# -------------------------------------------- one-launch regression pin

def _walk_eqns(jaxpr, *, into_pallas=True):
    for eqn in jaxpr.eqns:
        yield eqn
        if not into_pallas and eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for v in (val if isinstance(val, (list, tuple)) else [val]):
                closed = getattr(v, "jaxpr", None)
                if hasattr(v, "eqns"):
                    yield from _walk_eqns(v, into_pallas=into_pallas)
                elif closed is not None and hasattr(closed, "eqns"):
                    yield from _walk_eqns(closed, into_pallas=into_pallas)


def _count(jaxpr, name, *, into_pallas=True):
    return sum(e.primitive.name == name
               for e in _walk_eqns(jaxpr, into_pallas=into_pallas))


def test_fused_epilogue_is_one_program():
    """The fused variant must trace to ONE pallas_call with the norm
    inside it; the unfused path runs the norm as a separate XLA program
    (rsqrt outside the kernel) before its single kernel launch."""
    d, v = 16, 64
    x = jnp.ones((4, d))
    w = jnp.ones((d, v))
    npar = {"scale": jnp.ones((d,))}

    def fused(x, g, w):
        return exit_confidence_fused(x, {"scale": g}, w,
                                     backend="pallas_interpret",
                                     block_b=4, block_v=32)

    def unfused(x, g, w):
        h = apply_norm(x, {"scale": g}, "rmsnorm")
        return exit_confidence(h, w, backend="pallas_interpret",
                               block_b=4, block_v=32)

    jf = jax.make_jaxpr(fused)(x, npar["scale"], w).jaxpr
    ju = jax.make_jaxpr(unfused)(x, npar["scale"], w).jaxpr
    assert _count(jf, "pallas_call") == 1
    assert _count(ju, "pallas_call") == 1
    # the norm's rsqrt lives INSIDE the fused kernel, OUTSIDE the unfused
    assert _count(jf, "rsqrt", into_pallas=False) == 0
    assert _count(ju, "rsqrt", into_pallas=False) >= 1
    assert _count(jf, "rsqrt", into_pallas=True) >= 1
