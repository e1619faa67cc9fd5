"""Decode serving (serving/decode.py + serving/kvcache.py): the
per-token early-exit/offload runtime behind ``workload="decode"``.

The suite is the subsystem's bit-identity ladder:

* **Forced-final differential pin** — ``split_policy="final"`` through the
  full `serve()` facade generates bit-identically to a plain full-depth
  decode loop (tokens AND per-step logits AND the final cache tree), on a
  transformer, a recurrent arch and the shared-block hybrid at B in
  {1, 8}. The plain loop is the test oracle's full ``lax.scan`` sweep
  with every row live (``_decode_oracle``), not the model's own layer
  loop. The whole masked-serving machinery must collapse to vanilla
  decode when no split happens.
* **Ledger replay property** (vendored hypothesis) — a bandit run's
  recorded per-step realized depths + offload decisions, replayed from a
  FRESH prefill cache through the same edge/cloud programs, regenerate the
  exact token matrix. This is the KV-consistency claim: exiting at ℓ for k
  steps then going deep again reads the same cache a dedicated
  realized-depth decode would have built.
* **Offload re-sync property** — edge(ℓ) + cloud resume at quant="none"
  is bitwise the full-depth step (logits + caches), and an all-inactive
  resume is a cache no-op: shipping state through the offload path loses
  nothing when the codec is lossless.
* **Multi-tenant pin** — two tenants (different model families, different
  workloads) behind one `MultiTenantEngine` produce per-tenant reports
  identical to each tenant served alone, with the scheduler's conservation
  law extended per tenant.

Plus report-shape/accounting sanity and the `ServingConfig` decode
validation surface.
"""
import copy
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # vendored fallback
    from _hypothesis_fallback import given, settings, strategies as st

from _decode_oracle import plain_step
from repro.configs import get_smoke_config
from repro.core import CostModel
from repro.models.api import build_model
from repro.serving import (DecodeRuntime, EdgeCloudRuntime, Engine,
                           MultiTenantEngine, ServingConfig, TenantSpec,
                           serve)
from repro.serving.decode import _DecodeSession
from repro.serving.kvcache import (DecodeCacheManager, hidden_raw_bytes,
                                   offload_scale_vec, per_step_layer_bytes,
                                   step_slice_bytes)
from repro.serving.offload_codec import OffloadCodec

ARCHS = ["qwen3-1.7b", "rwkv6-3b"]      # attention + recurrent families
S, T = 4, 3                              # prompt length / generated tokens

_BEDS = {}


def _bed(arch):
    """(cfg, params, runtime, cost) — module-cached per arch; f32 so every
    assertion can be bitwise."""
    if arch not in _BEDS:
        cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        cost = CostModel(num_layers=cfg.num_layers, alpha=0.5)
        _BEDS[arch] = (cfg, params, DecodeRuntime(cfg), cost)
    return _BEDS[arch]


def _prompts(cfg, n, seed=0, length=S):
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, cfg.vocab_size, size=length)}
            for _ in range(n)]


def _trees_equal(a, b):
    return all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------- forced-final differential pin

@pytest.mark.parametrize("arch", ARCHS + ["zamba2-1.2b"])
@pytest.mark.parametrize("B", [1, 8])
def test_forced_final_matches_plain_decode(arch, B):
    """serve(workload='decode', split_policy='final') == a hand-rolled
    full-depth loop of the oracle's full sweep, every row live at every
    layer under the session's own masks (depth L-1 for every row):
    tokens, per-step logits, and the final cache tree, all bitwise."""
    cfg, params, rt, cost = _bed(arch)
    L = cfg.num_layers
    total = S + T
    samples = _prompts(cfg, B, seed=3)

    rep = serve(rt, params, iter(samples), cost,
                ServingConfig(batch_size=B, workload="decode",
                              max_new_tokens=T, split_policy="final"))
    assert rep.path == "decode"
    got_tokens = np.asarray(rep.decode["tokens"])          # (B, T)

    # plain full-depth reference, jitted like the serving runtime
    plain = jax.jit(
        lambda p, c, t, i, d: plain_step(p, cfg, c, t, i,
                                         cfg.effective_window(total), d),
        static_argnums=(3,))
    depths = jnp.full((B,), L - 1, jnp.int32)
    prompts = np.stack([np.asarray(s["tokens"], np.int32) for s in samples])
    logits0, caches = rt.prefill_fn(params, jnp.asarray(prompts), total)
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)
    ref_tokens = np.zeros((B, T), np.int32)
    ref_logits = []
    for t in range(T):
        lg, _, _, caches = plain(params, caches, tok, S + t, depths)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        ref_tokens[:, t] = np.asarray(tok)
        ref_logits.append(np.asarray(lg))
    np.testing.assert_array_equal(got_tokens, ref_tokens)

    # final cache state + logits: replay the serving programs (the exact
    # calls the session makes under split_policy="final") against the
    # plain loop's tree
    logits0, m_caches = rt.prefill_fn(params, jnp.asarray(prompts), total)
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)
    for t in range(T):
        lg, _, _, _, pred_fin, _, m_caches = rt.edge_fn(
            params, m_caches, tok, S + t, depths, total)
        np.testing.assert_array_equal(np.asarray(lg), ref_logits[t])
        tok = pred_fin
    assert _trees_equal(caches, m_caches)

    # report accounting for the degenerate policy: nothing offloads
    assert rep.decode["split_policy"] == "final"
    assert rep.decode["offloads_per_sequence"].sum() == 0
    assert rep.decode["wire_bytes_per_sequence"].sum() == 0
    np.testing.assert_array_equal(rep.decode["realized_depths"], L - 1)


# ------------------------------------------------- ledger replay property

def _replay_from_ledger(rt, params, prompts, dec):
    """Regenerate a decode report's token matrix from a FRESH prefill
    cache, driving the edge/cloud programs with the recorded realized
    depths and offload decisions only."""
    cfg = rt.cfg
    L = cfg.num_layers
    B, T_ = dec["tokens"].shape
    total = prompts.shape[1] + T_
    Sp = prompts.shape[1]
    logits0, caches = rt.prefill_fn(params, jnp.asarray(prompts), total)
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)
    gen = np.zeros((B, T_), np.int32)
    for t in range(T_):
        arms = np.asarray(dec["realized_depths"][:, t], np.int64)
        depths_dev = jnp.asarray(arms, jnp.int32)
        _, _, pred, _, pred_fin, hidden, caches = rt.edge_fn(
            params, caches, tok, Sp + t, depths_dev, total)
        pred_np, pred_fin_np = np.asarray(pred), np.asarray(pred_fin)
        toks = np.empty(B, np.int32)
        for b in range(B):
            toks[b] = (pred_fin_np[b] if arms[b] + 1 == L
                       else pred_np[arms[b], b])
        off = np.asarray(dec["offloaded_steps"][:, t], bool)
        if off.any():
            _, _, pred_L, caches = rt.cloud_fn(
                params, caches, hidden, Sp + t, depths_dev,
                jnp.asarray(off), total)
            toks[off] = np.asarray(pred_L)[off]
        gen[:, t] = toks
        tok = jnp.asarray(toks)
    return gen


@pytest.mark.parametrize("arch", ARCHS)
def test_bandit_run_replays_from_fresh_cache(arch):
    """KV-consistency pin: the bandit run's ledger fully determines its
    tokens. Exit-at-ℓ-for-k-steps-then-full-depth must read the same
    cache a fresh realized-depth decode builds — any stale or wrongly
    advanced slot would diverge the replay."""
    cfg, params, rt, cost = _bed(arch)
    B = 8
    samples = _prompts(cfg, B, seed=5)
    rep = serve(rt, params, iter(samples), cost,
                ServingConfig(batch_size=B, workload="decode",
                              max_new_tokens=T))
    dec = rep.decode
    # the run must actually mix depths/offloads or the pin is vacuous
    assert len(np.unique(dec["realized_depths"])) >= 2
    assert 0 < dec["offloaded_steps"].sum()
    prompts = np.stack([np.asarray(s["tokens"], np.int32) for s in samples])
    gen = _replay_from_ledger(rt, params, prompts, dec)
    np.testing.assert_array_equal(gen, np.asarray(dec["tokens"]))


@given(st.integers(0, 10**6))
@settings(max_examples=4, deadline=None)
def test_exit_then_deep_replay_property(seed):
    """Random per-step depth schedules (arbitrary exit/deepen patterns,
    no offloads): stepping the masked edge through schedule D from a
    fresh cache twice is deterministic AND poking the same schedule with
    a different final full-depth step still matches a fresh replay —
    i.e. k masked steps leave exactly the cache a replay of those
    realized depths produces."""
    cfg, params, rt, _ = _bed(ARCHS[0])
    L = cfg.num_layers
    rng = np.random.default_rng(seed)
    B, T_ = 4, 4
    total = S + T_
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    sched = rng.integers(0, L, (T_ - 1, B))
    sched = np.concatenate([sched, np.full((1, B), L - 1)], 0)  # deep last

    def run():
        logits0, caches = rt.prefill_fn(params, jnp.asarray(prompts), total)
        tok = jnp.argmax(logits0, -1).astype(jnp.int32)
        outs = []
        for t in range(T_):
            depths = jnp.asarray(sched[t], jnp.int32)
            _, conf, pred, _, pred_fin, _, caches = rt.edge_fn(
                params, caches, tok, S + t, depths, total)
            pred_np, fin_np = np.asarray(pred), np.asarray(pred_fin)
            toks = np.asarray(
                [fin_np[b] if sched[t, b] + 1 == L
                 else pred_np[sched[t, b], b] for b in range(B)], np.int32)
            outs.append((np.asarray(conf), toks))
            tok = jnp.asarray(toks)
        return outs, caches

    outs_a, caches_a = run()
    outs_b, caches_b = run()
    for (ca, ta), (cb, tb) in zip(outs_a, outs_b):
        np.testing.assert_array_equal(ca, cb)
        np.testing.assert_array_equal(ta, tb)
    assert _trees_equal(caches_a, caches_b)


# -------------------------------------------- offload re-sync properties

@given(st.integers(0, 10**6))
@settings(max_examples=4, deadline=None)
def test_offload_resync_lossless_at_quant_none(seed):
    """edge(ℓ) + cloud resume == one full-depth step, bitwise in logits
    and the whole cache tree, for random split depths — offloading
    mid-generation with a lossless codec must be invisible."""
    cfg, params, rt, _ = _bed(ARCHS[1])
    L = cfg.num_layers
    rng = np.random.default_rng(seed)
    B = 6
    total = S + 1
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    _, caches = rt.prefill_fn(params, jnp.asarray(prompts), total)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    depths = jnp.asarray(rng.integers(0, L, B), jnp.int32)

    lg_full, _, _, _, _, _, c_full = rt.edge_fn(
        params, caches, tok, S, jnp.full((B,), L - 1, jnp.int32), total)
    _, _, _, _, _, hidden, c_edge = rt.edge_fn(
        params, caches, tok, S, depths, total)
    lg_res, _, _, c_res = rt.cloud_fn(
        params, c_edge, hidden, S, depths, jnp.ones(B, bool), total)
    np.testing.assert_array_equal(np.asarray(lg_full), np.asarray(lg_res))
    assert _trees_equal(c_full, c_res)

    # all-inactive resume: pure pass-through
    _, _, _, c_noop = rt.cloud_fn(
        params, c_edge, hidden, S, depths, jnp.zeros(B, bool), total)
    assert _trees_equal(c_edge, c_noop)


# ----------------------------------------------------- report accounting

@pytest.fixture(scope="module")
def bandit_report():
    cfg, params, rt, cost = _bed(ARCHS[0])
    samples = _prompts(cfg, 12, seed=9)
    rep = serve(rt, params, iter(samples), cost,
                ServingConfig(batch_size=4, workload="decode",
                              max_new_tokens=T))
    return cfg, cost, rep


def test_decode_report_shapes_and_conservation(bandit_report):
    cfg, cost, rep = bandit_report
    dec = rep.decode
    nseq, L = dec["sequences"], cost.num_layers
    assert nseq == 12 and rep.n == nseq * T == dec["tokens_generated"]
    assert len(rep.preds) == rep.n
    assert dec["tokens"].shape == (nseq, T)
    assert dec["realized_depths"].shape == (nseq, T)
    # preds are the step-major flattening of the token matrix
    got = np.concatenate([dec["tokens"][i:i + 4].T.reshape(-1)
                          for i in range(0, nseq, 4)])
    np.testing.assert_array_equal(rep.preds, got)
    # every (seq, step) either exited on the edge or offloaded — never
    # both, never neither
    ex, off = dec["exited_steps"], dec["offloaded_steps"]
    np.testing.assert_array_equal(ex ^ off, True)
    assert dec["exits_per_layer_per_step"].shape == (T, L)
    assert dec["exits_per_layer_per_step"].sum() == ex.sum()
    np.testing.assert_array_equal(dec["offloads_per_sequence"],
                                  off.sum(axis=1))
    # wire accounting: the controller's byte total IS the per-sequence
    # ledger's total, and each offload costs hidden + ≤depth slice bytes
    assert rep.offload_bytes == dec["wire_bytes_per_sequence"].sum() > 0
    raw_h = hidden_raw_bytes(cfg)
    depths_off = dec["realized_depths"][off]
    expect = sum(raw_h + step_slice_bytes(cfg, int(d)) for d in depths_off)
    assert rep.offload_bytes == expect
    assert dec["tokens_per_sec"] > 0 and dec["decode_wall_s"] > 0


STEP_PARTS = ("select", "edge", "edge_wait", "decide", "codec", "cloud",
              "cloud_wait", "fold")


def test_decode_telemetry_counts_the_rounds(bandit_report):
    """The session's tracer counts what the report shows: a step span per
    token round, a cloud launch per round with any row offloaded, each
    parent's total covering its children's, and the push spans' total
    as the decode wall time."""
    cfg, cost, rep = bandit_report
    sp, cnt = rep.telemetry["spans"], rep.telemetry["counts"]
    dec = rep.decode
    B = rep.batch_size
    pushes = dec["sequences"] // B
    assert sp["splitee.decode.push"]["n"] == pushes
    assert sp["splitee.decode.prefill"]["n"] == pushes
    assert sp["splitee.decode.step"]["n"] == pushes * T
    assert cnt["splitee.decode.steps"] == pushes * T
    off = np.asarray(dec["offloaded_steps"]).reshape(pushes, B, T)
    launches = int(off.any(axis=1).sum())
    assert 0 < launches < pushes * T
    assert cnt["splitee.decode.cloud_launches"] == launches
    assert cnt["splitee.decode.offload_rows"] == int(off.sum())
    for part in ("codec", "cloud", "cloud_wait"):
        assert sp[f"splitee.decode.{part}"]["n"] == launches
    for part in ("select", "edge", "edge_wait", "decide", "fold"):
        assert sp[f"splitee.decode.{part}"]["n"] == pushes * T
    tree = {"splitee.decode.push": ("prefill", "step"),
            "splitee.decode.step": STEP_PARTS}
    for parent, parts in tree.items():
        kids = sum(sp[f"splitee.decode.{k}"]["total_ms"] for k in parts)
        assert sp[parent]["total_ms"] >= kids - 1e-6
        assert sp[parent]["self_ms"] == pytest.approx(
            sp[parent]["total_ms"] - kids, abs=1e-6)
    assert all(v["self_ms"] >= 0 for v in sp.values())
    assert dec["decode_wall_s"] == sp["splitee.decode.push"]["total_ms"] / 1e3


def test_decode_telemetry_counts_the_layers_run(bandit_report):
    """The layer counters add up each round's loop bounds from the
    realized depths: the edge runs up to the round's deepest split, the
    cloud from above the shallowest offloaded split to the last layer."""
    cfg, cost, rep = bandit_report
    cnt, dec = rep.telemetry["counts"], rep.decode
    B, L = rep.batch_size, cost.num_layers
    depths = np.asarray(dec["realized_depths"]).reshape(-1, B, T)
    off = np.asarray(dec["offloaded_steps"]).reshape(-1, B, T)
    edge = cloud = 0
    for d, o in zip(depths, off):                  # one push: (B, T)
        for t in range(T):
            edge += int(d[:, t].max()) + 1
            if o[:, t].any():
                cloud += L - 1 - int(d[o[:, t], t].min())
    assert cnt["splitee.decode.edge_layers"] == edge
    assert cnt["splitee.decode.cloud_layers"] == cloud
    steps = cnt["splitee.decode.steps"]
    assert steps <= edge <= L * steps


def test_decode_spans_share_the_profiler_clock(tmp_path):
    """A push under the JAX profiler writes its step spans, with their
    ids, into the trace; the parts of a round nest inside its step span,
    and the rounds' XLA ops (host events on the CPU backend) run inside
    the step spans: one clock for both."""
    from jax.profiler import ProfileData

    cfg, params, rt, cost = _bed(ARCHS[0])
    sess = _DecodeSession(rt, params, cost, batch_size=2, max_new_tokens=T)
    batch = _prompts(cfg, 2, seed=21)
    sess.push(batch)                       # compile outside the trace
    before = sess.tracer.snapshot()["spans"]
    with jax.profiler.trace(str(tmp_path)):
        sess.push(batch)
    after = sess.tracer.snapshot()["spans"]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith("splitee.decode."):
                    spans.append((ev.name, iv, dict(ev.stats)))
                elif "hlo_op" in dict(ev.stats):
                    ops.append(iv)
    steps = [(iv, st) for name, iv, st in spans
             if name == "splitee.decode.step"]
    assert sorted(int(st["step"]) for _, st in steps) == list(range(T))
    assert {int(st["push"]) for _, st in steps} == {1}
    parts = [iv for name, iv, _ in spans
             if name.rsplit(".", 1)[1] in STEP_PARTS]
    assert len(parts) == sum(
        after[k]["n"] - before.get(k, {"n": 0})["n"]
        for k in (f"splitee.decode.{p}" for p in STEP_PARTS) if k in after)
    for s, e in parts:
        assert any(a <= s and e <= b for (a, b), _ in steps)
    inside = [(s, e) for s, e in ops
              if any(a <= s and e <= b for (a, b), _ in steps)]
    assert inside


def test_decode_programs_name_their_scopes():
    """Each decode program's ops carry its scope and the layer sweep's or
    head's scope in their metadata (the edge's and the cloud's layer
    sweeps are told apart in a device trace)."""
    cfg, params, rt, cost = _bed(ARCHS[0])
    B, total = 2, S + T
    prompts = jnp.asarray(np.stack(
        [p["tokens"] for p in _prompts(cfg, B)]).astype(np.int32))
    _, caches = rt.prefill_fn(params, prompts, total)
    tok = jnp.zeros(B, jnp.int32)
    depths = jnp.zeros(B, jnp.int32)
    edge = rt.edge_fn.lower(params, caches, tok, S, depths,
                            total).as_text(debug_info=True)
    for scope in ("splitee.edge", "splitee.layers", "splitee.exit_heads",
                  "splitee.final_head"):
        assert scope in edge
    hidden = jnp.zeros((B, 1, cfg.d_model), jnp.float32)
    cloud = rt.cloud_fn.lower(params, caches, hidden, S, depths,
                              jnp.ones(B, bool),
                              total).as_text(debug_info=True)
    for scope in ("splitee.cloud", "splitee.layers", "splitee.final_head"):
        assert scope in cloud
    assert "splitee.exit_heads" not in cloud
    assert "splitee.prefill" in rt.prefill_fn.lower(
        params, prompts, total).as_text(debug_info=True)


def test_engine_decode_matches_one_shot_serve():
    cfg, params, rt, cost = _bed(ARCHS[0])
    config = ServingConfig(batch_size=4, workload="decode",
                           max_new_tokens=T)
    samples = _prompts(cfg, 12, seed=11)
    ref = serve(rt, params, iter(samples), cost, config)
    eng = Engine(rt, params, cost, config)
    i = 0
    for chunk in (3, 1, 5, 2, 1):
        eng.submit(samples[i:i + chunk])
        i += chunk
    got = eng.close()
    assert got.path == "decode"
    np.testing.assert_array_equal(ref.preds, got.preds)
    np.testing.assert_array_equal(ref.arms, got.arms)
    np.testing.assert_array_equal(ref.rewards, got.rewards)
    assert ref.cost_total == got.cost_total
    np.testing.assert_array_equal(ref.decode["tokens"],
                                  got.decode["tokens"])


def test_codec_decode_run_meters_encoded_bytes():
    """With a lossy codec the hidden payload is metered at codec bytes
    (+ raw slice bytes) and the (L,) offload scale reprices the bandit's
    communication term arm-by-arm."""
    cfg, params, rt, cost = _bed(ARCHS[0])
    codec = OffloadCodec(quant="int8", error_feedback=True)
    rep = serve(rt, params, iter(_prompts(cfg, 8, seed=13)), cost,
                ServingConfig(batch_size=8, workload="decode",
                              max_new_tokens=T, offload_quant="int8",
                              offload_error_feedback=True))
    dec = rep.decode
    off = dec["offloaded_steps"]
    assert off.sum() > 0
    wire_h = codec.row_bytes(1, cfg.d_model, np.dtype(cfg.dtype).itemsize)
    depths_off = dec["realized_depths"][off]
    expect = sum(wire_h + step_slice_bytes(cfg, int(d))
                 for d in depths_off)
    assert rep.offload_bytes == dec["wire_bytes_per_sequence"].sum() \
        == expect


# ------------------------------------------------ host <-> device schedule

def _spied(rt):
    """A copy of the runtime (the same jitted programs) whose edge and
    cloud calls record, by step, the ``hidden`` the edge returned and the
    ``hidden``/``active`` the cloud received."""
    spy = copy.copy(rt)
    edge_hidden, cloud_in = {}, {}

    def edge_fn(params, caches, tok, step, depths, total):
        out = rt.edge_fn(params, caches, tok, step, depths, total)
        edge_hidden[step] = out[5]
        return out

    def cloud_fn(params, caches, hidden, step, depths, active, total):
        cloud_in[step] = (hidden, np.asarray(active))
        return rt.cloud_fn(params, caches, hidden, step, depths, active,
                           total)

    spy.edge_fn, spy.cloud_fn = edge_fn, cloud_fn
    return spy, edge_hidden, cloud_in


def _spied_push(codec, seed=9):
    cfg, params, rt, cost = _bed(ARCHS[0])
    spy, edge_hidden, cloud_in = _spied(rt)
    sess = _DecodeSession(spy, params, cost, batch_size=4,
                          max_new_tokens=T, codec=codec)
    sess.push(_prompts(cfg, 4, seed=seed))
    assert cloud_in, "no round offloaded: the test needs a cloud launch"
    return sess, edge_hidden, cloud_in


def test_identity_codec_payload_stays_on_the_device():
    """Without a codec the cloud is handed the very device array the
    edge returned: no host copy, no upload."""
    sess, edge_hidden, cloud_in = _spied_push(None)
    for step, (hidden, _) in cloud_in.items():
        assert hidden is edge_hidden[step]
    cnt = sess.tracer.snapshot()["counts"]
    assert cnt["splitee.decode.cloud_launches"] == len(cloud_in)


def test_codec_cloud_reads_the_decoded_rows():
    """With an int8 codec the cloud reads the codec's reconstruction in
    the offloaded rows and the edge's hidden, bit for bit, in the rest."""
    codec = OffloadCodec(quant="int8")
    _, edge_hidden, cloud_in = _spied_push(codec)
    for step, (hidden, active) in cloud_in.items():
        raw = np.asarray(edge_hidden[step])
        got = np.asarray(hidden)
        assert hidden is not edge_hidden[step]
        want = codec.decode(codec.encode(raw[active])).astype(raw.dtype)
        np.testing.assert_array_equal(got[active], want)
        np.testing.assert_array_equal(got[~active], raw[~active])
        assert not np.array_equal(got[active], raw[active])   # lossy


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_host_fetches_count_the_blocking_reads(quant):
    """One blocking fetch per program run (the edge's every round, the
    cloud's every launch), plus the host read of the hidden per launch
    where a codec needs it."""
    codec = None if quant == "none" else OffloadCodec(quant=quant)
    sess, _, cloud_in = _spied_push(codec)
    cnt = sess.tracer.snapshot()["counts"]
    steps, launches = cnt["splitee.decode.steps"], len(cloud_in)
    assert steps == T
    per_launch = 1 if codec is None else 2
    assert cnt["splitee.decode.host_fetches"] == steps + per_launch * launches


# ------------------------------------------------------ kvcache closed forms

@pytest.mark.parametrize("arch", ARCHS + ["zamba2-1.2b",
                                          "granite-4.0-h-micro"])
def test_per_step_bytes_match_real_cache_growth(arch):
    """The closed-form per-layer step bytes must equal the real cache's
    per-step footprint: summing all layers reproduces total cache bytes
    per slot/state, and the cumsum is strictly increasing (deeper splits
    always ship more)."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    per = per_step_layer_bytes(cfg)
    assert per.shape == (cfg.num_layers,) and (per >= 0).all()
    assert per.sum() > 0
    cum = np.cumsum(per)
    assert (np.diff(cum) >= 0).all()
    assert step_slice_bytes(cfg, cfg.num_layers - 1) == int(cum[-1])
    # scale vector: identity without a codec, (L,) and positive with one
    assert np.all(offload_scale_vec(cfg, None) == 1.0)
    vec = offload_scale_vec(cfg, OffloadCodec(quant="int8"))
    assert vec.shape == (cfg.num_layers,) and (vec > 0).all()


def test_cache_manager_error_feedback_residual_is_per_sequence():
    cfg, params, rt, _ = _bed(ARCHS[0])
    prompts = np.stack([s["tokens"] for s in _prompts(cfg, 3, seed=17)])
    _, caches = rt.prefill_fn(params, jnp.asarray(prompts.astype(np.int32)),
                              S + 1)
    codec = OffloadCodec(quant="int8", error_feedback=True)
    mgr = DecodeCacheManager(cfg, caches, codec=codec)
    hidden = np.random.default_rng(0).standard_normal(
        (3, 1, cfg.d_model)).astype(np.float32)
    mgr.ship_hidden(hidden, np.asarray([0, 2]))
    assert np.abs(mgr._residual[[0, 2]]).sum() >= 0
    np.testing.assert_array_equal(mgr._residual[1], 0.0)   # untouched row


# ---------------------------------------------------------- multi-tenant

def _classify_bed():
    cfg = dataclasses.replace(get_smoke_config(ARCHS[1]), dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    cost = CostModel(num_layers=cfg.num_layers, alpha=0.5)
    return cfg, params, EdgeCloudRuntime(cfg), cost


def test_multi_tenant_reports_match_solo_engines():
    """Two tenants (decode on an attention arch, classify on a recurrent
    arch) behind ONE MultiTenantEngine: each tenant's report equals the
    report of a solo Engine fed the same traffic, and the shared
    scheduler conserves requests per tenant."""
    cfg_a, p_a, rt_a, cost_a = _bed(ARCHS[0])
    sc_a = ServingConfig(batch_size=2, workload="decode", max_new_tokens=2)
    cfg_b, p_b, rt_b, cost_b = _classify_bed()
    sc_b = ServingConfig(batch_size=2)

    rng = np.random.default_rng(21)
    sa = _prompts(cfg_a, 5, seed=21)
    sb = [{"tokens": rng.integers(0, cfg_b.vocab_size, size=8),
           "label": int(rng.integers(0, 2))} for _ in range(5)]

    mte = MultiTenantEngine({
        "alpha": TenantSpec(rt_a, p_a, cost_a, sc_a),
        "beta": TenantSpec(rt_b, p_b, cost_b, sc_b),
    })
    # interleaved arrival: formation must still be tenant-pure
    for x, y in zip(sa, sb):
        mte.submit("alpha", [x])
        mte.submit("beta", [y])
    reps = mte.close()

    solo = {}
    for name, (rt, p, cost, sc, samples) in {
            "alpha": (rt_a, p_a, cost_a, sc_a, sa),
            "beta": (rt_b, p_b, cost_b, sc_b, sb)}.items():
        eng = Engine(rt, p, cost, sc)
        for s in samples:
            eng.submit(s)
        solo[name] = eng.close()

    for name in ("alpha", "beta"):
        r, s = reps[name], solo[name]
        assert r.tenant == name
        assert r.n == s.n
        np.testing.assert_array_equal(r.preds, s.preds)
        np.testing.assert_array_equal(r.arms, s.arms)
        np.testing.assert_array_equal(r.rewards, s.rewards)
        np.testing.assert_array_equal(r.exited, s.exited)
        assert r.cost_total == s.cost_total
        assert r.offload_bytes == s.offload_bytes
        led = r.scheduler["tenant"]
        assert led["submitted"] == 5 and led["served"] == 5
        assert led["shed"] == 0 and led["pending"] == 0
    np.testing.assert_array_equal(reps["alpha"].decode["tokens"],
                                  solo["alpha"].decode["tokens"])
    assert reps["beta"].decode is None


def test_multi_tenant_quota_sheds_only_that_tenant():
    cfg_a, p_a, rt_a, cost_a = _bed(ARCHS[0])
    sc = ServingConfig(batch_size=4, workload="decode", max_new_tokens=1)
    mte = MultiTenantEngine(
        {"a": TenantSpec(rt_a, p_a, cost_a, sc),
         "b": TenantSpec(rt_a, p_a, cost_a, sc)},
        tenant_quota={"a": 2})
    sa = _prompts(cfg_a, 3, seed=23)
    for s in sa:
        mte.submit("a", [s])     # 3rd submit hits a's quota of 2
    for s in _prompts(cfg_a, 3, seed=24):
        mte.submit("b", [s])
    reps = mte.close()
    led_a = reps["a"].scheduler["tenant"]
    led_b = reps["b"].scheduler["tenant"]
    assert led_a["submitted"] == 3 and led_a["shed"] == 1
    assert led_a["served"] == 2 == reps["a"].decode["sequences"]
    assert led_b["shed"] == 0 and led_b["served"] == 3
    assert reps["a"].scheduler["shed_reasons"]["tenant_quota"] == 1


def test_multi_tenant_validation():
    cfg_a, p_a, rt_a, cost_a = _bed(ARCHS[0])
    sc = ServingConfig(batch_size=2, workload="decode", max_new_tokens=1)
    spec = TenantSpec(rt_a, p_a, cost_a, sc)
    with pytest.raises(ValueError, match="unknown tenant"):
        MultiTenantEngine({"a": spec}, tenant_quota={"ghost": 2})
    with pytest.raises(ValueError, match="scheduler"):
        MultiTenantEngine({"a": TenantSpec(
            rt_a, p_a, cost_a,
            dataclasses.replace(sc, scheduler="fifo"))})
    mte = MultiTenantEngine({"a": spec})
    with pytest.raises(KeyError):
        mte.submit("ghost", _prompts(cfg_a, 1))
    mte.close()


# ----------------------------------------------------- config validation

def test_decode_config_validation():
    ok = ServingConfig(workload="decode", max_new_tokens=4)
    assert ok.resolved_path() == "decode"
    assert ok.split_policy == "bandit"
    with pytest.raises(ValueError, match="workload"):
        ServingConfig(workload="streaming")
    with pytest.raises(ValueError, match="max_new_tokens"):
        ServingConfig(workload="decode")            # needs >= 1
    with pytest.raises(ValueError, match="split_policy"):
        ServingConfig(workload="decode", max_new_tokens=1,
                      split_policy="greedy")
    with pytest.raises(ValueError, match="max_new_tokens"):
        ServingConfig(max_new_tokens=4)             # classify forbids
    for bad in (dict(distributed=True), dict(fault_tolerant=True),
                dict(record_trace=True), dict(side_info=True),
                dict(replicas=2), dict(edge_mode="scan")):
        with pytest.raises(ValueError):
            ServingConfig(workload="decode", max_new_tokens=1, **bad)
    with pytest.raises(ValueError, match="error_feedback"):
        ServingConfig(workload="decode", max_new_tokens=1,
                      offload_error_feedback=True)  # identity codec
    clone = ServingConfig.from_json(ok.to_json())
    assert clone == ok and clone.workload == "decode"


def test_runtime_and_session_type_guards():
    cfg_a, p_a, rt_a, cost_a = _bed(ARCHS[0])
    _, p_b, rt_b, cost_b = _classify_bed()
    with pytest.raises(ValueError, match="decode"):
        serve(rt_a, p_a, iter([]), cost_a, ServingConfig(batch_size=2))
    with pytest.raises(TypeError, match="DecodeRuntime"):
        _DecodeSession(rt_b, p_b, cost_b)
    with pytest.raises(NotImplementedError, match="decoder-only"):
        DecodeRuntime(dataclasses.replace(
            get_smoke_config("seamless-m4t-large-v2"), dtype="float32"))


def test_ragged_prompts_error_is_actionable():
    cfg, params, rt, cost = _bed(ARCHS[0])
    sess = _DecodeSession(rt, params, cost, batch_size=2, max_new_tokens=1)
    bad = [{"tokens": np.arange(4)}, {"tokens": np.arange(6)}]
    with pytest.raises(ValueError, match="equal-length prompts"):
        sess.push(bad)
