"""Closed-loop decode traffic: back-to-back batches of prompts through a
decode ``Engine``; each token is stamped when the session starts the
next step (or the push returns)."""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench import generator as gen
from bench.driver import Driver, numerics, program_config, quantiles
from bench.harness import log, percentile


class DecodeDriver(Driver):
    """Closed loop: one batch of prompts at a time through a decode
    ``Engine``, the next as soon as the last returns."""

    def setup(self, runtime_hook=None):
        from repro.core import CostModel
        from repro.core.controller import SplitEEController
        from repro.serving import DecodeRuntime, Engine, ServingConfig

        t = self.traffic
        self.cfg = program_config(self.spec)
        L = self.cfg.num_layers
        self.params = self.cell.model.make_params(self.spec, self.seed)
        rt = DecodeRuntime(self.cfg)
        for name in ("prefill_fn", "edge_fn", "cloud_fn"):
            setattr(rt, name, self.probe.wrap(name, getattr(rt, name)))
        if runtime_hook is not None:
            runtime_hook(rt)
        self.runtime = rt
        B, S, T = t["batch_size"], t["prompt_len"], t["new_tokens"]
        self.scfg = ServingConfig(workload="decode", batch_size=B,
                                  max_new_tokens=T)
        total = S + T
        # the probe: prefill and full-depth steps; alpha at the quantile
        # that lets the target share of shallow exits clear it
        prompts = gen.decode_prompts(B, S, self.cfg.vocab_size, self.rng)
        logits0, caches = rt.prefill_fn(self.params, jnp.asarray(prompts),
                                        total)
        tok = jnp.argmax(logits0, -1).astype(jnp.int32)
        depths = jnp.full((B,), L - 1, jnp.int32)
        confs = []
        a = t["alpha"]
        for s in range(a["probe_steps"]):
            _, conf, _, _, pred_fin, hidden, caches = rt.edge_fn(
                self.params, caches, tok, S + s, depths, total)
            confs.append(np.asarray(conf)[:-1].ravel())
            tok = pred_fin
        alpha = float(np.quantile(np.concatenate(confs),
                                  1.0 - a["exit_rate"]))
        # the cloud resume, at the window's shapes and types
        active = np.zeros(B, bool)
        active[0] = True
        half = jnp.asarray(np.arange(B) % L, jnp.int32)
        jax.block_until_ready(rt.cloud_fn(
            self.params, caches, jnp.asarray(np.asarray(hidden)),
            S + a["probe_steps"] - 1, half, jnp.asarray(active), total))
        self.cost = CostModel(num_layers=L, offload=t["offload_price"],
                              alpha=alpha)
        # the session's host-side ops: the bandit's state and one update
        self.engine = Engine(rt, self.params, self.cost, self.scfg)
        SplitEEController(self.cost).update_batch(
            np.full(B, L - 1), [np.ones(1)] * B, [None] * B, [0] * B)
        log(f"alpha {alpha!r} ({a['exit_rate']} of {a['probe_steps']} "
            f"probe steps' shallow exits clear it)")

    def window(self, seconds: float) -> Dict[str, Any]:
        t = self.traffic
        B, S, T = t["batch_size"], t["prompt_len"], t["new_tokens"]
        V = self.cfg.vocab_size
        eng, self.engine = self.engine, None
        probe = self.probe
        steps: List[float] = []
        inputs: List[Any] = []

        def on_edge(args):
            steps.append(time.perf_counter())
            inputs.append(args[2])

        probe.hooks["edge_fn"] = on_edge
        self.pushes: List[Dict[str, Any]] = []
        probe.counting = True
        t0 = time.perf_counter()
        t_end = t0 + seconds
        with probe.span("window"):
            while time.perf_counter() < t_end:
                prompts = gen.decode_prompts(B, S, V, self.rng)
                del steps[:], inputs[:]
                start = time.perf_counter()
                with probe.span("push"):
                    eng.submit([{"tokens": p} for p in prompts])
                end = time.perf_counter()
                self.pushes.append({"prompts": prompts, "start": start,
                                    "steps": list(steps), "end": end,
                                    "tok0": inputs[0] if inputs else None})
        probe.counting = False
        probe.hooks.pop("edge_fn", None)
        self.report = eng.close()
        self.window_s = seconds
        gaps, stamps = [], []
        for p in self.pushes:
            done = p["steps"][1:] + [p["end"]]
            prev = [p["start"]] + done[:-1]
            stamps.extend(done)
            gaps.extend(d - q for d, q in zip(done, prev) if d <= t_end)
        in_window = sum(1 for s in stamps if s <= t_end)
        gaps_ms = np.asarray(gaps) * 1e3
        dec = self.report.decode
        log(f"decode: {len(self.pushes)} pushes, {in_window} steps x {B} "
            f"tokens in the window, offload share "
            f"{self.report.offload_frac:.3f}")
        log(f"token gap (ms): {quantiles(gaps_ms)}")
        ok = (dec["sequences"] == B * len(self.pushes)
              and len(self.pushes) > 0)
        return {"attempted": B * len(self.pushes),
                "failed": 0 if ok else B * len(self.pushes),
                "end_to_end": {
                    "tokens_per_s": in_window * B / seconds,
                    "token_gap_p95_ms": percentile(gaps_ms, 95)}}

    def useful_flops(self) -> float:
        """Prefill of every push begun in the window and every token
        stamped in it, each at its own depth (the counts of steps are
        taken from the pushes' stamps)."""
        model, spec = self.cell.model, self.spec
        t = self.traffic
        B, S, T = t["batch_size"], t["prompt_len"], t["new_tokens"]
        dec = self.report.decode
        depths = np.asarray(dec["realized_depths"])
        offl = np.asarray(dec["offloaded_steps"], bool)
        t_end = self.pushes[0]["start"] + self.window_s
        total = 0.0
        for k, p in enumerate(self.pushes):
            total += B * model.prefill_flops(spec, S)
            done = p["steps"][1:] + [p["end"]]
            rows = slice(k * B, (k + 1) * B)
            for s, stamp in enumerate(done):
                if stamp > t_end:
                    break
                for b in range(B):
                    total += model.token_flops(
                        spec, S + s, int(depths[rows][b, s]),
                        bool(offl[rows][b, s]))
        return total

    def check(self, control: bool = False) -> Dict[str, Any]:
        t = self.traffic
        B, T = t["batch_size"], t["new_tokens"]
        dec = self.report.decode
        L = self.cfg.num_layers
        n = len(self.pushes) * B
        k = min(n, int(t["compare"]["sequences"]))
        idx = np.sort(self.sample_rng.choice(n, size=k, replace=False))
        rows = []
        for i in idx:
            p = self.pushes[i // B]
            rows.append(compare.decode_layout(
                p["prompts"][i % B], int(np.asarray(p["tok0"])[i % B]),
                np.asarray(dec["tokens"])[i],
                np.asarray(dec["realized_depths"])[i],
                np.asarray(dec["offloaded_steps"])[i], L))
        tokens, valid, out_pos, head, served = (
            np.stack([r[j] for r in rows]) for j in range(5))
        self.params = None
        self.runtime = None
        model, spec = self.cell.model, self.spec
        params = model.make_params(spec, self.seed)
        ref = model.reference_logits(spec, params, tokens, valid,
                                     out_pos[0], head,
                                     numerics(spec, "reference"))
        out = compare.decode_numbers(ref, served)
        if control:
            ctl = model.reference_logits(spec, params, tokens, valid,
                                         out_pos[0], head,
                                         numerics(spec, "control"))
            out["control"] = compare.decode_numbers(ref, served, ctl)
        return out


DRIVER = DecodeDriver
