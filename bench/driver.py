"""The program-facing side of a run, shared by the drivers of every
traffic kind: build the served model from a cell's files, calibrate
alpha, warm exactly the cell's shapes, and collect what the comparison
and the per-layer readers need.

Each traffic ``kind`` has a driver of its own in
``bench/kinds/<kind>.py`` (a module-level ``DRIVER`` class), which drives
the window through the served entry point.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, ref_ops
from bench import generator as gen
from bench.harness import log, percentile
from bench.peaks import peaks


def numerics(spec: Dict[str, Any], which: str) -> ref_ops.Numerics:
    """The reference's (``which="reference"``) or the control's
    (``"control"``) rounding, as the configuration's file states it under
    ``<which>_numerics``."""
    return ref_ops.Numerics(**spec[f"{which}_numerics"])


def program_config(spec: Dict[str, Any]):
    """The program's ModelConfig for a configuration file's numbers."""
    from repro.configs import get_config
    base = get_config(spec["program_arch"])
    heads = spec["num_attention_heads"]
    return dataclasses.replace(
        base, num_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"], num_heads=heads,
        num_kv_heads=spec.get("num_key_value_heads", heads),
        head_dim=spec.get("head_dim", 0), d_ff=spec["intermediate_size"],
        vocab_size=spec["vocab_size"], num_classes=spec.get("num_labels", 0),
        rope_theta=spec.get("rope_theta", base.rope_theta),
        dtype=spec["torch_dtype"])


def quantiles(values) -> str:
    if not len(values):
        return "none"
    return (f"p50 {percentile(values, 50):.3f} p95 "
            f"{percentile(values, 95):.3f} max {np.max(values):.3f}")


class Driver:
    def __init__(self, cell, *, seed: int, seconds: float, devices, probe):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.devices = devices
        self.probe = probe
        self.spec = cell.spec
        self.traffic = cell.traffic
        self.rng = np.random.default_rng([seed, 1])
        self.sample_rng = np.random.default_rng([seed, 2])
        # a configuration may fix its weights (``weights_seed``): then
        # ``--seed`` draws the inputs, their order and the sample compared
        self.weights_seed = int(cell.spec.get("weights_seed", seed))

    def useful_flops(self) -> float:
        raise NotImplementedError

    def mfu(self) -> float:
        """Useful operations of the window's served work over the window
        times the chips' bf16 peak (the float32 classifier multiplies in
        one bf16 pass at XLA's default precision, so bf16 is its peak)."""
        peak = peaks(self.devices[0].device_kind)["bf16_flops"]
        return self.useful_flops() / (self.window_s * len(self.devices)
                                      * peak)


# --------------------------------------------------------------- classify

class ClassifyDriver(Driver):
    """Shared set-up and comparison of the classify drivers."""

    def setup(self, runtime_hook=None):
        from repro.core import CostModel
        from repro.serving import EdgeCloudRuntime, Engine, ServingConfig
        from repro.serving.batched import _bucket_cap

        t = self.traffic
        self.cfg = program_config(self.spec)
        L = self.cfg.num_layers
        params = self.cell.model.make_params(self.spec, self.weights_seed)
        rt = EdgeCloudRuntime(self.cfg)
        for name in ("edge_fn", "cloud_fn", "edge_fn_s", "edge_scan_fn"):
            setattr(rt, name, self.probe.wrap(name, getattr(rt, name)))
        if runtime_hook is not None:
            runtime_hook(rt)
        self.runtime = rt
        serving = dict(t["serving"])
        self.scfg = ServingConfig(**serving, record_trace=True)
        B = self.scfg.batch_size
        R = self.scfg.replicas
        # place the weights as the serving session does, once, so the
        # window's session finds them in place
        plain = dataclasses.replace(self.scfg, scheduler="none",
                                    batch_deadline_ms=0.0, max_queue=0)
        sess = Engine(rt, params, CostModel(num_layers=L), plain)._sess
        self.params = sess.params
        put = getattr(sess, "put", jnp.asarray)
        self.make_inputs()

        # alpha at the median of the exit confidences of one probe batch
        # over every exit, so about half the samples exit at the edge; the
        # batch is drawn with the weights, so alpha is the weights' own
        tokens = gen.classify_tokens(
            t["domain"], B, t["seq_len"],
            np.random.default_rng([self.weights_seed, 3]))
        confs = [np.asarray(rt.edge_fn(self.params, {"tokens": put(tokens)},
                                       jnp.int32(d))[0]) for d in range(L)]
        alpha = float(np.median(np.concatenate(confs)))
        self.cost = CostModel(num_layers=L, offload=t["offload_price"],
                              alpha=alpha)
        # every bucket size the window can launch, edge and cloud
        caps = sorted({_bucket_cap(k, R) for k in range(1, B + 1)})
        for cap in caps:
            _, _, hidden = rt.edge_fn(self.params,
                                      {"tokens": put(tokens[:cap])},
                                      jnp.int32(0))
            jax.block_until_ready(rt.cloud_fn(
                self.params, put(np.asarray(hidden)), jnp.int32(0)))
        # and one batch through the session, for the host-side ops
        sess.push([{"tokens": row} for row in tokens])
        sess.drain()
        log(f"alpha {alpha!r}; warmed buckets {caps}")

    def make_inputs(self):
        raise NotImplementedError

    def served_tokens(self, idx) -> np.ndarray:
        raise NotImplementedError

    def check(self, control: bool = False) -> Dict[str, Any]:
        """Reference over a sample of the answers served in the window."""
        rep = self.report
        n = rep.n
        k = min(n, int(self.traffic["compare"]["samples"]))
        idx = np.sort(self.sample_rng.choice(n, size=k, replace=False))
        tokens = self.served_tokens(idx)
        arms = np.asarray(rep.arms)[idx]
        exited = np.asarray(rep.exited, bool)[idx]
        preds = np.asarray(rep.preds)[idx]
        conf_edge = np.asarray([float(rep.trace["conf_path"][i][-1])
                                for i in idx])
        conf_cloud = np.asarray([np.nan if rep.trace["conf_L"][i] is None
                                 else float(rep.trace["conf_L"][i])
                                 for i in idx])
        self.release()
        model, spec = self.cell.model, self.spec
        params = model.make_params(spec, self.weights_seed)
        ref_e, ref_f = model.reference_logits(spec, params, tokens,
                                              numerics(spec, "reference"))
        out = compare.classify_numbers(ref_e, ref_f, arms, exited,
                                       conf_edge, conf_cloud, preds)
        if control:
            ctl_e, ctl_f = model.reference_logits(spec, params, tokens,
                                                  numerics(spec, "control"))
            out["control"] = compare.classify_control(ref_e, ref_f, ctl_e,
                                                      ctl_f, arms, exited)
        return out

    def release(self):
        """Drop the program's state before the reference runs."""
        self.params = None
        self.runtime = None

    def served_flops(self, n: int) -> float:
        model, spec = self.cell.model, self.spec
        S = self.traffic["seq_len"]
        arms = np.asarray(self.report.arms)[:n]
        offl = ~np.asarray(self.report.exited, bool)[:n]
        layer = model.layer_flops(spec, S)
        head = model.head_flops(spec)
        L = self.cfg.num_layers
        layers = np.where(offl, L, arms + 1).sum()
        return float(layers * layer + (n + offl.sum()) * head)
