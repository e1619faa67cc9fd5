import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh with ShapeDtypeStruct stand-ins (no allocation).

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x22b \
        --shape train_4k [--multipod] [--out benchmarks/results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Per combo it records: memory_analysis (proves HBM fit), cost_analysis
(FLOPs/bytes for the roofline), and the collective schedule parsed from
the compiled HLO (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute operand bytes), into a JSON the roofline benchmark
reads.

NOTE: the first lines of this file set XLA_FLAGS before ANY other
import — jax locks the device count at first init. Do not move them.
They do so only when the file runs as a script, so importing it (the
unit tests do) leaves the importer's device count alone.
(`from __future__` is consequently omitted — it must be line 1, which the
XLA_FLAGS contract forbids.)
"""
import argparse
import functools
import json
import re
import time
from typing import Any, Dict

import jax

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import axis_map, make_production_mesh
from repro.launch.shardings import (batch_shardings, cache_shardings,
                                    param_shardings, sharding_tree)
from repro.launch.train import make_train_step
from repro.models.api import build_model
from repro.optim import adamw_init
from repro.optim.adamw import AdamWConfig
from repro.sharding.rules import mesh_rules, param_specs
from jax.sharding import NamedSharding, PartitionSpec as P

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "f64": 8, "s64": 8, "pred": 1, "s8": 1, "u8": 1, "f8": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes of collective ops in (optimized) HLO text.

    Returns {op: {"count": int, "bytes": int}} plus "total_bytes"."""
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    # lines look like:  %ag = bf16[8,1024]{...} all-gather(%x), ...
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(
            c + r"(?:-start|-done)?" for c in _COLLECTIVES) + r")\(", stripped)
        if not m:
            continue
        op = next(c for c in _COLLECTIVES if m.group(1).startswith(c))
        if m.group(1).endswith("-done"):
            continue  # counted at -start
        # output shape(s) between '=' and the op name (handles tuple
        # outputs like "(f32[4,4], f32[4,4]) all-to-all(...)")
        rhs = stripped.split("=", 1)[1]
        rhs_shapes = shape_re.findall(rhs[:rhs.index(m.group(1))])
        nbytes = 0
        for dt, dims in rhs_shapes:
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


_DEF_RE = re.compile(r"^\s*(%[\w.-]+|[\w.-]+) = ([a-z0-9]+)\[([\d,]*)\]")
_DOT_RE = re.compile(
    r"=\s+[a-z0-9]+\[([\d,]*)\][^=]*?\bdot\((%[\w.-]+)(?:,| )\s*(%[\w.-]+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def parse_dot_flops(hlo_text: str) -> float:
    """Sum 2*prod(out)*K over every dot in the (partitioned) HLO.

    XLA's ``compiled.cost_analysis()`` on the CPU backend under-counts
    batched dot_generals after SPMD partitioning (batch dims dropped from
    the flop product — verified against single-device compiles, which
    match analytic counts exactly). This parser is the source of truth for
    the roofline compute term; while/scan bodies still appear once, so the
    depth-fit extrapolation applies on top.
    """
    shapes = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name = m.group(1).lstrip("%")
            dims = [int(d) for d in m.group(3).split(",") if d]
            shapes[name] = dims
    total = 0.0
    for line in hlo_text.splitlines():
        m = _DOT_RE.search(line)
        if not m:
            continue
        out_dims = [int(d) for d in m.group(1).split(",") if d]
        lhs = m.group(2).lstrip("%")
        cm = _CONTRACT_RE.search(line)
        k = 1.0
        if cm and lhs in shapes:
            lshape = shapes[lhs]
            for d in cm.group(1).split(","):
                if d:
                    idx = int(d)
                    if idx < len(lshape):
                        k *= lshape[idx]
        elif lhs in shapes:
            k = shapes[lhs][-1] if shapes[lhs] else 1.0
        out = 1.0
        for d in out_dims:
            out *= d
        total += 2.0 * out * k
    return total


def _with_depth(cfg, num_layers: int):
    """Reduced-depth variant of the same config (for the linear flop fit —
    XLA cost_analysis counts a while/scan body once, so totals are
    extrapolated from two depths; encoder depth scales along)."""
    import dataclasses
    enc = cfg.encoder
    if enc is not None:
        enc = dataclasses.replace(enc, num_layers=num_layers)
    return dataclasses.replace(cfg, num_layers=num_layers, encoder=enc)


def build_step(arch: str, shape_name: str, mesh, multi_pod: bool, *,
               remat: bool = True, cfg=None, decode_tp_only: bool = True):
    """Returns (lower_fn, abstract_args, in_shardings) for the combo.

    ``decode_tp_only`` (§Perf it.1): decode steps use tensor-parallel-only
    weight sharding — FSDP gathers of the full parameter set per decoded
    token are the baseline's dominant collective cost. Expert stacks
    (moe/*) keep the data-axis shard to fit HBM (contraction-dim sharded:
    psum of the small (E, C, F) output instead of a weight gather)."""
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg, backend="ref")
    amap = {"model": "model", "fsdp": "data"}
    abstract = model.abstract_params()
    fsdp_paths = None
    if shape.kind == "decode" and decode_tp_only:
        fsdp_paths = r"moe/"
    p_sh = param_shardings(mesh, abstract, axis_map=amap,
                           fsdp_paths=fsdp_paths)
    specs = model.input_specs(shape)

    if shape.kind == "train":
        opt_abstract = jax.eval_shape(adamw_init, abstract)
        opt_sh = sharding_tree(mesh, param_specs(opt_abstract, amap),
                               opt_abstract)
        step = make_train_step(model, AdamWConfig(), remat=remat)
        b_sh = batch_shardings(mesh, specs["batch"], multi_pod)
        args = (abstract, opt_abstract, specs["batch"])
        in_sh = (p_sh, opt_sh, b_sh)
        fn = step
    elif shape.kind == "prefill":
        def fn(params, batch):
            return model.prefill(params, batch,
                                 cache_seq_len=shape.seq_len)
        b_sh = batch_shardings(mesh, specs["batch"], multi_pod)
        args = (abstract, specs["batch"])
        in_sh = (p_sh, b_sh)
    else:  # decode
        split_layer = cfg.num_layers // 2

        def fn(params, caches, token, cur_index, extras=None):
            return model.decode_step(
                params, caches, token, cur_index, extras=extras,
                split_layer=split_layer, window_seq_len=shape.seq_len)

        c_sh = cache_shardings(mesh, specs["caches"], multi_pod)
        t_sh = batch_shardings(mesh, specs["token"], multi_pod)
        i_sh = NamedSharding(mesh, P())
        args = [specs["caches"], specs["token"], specs["cur_index"]]
        in_sh = [c_sh, t_sh, i_sh]
        if "extras" in specs:
            args.append(specs["extras"])
            in_sh.append(batch_shardings(mesh, specs["extras"], multi_pod))
            fn = functools.partial(fn)
        args = (abstract, *args)
        in_sh = (p_sh, *in_sh)
    return fn, args, in_sh, cfg, shape


def _compile_combo(arch, shape_name, mesh, multi_pod, remat, cfg=None):
    fn, args, in_sh, cfg, shape = build_step(arch, shape_name, mesh,
                                             multi_pod, remat=remat, cfg=cfg)
    # decode: donate the caches so the ring-slot write aliases in place —
    # without donation XLA double-buffers the full KV cache (§Perf it.1)
    donate = (1,) if INPUT_SHAPES[shape_name].kind == "decode" else ()
    with mesh_rules(mesh, axis_map(multi_pod)):
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled, cfg, shape


def _terms(compiled):
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    # dot flops parsed from HLO (cost_analysis under-counts batched dots
    # post-SPMD on the CPU backend; see parse_dot_flops)
    return (parse_dot_flops(hlo),
            float(cost.get("bytes accessed", 0.0)), coll)


def depth_fit(arch: str, shape_name: str, mesh, multi_pod: bool,
              remat: bool, full_layers: int, k: int):
    """Extrapolate per-device flops/bytes/collective-bytes to full depth
    from two reduced-depth compiles (L=k and L=2k; k respects the hybrid
    shared-attention period).

    The reduced compiles run with the layer scans fully UNROLLED: XLA's
    cost_analysis counts a while body once regardless of trip count, so
    rolled fit points would both measure "one body" and the slope would
    collapse (observed: f2/f1 ~ 1.0). Unrolled, f2 - f1 is exactly one
    layer's per-device cost."""
    from repro.models import transformer as _tr
    base = get_config(arch)
    l1, l2 = k, 2 * k
    prev_unroll = _tr.LAYER_SCAN_UNROLL
    _tr.LAYER_SCAN_UNROLL = max(l2, 2)
    try:
        c1, _, _ = _compile_combo(arch, shape_name, mesh, multi_pod, remat,
                                  cfg=_with_depth(base, l1))
        c2, _, _ = _compile_combo(arch, shape_name, mesh, multi_pod, remat,
                                  cfg=_with_depth(base, l2))
    finally:
        _tr.LAYER_SCAN_UNROLL = prev_unroll
    f1, b1, co1 = _terms(c1)
    f2, b2, co2 = _terms(c2)

    def extrap(v1, v2):
        slope = (v2 - v1) / (l2 - l1)
        return v1 + slope * (full_layers - l1)

    coll = {}
    for key in co1:
        if key == "total_bytes":
            continue
        coll[key] = {
            "count": int(round(extrap(co1[key]["count"], co2[key]["count"]))),
            "bytes": int(max(0, round(extrap(co1[key]["bytes"],
                                             co2[key]["bytes"])))),
        }
    coll["total_bytes"] = sum(v["bytes"] for v in coll.values()
                              if isinstance(v, dict))
    return {
        "flops": float(max(0.0, extrap(f1, f2))),
        "bytes_accessed": float(max(0.0, extrap(b1, b2))),
        "collectives": coll,
        "fit_points": {"l1": l1, "l2": l2, "flops": [f1, f2],
                       "bytes": [b1, b2],
                       "coll_bytes": [co1["total_bytes"],
                                      co2["total_bytes"]]},
    }


def run_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
              remat: bool = True, out_dir: str | None = None,
              tag: str = "", quiet: bool = False,
              with_fit: bool = True, dp: int = 16,
              tp: int = 16) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod, dp=dp, tp=tp)
    t0 = time.time()
    compiled, cfg, shape = _compile_combo(arch, shape_name, mesh, multi_pod,
                                          remat)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    n_dev = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "num_devices": int(n_dev),
        "tag": tag,
        "compile_s": round(time.time() - t0, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if with_fit:
        k = cfg.hybrid_attn_every or 1
        fit = depth_fit(arch, shape_name, mesh, multi_pod, remat,
                        cfg.num_layers, k)
        result["extrapolated"] = fit
    if not quiet:
        print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}"
              f"{' #' + tag if tag else ''}: compiled in "
              f"{result['compile_s']}s  flops={result['flops']:.3e}  "
              f"bytes={result['bytes_accessed']:.3e}  "
              f"coll={coll['total_bytes']:.3e}B")
        print(f"  memory/device: args={result['memory']['argument_bytes']/1e9:.2f}GB "
              f"temp={result['memory']['temp_bytes']/1e9:.2f}GB "
              f"out={result['memory']['output_bytes']/1e9:.2f}GB")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = f"{arch}_{shape_name}_{result['mesh']}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def refit(out_dir: str, *, only_arch: str = "", remat: bool = True):
    """Recompute only the depth-fit extrapolations for existing single-pod
    JSONs (no full recompiles)."""
    import glob as _glob
    mesh = make_production_mesh(multi_pod=False)
    for path in sorted(_glob.glob(os.path.join(out_dir,
                                               "*single_pod*.json"))):
        with open(path) as f:
            r = json.load(f)
        if only_arch and r["arch"] != only_arch:
            continue
        cfg = get_config(r["arch"])
        k = cfg.hybrid_attn_every or 1
        t0 = time.time()
        fit = depth_fit(r["arch"], r["shape"], mesh, False, remat,
                        cfg.num_layers, k)
        r["extrapolated"] = fit
        with open(path, "w") as f:
            json.dump(r, f, indent=1)
        print(f"[refit] {r['arch']} x {r['shape']}: "
              f"flops={fit['flops']:.3e} bytes={fit['bytes_accessed']:.3e} "
              f"coll={fit['collectives']['total_bytes']:.3e} "
              f"({time.time()-t0:.0f}s)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all 10 archs x 4 shapes on the selected mesh")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-fit", action="store_true",
                    help="skip the depth-fit compiles (multi-pod pass: "
                         "prove-it-lowers only, roofline is single-pod)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--dp", type=int, default=16)
    ap.add_argument("--tp", type=int, default=16)
    ap.add_argument("--refit", action="store_true",
                    help="recompute depth-fit extrapolations for existing "
                         "single-pod JSONs only")
    args = ap.parse_args()

    if args.refit:
        refit(args.out, only_arch=args.arch or "",
              remat=not args.no_remat)
        return

    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    failures = []
    for a, s in combos:
        try:
            run_combo(a, s, multi_pod=args.multipod, out_dir=args.out,
                      remat=not args.no_remat, tag=args.tag,
                      with_fit=not args.no_fit, dp=args.dp, tp=args.tp)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append((a, s, repr(e)[:300]))
            print(f"[dryrun] FAILED {a} x {s}: {repr(e)[:300]}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run combos failed: "
                         + "; ".join(f"{a}x{s}" for a, s, _ in failures))
    print(f"[dryrun] all {len(combos)} combos compiled OK "
          f"({'multi' if args.multipod else 'single'}-pod)")


if __name__ == "__main__":
    main()
