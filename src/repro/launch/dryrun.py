import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, dump memory/cost/collective analysis for §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json. Skipped
pairs (encoder-only decode) are recorded with status="skipped".
"""
import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as ST
from repro import sharding as SH
from repro.utils.pytree import tree_size

_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
          "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def peak_bytes(mem) -> int:
    """peak_memory_in_bytes where jaxlib provides it; else the
    argument+output+temp sum as a live-bytes proxy (jaxlib <= 0.4.x)."""
    peak = getattr(mem, "peak_memory_in_bytes", None)
    if peak is not None:
        return int(peak)
    return int(
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )


def collective_bytes(hlo_text: str):
    """Per-collective-op byte totals from the (per-device, post-SPMD)
    optimized HLO. For every collective instruction we take the LARGEST
    shape on the line (for all-gather that's the gathered result; for
    reduce-scatter the un-scattered operand; for all-reduce/all-to-all the
    tensor itself) as the bytes-on-the-wire proxy. '-done' ops are skipped
    ('-start' carries the shapes)."""
    out = {}
    for line in hlo_text.splitlines():
        if "all-" not in line and "reduce-scatter" not in line \
                and "collective-permute" not in line:
            continue
        if "=" not in line:
            continue
        lhs, rhs = line.split("=", 1)
        rhs = rhs.strip()
        kind = None
        for k in _KINDS:
            i = rhs.find(k + "(")
            j = rhs.find(k + "-start(")
            if i == -1 and j == -1:
                continue
            pos = i if i != -1 else j
            kind, oppos = k, pos
            break
        if kind is None:
            continue
        best = 0
        for dt, dims in _SHAPE_RE.findall(rhs[:oppos]):
            if dt not in _DTYPE_BYTES:
                continue
            n = _DTYPE_BYTES[dt]
            for d in dims.split(","):
                if d:
                    n *= int(d)
            best = max(best, n)
        out[kind] = out.get(kind, 0) + best
    return out


def model_flops(cfg, shape) -> float:
    """6 * N_active * tokens (train) / 2 * N_active * tokens (fwd-only)."""
    params, _ = ST.train_state_structs(cfg)
    n_total = tree_size(params)
    if cfg.num_experts:
        # active params: replace full expert stack by top-k experts
        import jax as _j
        expert = sum(
            int(np.prod(l.shape))
            for p, l in _j.tree_util.tree_flatten_with_path(params)[0]
            if any(str(getattr(q, "key", "")) in ("w_gate", "w_up", "w_down")
                   and l.ndim == 4 for q in p)
        )
        n_active = n_total - expert + expert * cfg.experts_per_token // cfg.num_experts
    else:
        n_active = n_total
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n_active * tokens


def run_pair(arch: str, shape_name: str, mesh_kind: str, save_hlo: bool = False,
             out_dir: str = "experiments/dryrun", overrides=None,
             suffix: str = ""):
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "program": {"train": "train_step", "prefill": "prefill_step",
                    "decode": "serve_step"}[shape.kind],
    }
    if overrides:
        rec["overrides"] = list(overrides)
    if not cfg.supports_shape(shape_name):
        rec["status"] = "skipped"
        rec["reason"] = "encoder-only architecture has no autoregressive decode"
        return rec
    cfg = _apply_overrides(cfg.decode_variant(shape_name), overrides)
    if cfg.window_size and shape_name == "long_500k" and cfg.family in ("dense", "moe", "vlm"):
        rec["variant"] = f"sliding_window_{cfg.window_size}"

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with mesh:
        params = ST.param_structs(cfg)
        pspecs = SH.param_specs(cfg, params, mesh)
        psh = SH.to_shardings(mesh, pspecs)

        if shape.kind == "train":
            params_s, opt_s = ST.train_state_structs(cfg)
            # opt state: AdamState(step, mu, nu) — mu/nu sharded like params
            from repro.optim.adam import AdamState
            from jax.sharding import NamedSharding, PartitionSpec as P
            osh = AdamState(
                step=NamedSharding(mesh, P()),
                mu=SH.to_shardings(mesh, pspecs),
                nu=SH.to_shardings(mesh, pspecs),
            )
            bspecs = SH.batch_specs(cfg, shape, mesh)
            bsh = SH.to_shardings(mesh, bspecs)
            step, _ = ST.make_train_step(cfg)
            batch = ST.input_specs(cfg, shape)
            fn = jax.jit(
                step,
                in_shardings=(psh, osh, psh, psh, bsh),
                out_shardings=(psh, osh, NamedSharding(mesh, P())),
                # H2-it6: donate params + opt state — without aliasing the
                # in/out train state is double-counted resident (peak was
                # pinned at args+outputs = 68 GiB on llama4).
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(params_s, opt_s, params_s, params_s, batch)
        elif shape.kind == "prefill":
            from jax.sharding import NamedSharding, PartitionSpec as P
            bspecs = SH.batch_specs(cfg, shape, mesh)
            bsh = SH.to_shardings(mesh, bspecs)
            step = ST.make_prefill_step(cfg)
            batch = ST.input_specs(cfg, shape)
            # H1-it2: without out_shardings XLA leaves the returned KV
            # states batch-sharded only (15 GiB/dev outputs at 32k); shard
            # the cache seq dim over 'model' like the decode states.
            out_struct = jax.eval_shape(step, params, batch)
            baxis = "data" if shape.global_batch >= mesh.shape["data"] else None
            st_specs = SH.decode_state_specs(cfg, out_struct[1], shape, mesh)
            osh = (NamedSharding(mesh, P(baxis, None)),
                   SH.to_shardings(mesh, st_specs))
            fn = jax.jit(step, in_shardings=(psh, bsh), out_shardings=osh)
            lowered = fn.lower(params, batch)
        else:  # decode
            states, tokens, pos = ST.decode_input_specs(cfg, shape)
            sspecs = SH.decode_state_specs(cfg, states, shape, mesh)
            ssh = SH.to_shardings(mesh, sspecs)
            from jax.sharding import NamedSharding, PartitionSpec as P
            baxis = "data" if shape.global_batch >= mesh.shape["data"] else None
            tsh = NamedSharding(mesh, P(baxis))
            step = ST.make_serve_step(cfg)
            fn = jax.jit(step, in_shardings=(psh, ssh, tsh, tsh))
            lowered = fn.lower(params, states, tokens, pos)

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": peak_bytes(mem),
        }
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        rec["cost"] = {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        }
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo)
        rec["model_flops"] = model_flops(cfg, shape)
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            hpath = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.hlo")
            with open(hpath, "w") as f:
                f.write(hlo)
            rec["hlo_path"] = hpath
    rec["status"] = "ok"
    return rec


def _depth_variant(cfg, units: int):
    """Structure-preserving shallow variant for costing. A 'unit' is one
    pattern period (hybrid) or one layer (everything else). xlstm costing
    approximates sLSTM layers as mLSTM (slstm_at=()) — the per-layer matmul
    budget is comparable and sLSTM's time-scan can't be unrolled."""
    import dataclasses
    if cfg.family == "hybrid":
        period = len(cfg.block_pattern)
        return dataclasses.replace(cfg, num_layers=units * period), units * period
    if cfg.family == "ssm":
        return dataclasses.replace(cfg, num_layers=units, slstm_at=()), units
    return dataclasses.replace(cfg, num_layers=units), units


def _lower_compile(cfg, shape, mesh):
    """Shared lower+compile for one program; returns compiled."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    params = ST.param_structs(cfg)
    pspecs = SH.param_specs(cfg, params, mesh)
    psh = SH.to_shardings(mesh, pspecs)
    if shape.kind == "train":
        params_s, opt_s = ST.train_state_structs(cfg)
        from repro.optim.adam import AdamState
        osh = AdamState(step=NamedSharding(mesh, P()),
                        mu=SH.to_shardings(mesh, pspecs),
                        nu=SH.to_shardings(mesh, pspecs))
        bsh = SH.to_shardings(mesh, SH.batch_specs(cfg, shape, mesh))
        step, _ = ST.make_train_step(cfg)
        batch = ST.input_specs(cfg, shape)
        fn = jax.jit(step, in_shardings=(psh, osh, psh, psh, bsh),
                     out_shardings=(psh, osh, NamedSharding(mesh, P())))
        return fn.lower(params_s, opt_s, params_s, params_s, batch).compile()
    if shape.kind == "prefill":
        bsh = SH.to_shardings(mesh, SH.batch_specs(cfg, shape, mesh))
        step = ST.make_prefill_step(cfg)
        batch = ST.input_specs(cfg, shape)
        return jax.jit(step, in_shardings=(psh, bsh)).lower(params, batch).compile()
    states, tokens, pos = ST.decode_input_specs(cfg, shape)
    ssh = SH.to_shardings(mesh, SH.decode_state_specs(cfg, states, shape, mesh))
    from jax.sharding import NamedSharding as NS, PartitionSpec as P2
    baxis = "data" if shape.global_batch >= mesh.shape["data"] else None
    tsh = NS(mesh, P2(baxis))
    step = ST.make_serve_step(cfg)
    fn = jax.jit(step, in_shardings=(psh, ssh, tsh, tsh))
    return fn.lower(params, states, tokens, pos).compile()


def _extract(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops") or 0.0),
        "bytes_accessed": float(cost.get("bytes accessed") or 0.0),
        "collectives": coll,
    }


def _apply_overrides(cfg, overrides):
    """--set key=value config overrides (str/int/float/bool inferred)."""
    import dataclasses
    if not overrides:
        return cfg
    repl = {}
    for kv in overrides:
        k, v = kv.split("=", 1)
        field = {f.name: f for f in dataclasses.fields(cfg)}[k]
        if field.type in ("int", int):
            v = int(v)
        elif field.type in ("float", float):
            v = float(v)
        elif field.type in ("bool", bool):
            v = v.lower() in ("1", "true")
        repl[k] = v
    return dataclasses.replace(cfg, **repl)


def run_costing(arch: str, shape_name: str, mesh_kind: str,
                out_dir: str = "experiments/dryrun", overrides=None,
                suffix: str = ""):
    """Corrected per-device cost via diff-of-two-depths with fully unrolled
    scans (XLA cost_analysis counts a while body ONCE — see EXPERIMENTS.md
    §Methodology). total(L) = c1 + (c2 - c1) * (L - L1) / (L2 - L1)."""
    from repro.models import flags as MFLAGS
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    if not cfg.supports_shape(shape_name):
        return None
    cfg = _apply_overrides(cfg.decode_variant(shape_name), overrides)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    # depths 2 and 3 (depth 1 shows XLA compile anomalies for encoders)
    cfg1, L1 = _depth_variant(cfg, 2)
    cfg2, L2 = _depth_variant(cfg, 3)
    MFLAGS.UNROLL_SCANS = True
    try:
        with mesh:
            c1 = _extract(_lower_compile(cfg1, shape, mesh))
            c2 = _extract(_lower_compile(cfg2, shape, mesh))
    finally:
        MFLAGS.UNROLL_SCANS = False
    Lf = cfg.num_layers
    scale = (Lf - L1) / (L2 - L1)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "method": "diff_of_depths_unrolled", "L1": L1, "L2": L2,
           "flops": c1["flops"] + (c2["flops"] - c1["flops"]) * scale,
           "bytes_accessed": c1["bytes_accessed"]
           + (c2["bytes_accessed"] - c1["bytes_accessed"]) * scale,
           "collectives": {}}
    kinds = set(c1["collectives"]) | set(c2["collectives"])
    for k in kinds:
        a, b = c1["collectives"].get(k, 0), c2["collectives"].get(k, 0)
        rec["collectives"][k] = a + (b - a) * scale
    rec["model_flops"] = model_flops(cfg, shape)
    if overrides:
        rec["overrides"] = list(overrides)
    path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.cost.json"
    )
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--costing", action="store_true",
                    help="corrected per-device costs (diff-of-depths, unrolled)")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    help="config override key=value (e.g. attn_impl=online)")
    ap.add_argument("--suffix", default="", help="output-file suffix for variants")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape_name in shapes:
            tag = f"{arch}__{shape_name}__{args.mesh}"
            if args.costing:
                t0 = time.time()
                try:
                    rec = run_costing(arch, shape_name, args.mesh, args.out,
                                      overrides=args.overrides,
                                      suffix=args.suffix)
                    status = "skipped" if rec is None else "ok"
                    extra = (f" flops/dev={rec['flops']:.3e}"
                             if rec else "")
                except Exception as e:  # noqa: BLE001
                    status, extra = "error", f" {type(e).__name__}: {e}"
                print(f"[{status:7s}] cost {tag}{extra} ({time.time()-t0:.0f}s)",
                      flush=True)
                continue
            path = os.path.join(args.out, tag + args.suffix + ".json")
            t0 = time.time()
            try:
                rec = run_pair(arch, shape_name, args.mesh, args.save_hlo,
                               args.out, overrides=args.overrides,
                               suffix=args.suffix)
            except Exception as e:  # noqa: BLE001 — record the failure
                rec = {
                    "arch": arch, "shape": shape_name, "mesh": args.mesh,
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
            rec["wall_s"] = round(time.time() - t0, 2)
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
            status = rec["status"]
            extra = ""
            if status == "ok":
                mb = (rec["memory"]["peak_bytes"] or 0) / 2**20
                extra = (f" flops/dev={rec['cost']['flops']:.3e}"
                         f" peak={mb:.0f}MiB"
                         f" compile={rec['compile_s']}s")
            print(f"[{status:7s}] {tag}{extra}", flush=True)


if __name__ == "__main__":
    main()
