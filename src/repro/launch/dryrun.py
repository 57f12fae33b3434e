import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the lines above MUST run before any other import — jax locks the
# platform and device count at first initialization. The dry-run is a
# CPU-only tool: 512 host devices stand in for the production mesh, and
# neither it nor its --all children ever claim an accelerator.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces (and caches to results/dryrun/*.json):
  - memory_analysis (bytes per device: args/outputs/temps) — proves it fits
  - cost_analysis  (per-device HLO FLOPs / bytes accessed)
  - the collective schedule: per-op counts + per-device bytes, parsed from
    the SPMD-partitioned HLO (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute)
  - derived roofline terms (v5e constants; see benchmarks/roofline.py)

Usage:
  python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--force]
  python -m repro.launch.dryrun --sync-step --arch gemma3-4b   # FedLuck Eq.6

The `--all` driver runs each cell in a fresh subprocess (compiles leak
memory on a 1-core host) and tolerates per-cell failures.
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# ------------------------------------------------------- HLO collective parse
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s32|s64|u32|u8|s8|pred|s16|u16)"
                       r"\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u8": 1, "s8": 1, "pred": 1, "s16": 2, "u16": 2}
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _bytes_of_types(s: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt[:4], _DTYPE_BYTES.get(dt[:3], 4))
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device RESULT bytes of every collective op in the optimized
    (post-SPMD) module, keyed by op kind. The result type annotation sits
    between '=' and the opcode: `%x = f32[16,128]{1,0} all-reduce(...)`.

    NOTE: ops inside while-loop (scan) bodies appear ONCE here; run_cell
    extrapolates true totals from unrolled L1/L2 auxiliary lowerings.
    """
    out = {k: {"count": 0, "bytes": 0} for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1]
        for kind in _COLL_KINDS:
            m = re.search(rf"\b{kind}(-start)?\(", rhs)
            if m:
                b = _bytes_of_types(rhs[:m.start()])
                out[kind]["count"] += 1
                out[kind]["bytes"] += b
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _cost_dict(ca) -> dict:
    """compiled.cost_analysis() returns a dict on current jax but a
    per-computation list on 0.4.x — normalize to the dict."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


# ------------------------------------------------------------- cell execution
def run_cell(arch: str, shape: str, mesh_kind: str, *, verbose: bool = True,
             step_override: str | None = None, zero3: bool = False,
             moe_local: bool = False, seq_parallel: bool = True,
             layout: str = "tp", microbatches: int = 1,
             kv_int8: bool = False, tag: str = "") -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.dist import sharding as shl
    from repro.dist.steps import (make_decode_step, make_prefill_step,
                                  make_train_step)
    from repro.launch.mesh import batch_axes_for, make_production_mesh
    from repro.models.transformer import LM
    from repro.optim import momentum_sgd

    import dataclasses as _dc

    t0 = time.perf_counter()
    cfg = get_config(arch)
    from repro.configs.base import SHAPES
    sinfo = SHAPES[shape]
    if shape in cfg.skip_shapes or (
            sinfo["kind"] == "decode" and cfg.family == "audio"):
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": "see DESIGN.md §5"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    # layout "tp": batch over (pod, data), TP+SP over model (default).
    # layout "dp": batch covers the WHOLE mesh; params FSDP over all axes,
    # streamed per-layer ZeRO-3 gather inside the scan (train only).
    if layout == "dp":
        baxes = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)
        fsdp_axis, model_axis = baxes, None
    else:
        baxes = batch_axes_for(mesh)
        fsdp_axis, model_axis = "data", "model"
    kind = step_override or sinfo["kind"]
    B, S = sinfo["batch"], sinfo["seq"]
    ns = lambda tree: shl.named(tree, mesh)

    # pin activation batch sharding only when the batch divides the shards
    n_bshards = 1
    for a in baxes:
        n_bshards *= mesh.shape[a]
    act_axes = baxes if B % n_bshards == 0 else None
    # Megatron sequence parallelism on the residual stream for full-sequence
    # steps: cuts per-device activation temps ~7x (30.7 -> 4.6 GiB on
    # stablelm train_4k) so every cell fits v5e HBM.
    seq_axis = "model" if (seq_parallel and layout == "tp"
                           and kind in ("train", "prefill")) else None

    def lower_one(cfg_l, *, use_scan: bool):
        lm = LM(cfg_l, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                remat=True, use_scan=use_scan, batch_axes=act_axes,
                act_seq_axis=seq_axis,
                kv_dtype=("int8" if kv_int8 else "compute"),
                zero3_layer=(layout == "dp"),
                moe_dispatch_axes=(act_axes if moe_local and act_axes
                                   else None))
        params_shape = jax.eval_shape(lm.init, jax.random.key(0))
        pspec = shl.param_specs(params_shape, mesh, fsdp_axis=fsdp_axis,
                                model_axis=model_axis)
        if layout == "dp":
            layer_specs = jax.tree.map(
                lambda s: P(*s[1:]), pspec["layers"],
                is_leaf=lambda x: isinstance(x, P))
            lm = _dc.replace(lm, layer_param_specs=layer_specs)
        batch_sds = cfg_l.input_specs(shape)
        bspec = shl.batch_specs(batch_sds, mesh, batch_axes=baxes)
        with jax.set_mesh(mesh):
            if kind == "train":
                opt = momentum_sgd(1e-2, momentum=0.9)
                opt_shape = jax.eval_shape(opt.init, params_shape)
                ospec = shl.opt_state_specs(opt_shape, pspec, mesh)
                # dp layout: the per-layer explicit gathers live INSIDE
                # the scan; no outer whole-tree gather (it double-gathers).
                z3 = act_axes if zero3 and layout == "tp" and act_axes \
                    else None
                fn = make_train_step(lm, opt, pspec=pspec, zero3_axes=z3,
                                     microbatches=microbatches)
                jf = jax.jit(fn,
                             in_shardings=(ns(pspec), ns(ospec), ns(bspec)),
                             out_shardings=(ns(pspec), ns(ospec), ns(P())),
                             donate_argnums=(0, 1))
                lowered = jf.lower(params_shape, opt_shape, batch_sds)
            elif kind == "prefill":
                fn = make_prefill_step(lm)
                cache_shape = lm.cache_specs(B, S)
                cspec = shl.cache_specs(cache_shape, mesh, batch_axes=baxes)
                jf = jax.jit(fn, in_shardings=(ns(pspec), ns(bspec)),
                             out_shardings=(ns(P(baxes)), ns(cspec)))
                lowered = jf.lower(params_shape, batch_sds)
            else:  # decode
                fn = make_decode_step(lm)
                cache_shape = lm.cache_specs(B, S)
                cspec = shl.cache_specs(cache_shape, mesh, batch_axes=baxes)
                tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
                idx = jax.ShapeDtypeStruct((), jnp.int32)
                tspec = shl.batch_specs({"t": tok}, mesh,
                                        batch_axes=baxes)["t"]
                jf = jax.jit(fn,
                             in_shardings=(ns(pspec), ns(cspec), ns(tspec),
                                           ns(P())),
                             out_shardings=(ns(P()), ns(cspec)),
                             donate_argnums=(1,))
                lowered = jf.lower(params_shape, cache_shape, tok, idx)
            return lowered.compile()

    # ---- main lowering: full depth, scanned (memory + schedule + timing)
    compiled = lower_one(cfg, use_scan=True)
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled.cost_analysis())
    coll = parse_collectives(compiled.as_text())

    # ---- cost extrapolation: HLO cost analysis visits a while-loop (scan)
    # body ONCE, so flops/bytes/collectives of the scanned layers are under-
    # counted. Lower unrolled 1- and 2-layer variants; the L2−L1 delta is
    # the exact per-layer cost; total = L1 + (L−1)·Δ.
    t1 = time.perf_counter()
    c1 = lower_one(_dc.replace(cfg, n_layers=1), use_scan=False)
    c2 = lower_one(_dc.replace(cfg, n_layers=2), use_scan=False)
    cost1 = _cost_dict(c1.cost_analysis())
    cost2 = _cost_dict(c2.cost_analysis())
    coll1 = parse_collectives(c1.as_text())
    coll2 = parse_collectives(c2.as_text())
    L = cfg.n_layers

    def extrap(v1, v2):
        return v1 + (L - 1) * (v2 - v1)

    flops_dev = extrap(cost1.get("flops", 0.0), cost2.get("flops", 0.0))
    bytes_dev = extrap(cost1.get("bytes accessed", 0.0),
                       cost2.get("bytes accessed", 0.0))
    coll_bytes_dev = extrap(coll1["total_bytes"], coll2["total_bytes"])
    t_aux = time.perf_counter() - t1

    n_dev = 512 if mesh_kind == "multi" else 256
    res = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "kind": kind,
        "variant": {"zero3": zero3, "moe_local": moe_local, "layout": layout,
                    "seq_parallel": seq_parallel, "kv_int8": kv_int8,
                    "microbatches": microbatches, "tag": tag},
        "status": "ok", "n_devices": n_dev,
        "compile_s": round(t_compile, 1), "aux_compile_s": round(t_aux, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "cost": {
            # raw (scan-body-once) numbers, kept for reference
            "raw_flops_per_device": cost.get("flops"),
            "raw_bytes_per_device": cost.get("bytes accessed"),
            # extrapolated true per-device totals
            "flops_per_device": flops_dev,
            "bytes_accessed_per_device": bytes_dev,
            "collective_bytes_per_device": coll_bytes_dev,
        },
        "collectives": coll,
        "collectives_L1": coll1, "collectives_L2": coll2,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if verbose:
        m = res["memory"]
        live = (m["argument_bytes"] or 0) + (m["temp_bytes"] or 0)
        print(f"[{arch} × {shape} × {mesh_kind}] OK "
              f"compile={t_compile:.0f}s(+{t_aux:.0f}s aux) "
              f"mem/dev={live/2**30:.2f}GiB "
              f"flops/dev={flops_dev:.3e} "
              f"coll/dev={coll_bytes_dev/2**20:.1f}MiB")
        print("  memory_analysis:", {k: v for k, v in m.items() if v})
        print("  collective schedule (scanned module):",
              {k: v for k, v in coll.items()
               if isinstance(v, dict) and v["count"]})
    return res


def run_sync_step(arch: str, *, rate: float = 0.01, verbose=True) -> dict:
    """Lower the FedLuck cross-pod sync (Eq. 6) on the multi-pod mesh."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.dist.collectives import make_pod_sync
    from repro.launch.mesh import make_production_mesh

    t0 = time.perf_counter()
    cfg = get_config(arch)
    dim = cfg.param_count()
    # sharding-aligned 2D layout: n_blocks sharded over the 256 in-pod chips
    n_blocks = 4096
    blk = -(-dim // n_blocks)
    dim_p = n_blocks * blk
    mesh = make_production_mesh(multi_pod=True)
    n_pods = mesh.shape["pod"]
    sync = make_pod_sync(mesh, dim_p, rate=rate, n_blocks=n_blocks)
    p_sds = jax.ShapeDtypeStruct((n_blocks, blk), jnp.float32)
    d_sds = jax.ShapeDtypeStruct((n_pods, n_blocks, blk), jnp.float32)
    from jax.sharding import NamedSharding, PartitionSpec as P
    inpod = ("data", "model")
    p_sh = NamedSharding(mesh, P(inpod, None))
    d_sh = NamedSharding(mesh, P("pod", inpod, None))
    with jax.set_mesh(mesh):
        lowered = jax.jit(sync, in_shardings=(p_sh, d_sh, d_sh),
                          out_shardings=(p_sh, d_sh)).lower(
            p_sds, d_sds, d_sds)
        compiled = lowered.compile()
    coll = parse_collectives(compiled.as_text())
    cost = _cost_dict(compiled.cost_analysis())
    res = {"arch": arch, "kind": "fedluck_sync", "rate": rate, "dim": dim_p,
           "status": "ok", "compile_s": round(time.perf_counter() - t0, 1),
           "collectives": coll,
           "flops_per_device": cost.get("flops"),
           "bytes_accessed_per_device": cost.get("bytes accessed")}
    if verbose:
        print(f"[{arch} sync δ={rate}] coll/dev="
              f"{coll['total_bytes']/2**20:.2f}MiB "
              f"{ {k: v for k, v in coll.items() if isinstance(v, dict) and v['count']} }")
    return res


# -------------------------------------------------------------------- driver
def _result_path(arch, shape, mesh_kind):
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_kind}.json")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--sync-step", action="store_true")
    ap.add_argument("--rate", type=float, default=0.01)
    ap.add_argument("--zero3", action="store_true")
    ap.add_argument("--moe-local", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    os.makedirs(RESULTS_DIR, exist_ok=True)

    if args.sync_step:
        res = run_sync_step(args.arch, rate=args.rate)
        with open(os.path.join(RESULTS_DIR,
                               f"{args.arch}__sync.json"), "w") as f:
            json.dump(res, f, indent=1)
        return

    if args.all:
        from repro.configs import ARCH_IDS
        from repro.configs.base import SHAPES
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        failures = []
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mk in meshes:
                    path = _result_path(arch, shape, mk)
                    if os.path.exists(path) and not args.force:
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mk]
                    print(f"--- {arch} × {shape} × {mk}", flush=True)
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=3600)
                    sys.stdout.write(r.stdout)
                    if r.returncode != 0:
                        failures.append((arch, shape, mk))
                        sys.stderr.write(r.stderr[-3000:])
        print("FAILURES:", failures if failures else "none")
        return

    res = run_cell(args.arch, args.shape, args.mesh, zero3=args.zero3,
                   moe_local=args.moe_local, layout=args.layout,
                   microbatches=args.microbatch, kv_int8=args.kv_int8,
                   seq_parallel=not args.no_seq_parallel, tag=args.tag)
    path = _result_path(args.arch, args.shape, args.mesh)
    if args.tag:
        path = path.replace(".json", f"__{args.tag}.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
