import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# The lines above MUST precede every other import: jax locks the device
# count at first init, and the dry-run needs 512 placeholder host devices to
# build the production meshes.  (Smoke tests / benches import repro without
# this module and see 1 device.)  main() also pins the process, and the
# per-cell children it starts, to the CPU backend.
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, lower + compile the step the
cell's kind dictates (train_step / prefill_step / serve_step) against
ShapeDtypeStruct stand-ins on the production mesh:

    single-pod:  16 x 16          ('data', 'model')     = 256 chips
    multi-pod :  2 x 16 x 16      ('pod', 'data', 'model') = 512 chips

and record memory_analysis() (fits/doesn't), cost_analysis() (FLOPs/bytes
for the roofline), and the collective-op byte census parsed from the
optimized HLO.

Usage:
    python -m repro.launch.dryrun --arch minicpm-2b --shape train_4k \
        --mesh both --out results/minicpm-2b.train_4k.json
    python -m repro.launch.dryrun --all --out-dir results/dryrun
"""
import argparse
import json
import subprocess
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import (ARCHS, get_config, get_shape,
                                    cell_is_runnable, SHAPES)
from repro.launch import mesh as meshlib
from repro.launch import roofline as rl
from repro.obs import trace as obs_trace
from repro.parallel import sharding as sh
from repro.train import optimizer as opt
from repro.train import steps as st

__all__ = ["run_cell", "main"]


def _attach(tree_specs, tree_shardings):
    """ShapeDtypeStructs + NamedShardings -> sharded ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
        tree_specs, tree_shardings)


def _rules_for(cfg, multi_pod: bool, mesh, global_batch: int,
               seq_axis: Optional[str] = None,
               capacity_axis: Optional[str] = None,
               shard_kv: Optional[bool] = None,
               kv_seq_axis: Optional[str] = None):
    tp = mesh.shape["model"]
    dp = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                      if a != "model"]))
    if shard_kv is None:
        # explicit arg shardings must divide evenly
        shard_kv = cfg.n_kv_heads >= tp and cfg.n_kv_heads % tp == 0
    cap = capacity_axis
    if cap == "batch":
        cap = ("pod", "data") if multi_pod else ("data",)
    return sh.default_rules(
        multi_pod=multi_pod,
        fsdp=cfg.fsdp,
        fsdp_over_pod=cfg.fsdp_over_pod,
        shard_kv_heads=shard_kv,
        seq_axis=seq_axis,
        shard_batch=global_batch >= dp and global_batch % dp == 0,
        capacity_axis=cap,
        kv_seq_axis=kv_seq_axis,
    )


def _compile_step(cfg, shape, mesh, rules, multi_pod: bool,
                  microbatches: int = 1):
    """Lower + compile the step a cell's kind dictates.  Returns
    (lowered, compiled)."""
    with sh.mesh_context(mesh, rules):
        if shape.kind == "train":
            ocfg = opt.OptConfig(total_steps=1000,
                                 moment_dtype=cfg.opt_state_dtype)
            state, axes = st.abstract_train_state(cfg, ocfg)
            st_shard = st.train_state_shardings(axes, mesh, rules)
            b_specs = st.batch_specs(cfg, shape.global_batch, shape.seq_len)
            b_shard = st.batch_shardings(cfg, mesh, rules, shape.global_batch)
            step = st.make_train_step(cfg, ocfg, microbatches=microbatches)
            args = (_attach(state, st_shard), _attach(b_specs, b_shard))
            lowered = jax.jit(step, donate_argnums=(0,)).lower(*args)
        elif shape.kind == "prefill":
            state, axes = st.abstract_train_state(
                cfg, opt.OptConfig(moment_dtype=cfg.opt_state_dtype))
            p_shard = st.train_state_shardings(axes, mesh, rules)
            b_specs = st.batch_specs(cfg, shape.global_batch, shape.seq_len)
            b_shard = st.batch_shardings(cfg, mesh, rules, shape.global_batch)
            # prefill runs inference: drop labels from the lowered signature
            b_specs.pop("labels"); b_shard.pop("labels")
            step = st.make_prefill_step(cfg)
            args = (_attach(state.params, p_shard.params),
                    _attach(b_specs, b_shard))
            lowered = jax.jit(step).lower(*args)
        else:  # decode
            state, axes = st.abstract_train_state(
                cfg, opt.OptConfig(moment_dtype=cfg.opt_state_dtype))
            p_shard = st.train_state_shardings(axes, mesh, rules)
            dstate, daxes = st.abstract_decode_state(cfg, shape.global_batch,
                                                     shape.seq_len)
            d_shard = st.decode_state_shardings(daxes, mesh, rules)
            b_shard = st.batch_shardings(cfg, mesh, rules, shape.global_batch)
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32,
                                       sharding=b_shard["tokens"])
            pos = jax.ShapeDtypeStruct(
                (shape.global_batch,), jnp.int32,
                sharding=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(
                        *b_shard["tokens"].spec[:1])))
            step = st.make_serve_step(cfg)
            args = (_attach(state.params, p_shard.params), tok, pos,
                    _attach(dstate, d_shard))
            lowered = jax.jit(step, donate_argnums=(3,)).lower(*args)

        compiled = lowered.compile()
    return lowered, compiled


def _cost_tuple(compiled) -> dict:
    """(flops, bytes, collective-bytes, coll-by-op) of a compiled module."""
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(sum(coll.values())), "coll_by_op": coll,
            "transcendentals": float(cost.get("transcendentals", 0.0))}


def _extrapolate(c1: dict, c2: dict, n_layers: int) -> dict:
    """XLA cost analysis counts a while-loop body ONCE (calibrated on this
    backend), so a scanned-L-layer module under-reports by ~L.  We compile
    depth-1 (scan unrolled trivially) and depth-2 (scan_unroll=2, so both
    iterations appear in the HLO) variants: body = c2 - c1, base = c1 -
    body, total = base + L * body, for each of flops / bytes / collective
    bytes.  Dense (non-chunked) attention is used in the variants so
    softmax-attention FLOPs are not hidden inside inner chunk loops."""
    out = {}
    for k in ("flops", "bytes", "coll", "transcendentals"):
        body = max(c2[k] - c1[k], 0.0)
        base = max(c1[k] - body, 0.0)
        out[k] = base + n_layers * body
    out["coll_by_op"] = {
        op: max(c1["coll_by_op"].get(op, 0)
                + (n_layers - 1) * max(c2["coll_by_op"].get(op, 0)
                                       - c1["coll_by_op"].get(op, 0), 0), 0)
        for op in set(c1["coll_by_op"]) | set(c2["coll_by_op"])}
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               quant_planes: int = 0, seq_axis: Optional[str] = None,
               microbatches: int = 1, remat: Optional[bool] = None,
               capacity_axis: Optional[str] = None,
               shard_kv: Optional[bool] = None,
               kv_seq_axis: Optional[str] = None,
               fsdp: Optional[bool] = None,
               moe_groups: int = 0,
               param_dtype: Optional[str] = None,
               skip_cost_variants: bool = False,
               quant_impl: str = "pallas_fused",
               quant_spec: Optional[str] = None,
               mesh_shape=None):
    """Lower + compile one cell (+ cost variants).  Returns
    (record dict, lowered, compiled).

    mesh_shape: custom (data, model) mesh instead of the production
    16x16 / 2x16x16 (``--mesh DxM``); multi_pod is ignored then.
    """
    from repro.engine import spec_from_flags
    if mesh_shape is not None:
        mesh_name = "x".join(str(s) for s in mesh_shape)
    else:
        mesh_name = "multi" if multi_pod else "single"
    cfg = get_config(arch)
    overrides = {}
    spec = spec_from_flags(quant_spec, quant_planes, quant_impl)
    if spec is not None:
        # bake the spec into the cfg the steps close over (no global
        # switch).  Kernel impls lower each linear under tracing to one
        # int8 dot (what the bw_gemm kernel costs before plane skipping),
        # so cost_analysis reflects the kernelized technique instead of
        # the 4-dot oracle.
        quant_planes = spec.planes
        overrides["quant_planes"] = spec.planes
        overrides["quant"] = spec
    if remat is not None:
        overrides["remat"] = remat
    if fsdp is not None:
        overrides["fsdp"] = fsdp
    if moe_groups:
        overrides["moe_dispatch_groups"] = moe_groups
    if param_dtype:
        overrides["param_dtype"] = param_dtype
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    if not cell_is_runnable(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": mesh_name,
                "status": "skipped",
                "reason": "long_500k needs sub-quadratic attention "
                          "(full-attention arch; see DESIGN.md)"}, None, None

    if mesh_shape is not None:
        mesh = meshlib.make_mesh(tuple(mesh_shape), ("data", "model"))
        multi_pod = False
    else:
        mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    rules = _rules_for(cfg, multi_pod, mesh, shape.global_batch, seq_axis,
                       capacity_axis, shard_kv, kv_seq_axis)

    # 1) the deliverable compile: full depth, production attention path
    t0 = time.time()
    with obs_trace.span("dryrun.compile", cat="dryrun", arch=arch,
                        shape=shape_name, mesh=mesh_name):
        lowered, compiled = _compile_step(cfg, shape, mesh, rules,
                                          multi_pod, microbatches)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    raw = _cost_tuple(compiled)

    # 2) cost variants: depth 1 / depth 2 (unrolled), dense attention
    n_l = cfg.n_layers
    if skip_cost_variants or n_l <= 2:
        corrected = raw
    else:
        vkw = dict(n_layers=1, attn_chunk=1 << 30)
        if cfg.n_encoder_layers:
            vkw["n_encoder_layers"] = 1
        cfg1 = cfg.replace(**vkw)
        vkw2 = dict(vkw, n_layers=2, scan_unroll=2)
        if cfg.n_encoder_layers:
            vkw2["n_encoder_layers"] = 2
        cfg2 = cfg.replace(**vkw2)
        with obs_trace.span("dryrun.cost_variants", cat="dryrun",
                            arch=arch, shape=shape_name, mesh=mesh_name):
            _, comp1 = _compile_step(cfg1, shape, mesh, rules, multi_pod,
                                     microbatches)
            c1 = _cost_tuple(comp1)
            del comp1
            _, comp2 = _compile_step(cfg2, shape, mesh, rules, multi_pod,
                                     microbatches)
            c2 = _cost_tuple(comp2)
            del comp2
        corrected = _extrapolate(c1, c2, n_l)

    kind = shape.kind
    mfl = rl.model_flops(cfg, shape.global_batch, shape.seq_len, kind)
    roof = rl.roofline_from_compiled(
        {"flops": corrected["flops"], "bytes accessed": corrected["bytes"]},
        "", chips, mfl)
    roof.coll_bytes = corrected["coll"]
    roof.coll_by_op = corrected["coll_by_op"]
    roof.t_collective = corrected["coll"] / rl.ICI_BW
    terms = {"compute": roof.t_compute, "memory": roof.t_memory,
             "collective": roof.t_collective}
    roof.bottleneck = max(terms, key=terms.get)

    def _mem_attr(name):
        v = getattr(mem, name, None)
        return int(v) if v is not None else None

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok", "kind": kind, "chips": chips,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "quant_planes": quant_planes,
        "quant_impl": spec.impl if spec else None,
        "quant_spec": str(spec) if spec else None,
        "seq_axis": seq_axis,
        "capacity_axis": capacity_axis,
        "kv_seq_axis": kv_seq_axis,
        "fsdp": cfg.fsdp,
        "microbatches": microbatches,
        "remat": cfg.remat,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "t_compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": _mem_attr("argument_size_in_bytes"),
            "output_bytes": _mem_attr("output_size_in_bytes"),
            "temp_bytes": _mem_attr("temp_size_in_bytes"),
            "generated_code_bytes": _mem_attr("generated_code_size_in_bytes"),
            "alias_bytes": _mem_attr("alias_size_in_bytes"),
        },
        "cost_raw": raw,
        "cost_corrected": {k: corrected[k] for k in
                           ("flops", "bytes", "coll")},
        "roofline": roof.to_dict(),
        "hlo_collective_count": sum(
            1 for ln in hlo.splitlines()
            if any(f" {op}(" in ln or f" {op}-start(" in ln
                   for op in rl._COLLECTIVE_OPS)),
    }
    return record, lowered, compiled


def run_cell(arch: str, shape_name: str, mesh_kind: str = "both",
             **kw) -> list:
    """mesh_kind: 'single' | 'multi' | 'both' (the production meshes), or
    a custom 'DxM' (data x model) shape literal, e.g. '4x2'."""
    out = []
    kinds = {"single": [False], "multi": [True],
             "both": [False, True]}.get(mesh_kind)
    if kinds is None:
        shape = meshlib.parse_mesh_shape(mesh_kind)
        if len(shape) != 2:
            raise ValueError(f"custom --mesh expects two axes DxM, got "
                             f"{mesh_kind!r}")
        rec, _, _ = lower_cell(arch, shape_name, False, mesh_shape=shape,
                               **kw)
        return [rec]
    for mp in kinds:
        rec, _, _ = lower_cell(arch, shape_name, mp, **kw)
        out.append(rec)
    return out


def _print_record(rec: dict) -> None:
    if rec["status"] != "ok":
        print(f"[dryrun] {rec['arch']} x {rec['shape']} ({rec['mesh']}): "
              f"SKIP - {rec['reason']}")
        return
    r = rec["roofline"]
    m = rec["memory"]
    arg_gb = (m["argument_bytes"] or 0) / 2**30
    tmp_gb = (m["temp_bytes"] or 0) / 2**30
    print(f"[dryrun] {rec['arch']} x {rec['shape']} ({rec['mesh']}, "
          f"{rec['chips']} chips): OK  "
          f"args {arg_gb:.2f} GiB/dev, temps {tmp_gb:.2f} GiB/dev | "
          f"t_comp {r['t_compute_s']:.4f}s t_mem {r['t_memory_s']:.4f}s "
          f"t_coll {r['t_collective_s']:.4f}s -> {r['bottleneck']}-bound, "
          f"useful {100 * r['useful_ratio']:.1f}%, "
          f"roofline {100 * r['roofline_fraction']:.1f}%  "
          f"(compile {rec['t_compile_s']}s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    help="'single' (16x16), 'multi' (2x16x16), 'both', or "
                         "a custom 'DxM' data x model shape (e.g. 4x2) "
                         "built via launch.mesh.make_mesh")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell in subprocesses")
    ap.add_argument("--quant-spec", default=None,
                    help="full quantized-GEMM spec, e.g. "
                         "'planes=4,encoding=ent,impl=pallas' (the two "
                         "flags below are sugar for its fields)")
    ap.add_argument("--quant-planes", type=int, default=0,
                    help="enable the paper's BW-decomposed int8 path with "
                         "this many EN-T digit planes")
    from repro.engine import IMPLS
    ap.add_argument("--quant-impl", default="pallas_fused", choices=IMPLS,
                    help="quantized matmul engine to lower (kernel impls "
                         "use their cost-representative int8 lowering)")
    ap.add_argument("--seq-axis", default=None,
                    help="mesh axis for sequence parallelism (e.g. 'model')")
    ap.add_argument("--capacity-axis", default=None,
                    help="shard the MoE capacity dim ('batch' = DP axes)")
    ap.add_argument("--kv-seq-axis", default=None,
                    help="shard decode KV caches on the sequence dim "
                         "(e.g. 'model')")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params over the data axis (serving)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--moe-groups", type=int, default=0,
                    help="MoE local-dispatch groups (= DP shard count)")
    ap.add_argument("--param-dtype", default=None,
                    help="override param dtype (e.g. bfloat16 for serving)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable repro.obs tracing and write a Chrome "
                         "trace-event JSON of the lower/compile cells")
    args = ap.parse_args(argv)
    from repro.launch.runtime import pin_cpu
    pin_cpu("repro.launch.dryrun")

    if args.trace:
        obs_trace.enable(clear_events=True)

    if args.all:
        return _run_all(args)

    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --all)")
    recs = run_cell(args.arch, args.shape, args.mesh,
                    quant_planes=args.quant_planes,
                    quant_impl=args.quant_impl,
                    quant_spec=args.quant_spec, seq_axis=args.seq_axis,
                    capacity_axis=args.capacity_axis,
                    kv_seq_axis=args.kv_seq_axis,
                    fsdp=False if args.no_fsdp else None,
                    remat=False if args.no_remat else None,
                    moe_groups=args.moe_groups,
                    param_dtype=args.param_dtype,
                    microbatches=args.microbatches)
    for rec in recs:
        _print_record(rec)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
    if args.trace:
        obs_trace.save(args.trace)
        print(f"[obs] trace written to {args.trace} "
              f"({len(obs_trace.events())} events)", file=sys.stderr)
    return 0 if all(r["status"] in ("ok", "skipped") for r in recs) else 1


def _run_all(args) -> int:
    """Each cell in its own subprocess: isolates jax state + reclaims RAM."""
    os.makedirs(args.out_dir, exist_ok=True)
    failures = []
    for arch in ARCHS:
        for shape_name in SHAPES:
            out = os.path.join(args.out_dir,
                               f"{arch}.{shape_name}.json")
            if os.path.exists(out):
                print(f"[dryrun] cached: {out}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", args.mesh, "--out", out]
            if args.quant_planes:
                cmd += ["--quant-planes", str(args.quant_planes),
                        "--quant-impl", args.quant_impl]
            if args.quant_spec:
                cmd += ["--quant-spec", args.quant_spec]
            print(f"[dryrun] {' '.join(cmd[3:])}", flush=True)
            r = subprocess.run(cmd)
            if r.returncode != 0:
                failures.append((arch, shape_name))
                print(f"[dryrun] FAILED: {arch} x {shape_name}")
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}")
        return 1
    print("[dryrun] all cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
