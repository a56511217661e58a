"""Sharded-vs-single-device GEMM driver for benches and CI.

Runs the sharded planned GEMM (``sharded_planned_apply``) against the
single-device reference (``planned_dense_apply``) on a forced-host CPU
mesh: parity, per-device collective-bytes (from the cost model — the
deterministic, baseline-gated part) and wall-clock tok/s for both paths
(volatile; stripped from the BENCH baseline).

Run as a subprocess so the forced device count binds before jax
initializes its backends:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.parallel.benchrun --mesh 4x2 --json

When XLA_FLAGS does not already force a device count, ``--devices``
(default 8) is merged in at import time, before any jax backend query.
"""
from __future__ import annotations

import os

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # before any backend init (safe: importing jax does not lock devices)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import argparse
import json
import sys
import time

__all__ = ["run", "main"]


def run(mesh_shape, m: int, k: int, batch: int, planes: int,
        reps: int = 3, seed: int = 0) -> dict:
    """One sharded-vs-single comparison cell.  Returns the result dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import QuantSpec, get_engine
    from repro.kernels import ops
    from repro.parallel.apply import make_gemm_mesh, sharded_planned_apply
    from repro.parallel.plan import plan_sharded_weight

    s_data, s_model = mesh_shape
    spec = QuantSpec(planes=planes, block_m=128, block_k=128,
                     act_quant="per_token")
    rng = np.random.default_rng(seed)
    w = (rng.standard_t(4, size=(k, m)) * 0.02).astype(np.float32)
    x = rng.normal(0, 1, size=(batch, k)).astype(np.float32)
    bias = rng.normal(0, 0.1, size=(m,)).astype(np.float32)
    mesh = make_gemm_mesh((s_data, s_model))

    def _time(fn):
        y = jax.block_until_ready(fn(jnp.asarray(x)))   # warm-up + result
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(jnp.asarray(x)))
        return np.asarray(y), (time.perf_counter() - t0) / reps

    out = {"mesh": f"{s_data}x{s_model}", "devices": len(jax.devices()),
           "m": m, "k": k, "batch": batch, "planes": planes,
           "parity": {}, "collective_bytes": {}, "density": {},
           "timing": {}}
    for order in ("m_major", "k_major"):
        plan = ops.plan_dense_weight(w, spec, order=order)
        splan = plan_sharded_weight(w, spec, (s_data, s_model), order=order)

        def single(xx, plan=plan, order=order):
            return ops.planned_dense_apply(
                plan, xx, spec, m, bias=jnp.asarray(bias),
                activation="silu", fused=False, dispatch="auto",
                order=order)

        def sharded(xx, splan=splan):
            return sharded_planned_apply(
                splan, xx, spec, m, bias=jnp.asarray(bias),
                activation="silu", dispatch="auto", mesh=mesh)

        want, t_single = _time(jax.jit(single))
        got, t_sharded = _time(jax.jit(sharded))
        err = float(np.abs(got - want).max())
        out["parity"][order] = bool(
            np.allclose(got, want, rtol=1e-6, atol=1e-6))
        out["density"][order] = round(splan.density(), 4)
        # serving orientation (tokens on M, output channels on N) — the
        # same per-device reduce traffic TierRouter prices
        impl = "pallas_pipelined" if order == "k_major" else "pallas_sparse"
        cost = get_engine(impl).cost(batch, k, m, spec,
                                     density=splan.density(),
                                     shards=(s_data, s_model))
        out["collective_bytes"][order] = int(cost["collective_bytes"])
        out["timing"][order] = {
            "single_s": round(t_single, 4),
            "sharded_s": round(t_sharded, 4),
            "single_tok_per_s": round(batch / t_single, 1),
            "sharded_tok_per_s": round(batch / t_sharded, 1),
        }
        if not out["parity"][order]:
            out["timing"][order]["max_err"] = err
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default="4x2", metavar="DxM",
                    help="mesh shape 'data x model' (default 4x2)")
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--k", type=int, default=512)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--planes", type=int, default=3)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--json", action="store_true",
                    help="print the result dict as JSON on stdout")
    args = ap.parse_args(argv)

    from repro.launch.runtime import pin_cpu
    pin_cpu("repro.parallel.benchrun")
    from repro.launch.mesh import parse_mesh_shape
    from repro.parallel.collectives import enable_async_collectives
    enable_async_collectives()          # no-op flags on the CPU backend
    shape = parse_mesh_shape(args.mesh)
    if len(shape) != 2:
        ap.error(f"--mesh expects two axes DxM, got {args.mesh!r}")
    result = run(shape, args.m, args.k, args.batch, args.planes,
                 reps=args.reps)
    if args.json:
        json.dump(result, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        for order, timing in result["timing"].items():
            print(f"[benchrun] {result['mesh']} {order}: parity="
                  f"{result['parity'][order]} "
                  f"coll={result['collective_bytes'][order]}B "
                  f"single={timing['single_tok_per_s']} tok/s "
                  f"sharded={timing['sharded_tok_per_s']} tok/s")
    return 0 if all(result["parity"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
