"""``python -m repro.analysis`` — audit the repo's checked-in kernel
artifacts with the static analyzers.

Three audit stages (all offline, no TPU needed):

1. **autotune cache** — every entry of the checked-in (or
   ``--cache``-named) autotune cache must parse, and its winning config
   must fit the VMEM budget for the shape its key names (an over-budget
   winner could never have been measured honestly);
2. **config registry** — for every registered architecture, the
   characteristic decode GEMMs (attention/MLP/vocab projections) are
   priced against the VMEM budget per dispatch route; pipelined-route
   overruns surface as *info* clamp/fallback suggestions (the route is
   opt-in per spec — grok-scale ``d_ff`` legitimately needs the v2
   fallback), dense/sparse overruns are errors;
3. **CI-shape plans** — real plans are built for the autotuner's
   CI_SHAPES in both schedule orders and run through the schedule
   verifier, the DMA-hazard walk, and the ``GemmEngine.cost()``
   cross-check;
4. **sharded plans** — the CI-shape plans are partitioned over
   representative (s_data, s_model) shard grids and each shard's
   schedule is verified against its shard-local mask (plus the global
   partition check and per-shard VMEM pricing at shard-local dims) —
   no devices needed, the audit is pure numpy.

Exit status 1 when any error-severity diagnostic is found (the CI
``analysis-audit`` lane); ``--json`` emits machine-readable findings.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

from . import (INFO, Report, check_vmem, crosscheck_cost, verify_plan,
               verify_sharded_plan, vmem_budget)

# decode batch the registry audit prices (tokens on the kernel N axis)
AUDIT_TOKENS = 128


def _shape_from_key(key: str):
    try:
        dims = key.split("|", 1)[0].split("x")
        m, k, n = (int(d) for d in dims)
        return m, k, n
    except (ValueError, IndexError):
        return None


def _planes_from_key(key: str) -> int:
    """Digit planes resident per dense step for a cache key's plan part."""
    part = key.split("|")[1] if "|" in key else "default"
    if part == "default":
        return 4                           # ent/8b default grid
    try:
        from repro.core import encodings as enc
        encbits = part.split(".")[1]       # e.g. "ent8", "bitserial8"
        encoding = encbits.rstrip("0123456789")
        bits = int(encbits[len(encoding):] or 8)
        return enc.num_digits(encoding, bits)
    except Exception:
        return 4


def audit_autotune_cache(report: Report, path: Optional[str] = None,
                         budget: Optional[int] = None) -> None:
    from repro.kernels import autotune

    path = path or autotune.DEFAULT_CACHE_PATH
    try:
        cache = autotune.AutotuneCache.load(path)
    except Exception as e:
        report.add("AUDIT_BAD_ARTIFACT",
                   f"autotune cache {path!r} failed to load: {e}",
                   where=path)
        return
    if not cache.entries:
        report.add("AUDIT_BAD_ARTIFACT",
                   f"autotune cache {path!r} is missing or empty",
                   where=path)
        return
    for key, entry in sorted(cache.entries.items()):
        shape = _shape_from_key(key)
        if shape is None:
            report.add("AUDIT_BAD_ARTIFACT",
                       f"cache key {key!r} does not start with an MxKxN "
                       f"shape", where=path)
            continue
        m, k, n = shape
        check_vmem(entry.get("dispatch") or "dense", m, k, n,
                   block_m=entry["block_m"], block_k=entry["block_k"],
                   block_n=entry["block_n"],
                   n_planes=_planes_from_key(key), budget=budget,
                   report=report)


def audit_config_registry(report: Report,
                          budget: Optional[int] = None) -> None:
    from repro.configs import registry as configs
    from repro.kernels import ops

    for arch in configs.ARCHS:
        try:
            cfg = configs.get_config(arch)
        except Exception as e:
            report.add("AUDIT_BAD_ARTIFACT",
                       f"configs.get_config({arch!r}) failed: {e}",
                       where=arch)
            continue
        # the planned-weight GEMMs a decode step runs: (kernel rows M =
        # output channels, K = input dim), tokens on N
        gemms = {
            "attn": (cfg.d_model, cfg.d_model),
            "mlp_up": (cfg.d_ff, cfg.d_model),
            "mlp_down": (cfg.d_model, cfg.d_ff),
            "vocab": (cfg.vocab_size, cfg.d_model),
        }
        for name, (m, k) in gemms.items():
            n = AUDIT_TOKENS
            bm, bk, bn = ops.select_block_sizes(m, k, n)
            for route in ("dense", "sparse", "pipelined"):
                # the pipelined route is opt-in per spec and its acc
                # panel legitimately cannot fit grok-scale M: report the
                # clamp/fallback as info, not as a CI failure
                check_vmem(route, m, k, n, block_m=bm, block_k=bk,
                           block_n=bn, n_planes=4, budget=budget,
                           severity=INFO if route == "pipelined"
                           else "error",
                           where=f"{arch}.{name} {m}x{k}x{n}/{route}",
                           report=report)


def audit_ci_plans(report: Report) -> None:
    import numpy as np

    from repro.engine import engine_names, get_engine
    from repro.engine.spec import QuantSpec
    from repro.kernels import ops
    from repro.kernels.autotune import CI_SHAPES

    spec = QuantSpec(planes=3)
    rng = np.random.default_rng(0)
    kernel_engines = [get_engine(name) for name in engine_names()
                      if get_engine(name).uses_plans]
    for m, k, n in CI_SHAPES:
        w = (rng.standard_t(4, size=(k, m)) * 0.02).astype(np.float32)
        for order in ops.SCHEDULE_ORDERS:
            # each kernel engine's cost model against the plans it applies
            impls = [e.name for e in kernel_engines if e.order == order]
            planned, _sw = ops.plan_for(w, spec, order=order)
            sub = Report(f"plan {m}x{k}x{n} {order}")
            verify_plan(planned, spec.radix, order, report=sub)
            for impl in impls:
                crosscheck_cost(impl, m, k, n, spec, planned, report=sub)
            report.extend(sub)


# shard grids the sharded-plan audit partitions the CI-shape plans over
AUDIT_SHARD_GRIDS = ((2, 2), (4, 2))


def audit_sharded_plans(report: Report,
                        budget: Optional[int] = None) -> None:
    import numpy as np

    from repro.engine.spec import QuantSpec
    from repro.kernels import ops
    from repro.kernels.autotune import CI_SHAPES
    from repro.parallel.plan import shard_plan

    spec = QuantSpec(planes=3)
    rng = np.random.default_rng(0)
    for m, k, n in CI_SHAPES:
        w = (rng.standard_t(4, size=(k, m)) * 0.02).astype(np.float32)
        for order in ("m_major", "k_major"):
            planned, _sw = ops.plan_for(w, spec, order=order)
            for shards in AUDIT_SHARD_GRIDS:
                splan = shard_plan(planned, shards, verify=False)
                where = f"sharded {m}x{k}x{n} {order} {shards}"
                sub = Report(where)
                verify_sharded_plan(splan, report=sub)
                # per-shard VMEM pricing: each device runs the kernels
                # at shard-local dims, so that is the footprint to budget
                route = "pipelined" if order == "k_major" else "sparse"
                digits = splan.plan["digits"]
                m_s = digits.shape[1] // splan.s_model
                k_s = digits.shape[2] // splan.s_data
                check_vmem(route, m_s, k_s, n,
                           block_m=splan.block_m, block_k=splan.block_k,
                           block_n=128, n_planes=spec.num_digits,
                           budget=budget, where=where, report=sub)
                report.extend(sub)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description=__doc__.splitlines()[0])
    ap.add_argument("--cache", default=None,
                    help="autotune cache path to audit (default: the "
                         "checked-in cache)")
    ap.add_argument("--budget", type=int, default=None,
                    help="VMEM budget in bytes (default: "
                         "$REPRO_VMEM_BUDGET or 16 MiB)")
    ap.add_argument("--json", action="store_true",
                    help="emit diagnostics as JSON")
    ap.add_argument("--skip-plans", action="store_true",
                    help="skip the CI-shape plan verification stages "
                         "(single-device and sharded; no jax import)")
    args = ap.parse_args(argv)

    report = Report("repro.analysis audit")
    audit_autotune_cache(report, path=args.cache, budget=args.budget)
    audit_config_registry(report, budget=args.budget)
    if not args.skip_plans:
        audit_ci_plans(report)
        audit_sharded_plans(report, budget=args.budget)

    if args.json:
        payload = {
            "budget": vmem_budget(args.budget),
            "errors": len(report.errors),
            "warnings": len(report.warnings),
            "diagnostics": [
                {"code": d.code, "severity": d.severity, "step": d.step,
                 "where": d.where, "message": d.message,
                 "suggestion": d.suggestion}
                for d in report.diagnostics],
        }
        json.dump(payload, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        print(report)
    return 1 if report.errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
