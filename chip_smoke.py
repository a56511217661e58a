"""Smoke test of the main path on a TPU.

    python chip_smoke.py             # one chip: serve minicpm-2b
    python chip_smoke.py --chips 4   # four chips: the sharded GEMM only

One chip: minicpm-2b at its published widths (as many of its 40 layers as
fit in HBM) answers 4 requests through ``ServeEngine.run`` with every
dense weight planned into EN-T digit planes and applied by the fused
Pallas kernel (``impl=pallas_fused``).  The greedy tokens are then checked
against the ``ref`` engine (one XLA int32 dot on the same quantization
grid, same seed, so the same weights), and the GEMM dispatch counter must
show that only kernel routes ran.

Four chips: ``repro.parallel.sharded_planned_apply`` on 2x2, 4x1 and 1x4
meshes with minicpm-2b's MLP weight, in both reduce modes, each checked
against ``planned_dense_apply`` on device 0.

Everything runs in this one process, which holds the chip.  Any failed
phase raises, and the exit code is then non-zero; on success the last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "minicpm-2b"
SPEC = "planes=3,encoding=ent,impl=pallas_fused,act_quant=per_token"
REF_SPEC = "planes=3,encoding=ent,impl=ref,act_quant=per_token"
REQUESTS, BATCH, PROMPT_LEN, NEW_TOKENS = 4, 4, 16, 16
SEED = 0
# HBM kept free beyond the resident weights, plans and decode state: one
# layer's planning intermediates, the step's buffers, allocator slack.
# On a v5e the measured peak exceeded the predicted resident bytes by
# 0.16 GB at 38 layers.
HEADROOM_BYTES = 1 << 30
# The two engines' integer GEMMs agree exactly (check_gemm), but they are
# two compiled programs, and XLA fuses their bfloat16 norms, residuals and
# attention differently, so hidden states and logits differ in the last
# bfloat16 bits.  A greedy token may therefore differ from the reference
# only where the reference nearly ties: its top-2 logit margin below
# 2**-5 of the top logit (4 to 8 bfloat16 ulps of it).
TIE_MARGIN_REL = 2.0 ** -5
KERNEL_ROUTES = ("dense", "sparse", "pipelined")
TIMED_STEPS = 8


def device_info(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; exits unless they are
    ``chips`` TPUs (JAX falls back to the CPU when the TPU runtime fails
    to start, and the kernels would then quietly run interpreted)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{info['count']}")
    return info


def _tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _plan_bytes(k: int, n: int, spec) -> int:
    """Device bytes of one weight's plan record (ops.plan_dense_weight)."""
    from repro.kernels import ops
    bm, bk, _ = ops.select_block_sizes(n, k, 128, spec)
    mb, kb = -(-n // bm), -(-k // bk)
    m_pad, k_pad = mb * bm, kb * bk
    bw = spec.num_digits
    sched_rows = bw * mb * kb + mb          # every block + row sentinels
    return (bw * m_pad * k_pad              # int8 digit planes
            + bw * mb * kb                  # bool occupancy mask
            + sched_rows * 9 * 4            # int32 schedule (upper bound)
            + 3 * m_pad * 4)                # row_perm, inv_perm, sw_rows


def resident_bytes(cfg, spec, n_layers: int) -> int:
    """Predicted HBM held by a kernel-path ServeEngine of ``n_layers``:
    master weights, one plan per block weight, and the decode state."""
    import jax
    from repro.models.api import get_api
    from repro.parallel.sharding import unbox
    c = cfg.replace(n_layers=n_layers)
    api = get_api(c)
    params = jax.eval_shape(
        lambda: unbox(api.init(jax.random.PRNGKey(0), c)))
    state = jax.eval_shape(
        lambda: unbox(api.init_decode(c, BATCH, PROMPT_LEN + NEW_TOKENS + 1)))
    plans = sum(_plan_bytes(w.shape[1], w.shape[2], spec) * w.shape[0]
                for path, w in jax.tree_util.tree_leaves_with_path(
                    params["blocks"])
                if path[-1].key == "w" and w.ndim == 3)
    return _tree_bytes(params) + _tree_bytes(state) + plans


def fit_layers(cfg, spec, limit: int) -> int:
    """The deepest cut of ``cfg`` whose predicted resident bytes plus
    HEADROOM_BYTES fit in ``limit``."""
    for n in range(cfg.n_layers, 0, -1):
        if resident_bytes(cfg, spec, n) + HEADROOM_BYTES <= limit:
            return n
    raise SystemExit(f"chip_smoke: not one layer of {cfg.name} fits in "
                     f"{limit / 1e9:.2f} GB of device memory")


def make_requests(vocab: int):
    from repro.serving import Request
    rng = np.random.default_rng(SEED)
    return [Request(i, rng.integers(0, vocab, PROMPT_LEN).tolist(),
                    NEW_TOKENS) for i in range(REQUESTS)]


def time_steps(eng) -> tuple:
    """(first-call seconds: compile + one step, median steady-state step
    seconds) of the engine's jitted decode step, each ending in
    block_until_ready."""
    import jax
    import jax.numpy as jnp
    args = (eng.params, jnp.asarray(eng.slots.cur),
            jnp.asarray(eng.slots.pos), eng.state)
    t0 = time.perf_counter()
    jax.block_until_ready(eng.step_fn(*args))
    first = time.perf_counter() - t0
    times = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        jax.block_until_ready(eng.step_fn(*args))
        times.append(time.perf_counter() - t0)
    return first, float(np.median(times))


def serve(cfg, spec_text: str, label: str):
    """Build a ServeEngine, time its decode step, serve the requests.
    Returns (engine, {rid: tokens})."""
    from repro.engine import QuantSpec
    from repro.serving import ServeEngine
    spec = QuantSpec.parse(spec_text)
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, BATCH, PROMPT_LEN + NEW_TOKENS + 1, seed=SEED,
                      quant=spec)
    setup = time.perf_counter() - t0
    first, step = time_steps(eng)
    print(f"[{label}] spec {spec}: engine set-up {setup:.2f} s "
          f"(init + planning), first step {first:.2f} s (compile + run), "
          f"steady decode step {step * 1e3:.2f} ms over {TIMED_STEPS} "
          f"steps (batch {BATCH})", flush=True)
    if eng.quant.plan_stats:
        print(f"[{label}] plans: {eng.quant.plan_stats}", flush=True)
    reqs = make_requests(cfg.vocab_size)
    stats = eng.run(reqs)
    if stats["requests"] != REQUESTS or \
            any(len(r.out) != NEW_TOKENS for r in reqs):
        raise SystemExit(f"chip_smoke: {label} engine completed "
                         f"{stats['requests']} of {REQUESTS} requests")
    print(f"[{label}] served {stats['requests']} requests, "
          f"{stats['generated_tokens']} tokens in {stats['engine_steps']} "
          f"steps, wall {stats['wall_s']} s", flush=True)
    return eng, {r.rid: list(r.out) for r in reqs}


def check_dispatch() -> dict:
    """Routes the quantized GEMMs took while the serve step was traced:
    kernel routes only, and no kernel engine lowered to a plain int8 dot
    for want of a plan."""
    from repro.engine.registry import TRACED_INT8_ROUTE
    from repro.obs import metrics as obs_metrics
    values = obs_metrics.snapshot()["repro_gemm_dispatch_total"]["values"]
    routes = {k.partition("=")[2]: v for k, v in values.items() if k}
    print(f"[dispatch] repro_gemm_dispatch_total {routes}; "
          f"{TRACED_INT8_ROUTE} lowerings: "
          f"{routes.get(TRACED_INT8_ROUTE, 0)}", flush=True)
    if not routes or any(r not in KERNEL_ROUTES for r in routes):
        raise SystemExit(f"chip_smoke: non-kernel GEMM routes {routes}")
    return routes


def top2_margin(eng, tokens) -> tuple:
    """(top-2 margin, top logit) of the next-token logits the engine's
    model gives after ``tokens``, from a full-sequence forward."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import lm_apply
    logits, _ = jax.jit(lambda p, t: lm_apply(p, t, eng.cfg))(
        eng.params, jnp.asarray([tokens], jnp.int32))
    top = np.sort(np.asarray(logits[0, -1], np.float32))[-2:]
    return float(top[1] - top[0]), float(top[1])


def compare(got: dict, ref_eng, want: dict, prompts: dict) -> int:
    """Tokens of ``got`` matching ``want`` up to each request's first
    divergence; fails on a divergence the reference did not nearly tie."""
    matched = 0
    for rid, ref in want.items():
        out = got[rid]
        t = next((i for i, (a, b) in enumerate(zip(out, ref)) if a != b),
                 len(ref))
        matched += t
        if t == len(ref):
            continue
        margin, top = top2_margin(ref_eng, prompts[rid] + ref[:t])
        tol = TIE_MARGIN_REL * abs(top)
        print(f"[compare] request {rid} diverges at generated token {t}: "
              f"kernel {out[t]} vs ref {ref[t]}; ref top-2 margin "
              f"{margin:.4f} (tolerance {tol:.4f})", flush=True)
        if margin >= tol:
            raise SystemExit(f"chip_smoke: request {rid} diverges from "
                             f"the reference at token {t}")
    return matched


def mlp_operands(cfg):
    """Seeded float32 (w [d_model, d_ff], x [BATCH, d_model], bias
    [d_ff]) at the config's MLP widths."""
    import jax.numpy as jnp
    k, n = cfg.d_model, cfg.d_ff
    rng = np.random.default_rng(SEED)
    w = rng.normal(0, k ** -0.5, (k, n)).astype(np.float32)
    x = rng.normal(0, 1, (BATCH, k)).astype(np.float32)
    bias = rng.normal(0, 0.1, (n,)).astype(np.float32)
    return jnp.asarray(w), jnp.asarray(x), jnp.asarray(bias)


def check_gemm(cfg, spec_text: str) -> None:
    """One MLP GEMM at full width through the kernel path, equal to an
    exact host reference: int64 matmul of the same quantized operands,
    dequantized with the same float32 product."""
    import jax
    from repro.core import quant as quantlib
    from repro.engine import QuantSpec, get_engine
    from repro.kernels import ops
    spec = QuantSpec.parse(spec_text)
    w, x, _ = mlp_operands(cfg)
    k, n = w.shape
    qw, sw = quantlib.quantize_for_spec(w, spec, axis=0)
    qx, sx = quantlib.quantize_for_spec(x, spec, axis=-1)
    acc = np.asarray(qx, np.int64) @ np.asarray(qw, np.int64)
    want = acc.astype(np.float32) * (np.asarray(sx, np.float32)
                                     * np.asarray(sw, np.float32))
    plan = ops.plan_dense_weight(w, spec, use_cache=False)
    got = {"kernel": ops.planned_dense_apply(plan, x, spec, n)}
    for impl in ("int8", "ref"):
        got[impl] = get_engine(impl).apply(w, x, spec)
    # as inside the serve step: activations (and, for ref, weights)
    # quantized by the compiled program, not op by op
    got["kernel_jit"] = jax.jit(
        lambda xx: ops.planned_dense_apply(plan, xx, spec, n))(x)
    got["ref_jit"] = jax.jit(
        lambda ww, xx: get_engine("ref").apply(ww, xx, spec))(w, x)
    err = {impl: float(np.abs(np.asarray(y, np.float32) - want).max())
           for impl, y in got.items()}
    print(f"[gemm] {k}x{n} GEMM, batch {BATCH}: max |y - exact host "
          f"reference| {err} (scale of y: "
          f"{float(np.abs(want).max()):.3g})", flush=True)
    if err["kernel"] != 0.0:
        raise SystemExit("chip_smoke: the kernel GEMM is not exact")


def one_chip() -> None:
    import jax
    from repro import obs
    from repro.configs.registry import get_config
    from repro.engine import QuantSpec
    full = get_config(ARCH, param_dtype="bfloat16")
    spec = QuantSpec.parse(SPEC)
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    n_layers = fit_layers(full, spec, limit)
    cfg = full.replace(n_layers=n_layers)
    print(f"[config] {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}), bfloat16 masters; serving {n_layers} of "
          f"{full.n_layers} layers (predicted resident "
          f"{resident_bytes(full, spec, n_layers) / 1e9:.2f}"
          f" GB + {HEADROOM_BYTES / 2**30:.0f} GiB headroom, HBM limit "
          f"{limit / 1e9:.2f} GB)", flush=True)
    check_gemm(cfg, SPEC)
    obs.enable(clear_events=True)
    eng, got = serve(cfg, SPEC, "kernel")
    check_dispatch()
    mem = jax.devices()[0].memory_stats()
    print(f"[memory] kernel engine: peak {mem['peak_bytes_in_use'] / 1e9:.2f}"
          f" GB, in use {mem['bytes_in_use'] / 1e9:.2f} GB", flush=True)
    prompts = {r.rid: list(r.prompt) for r in make_requests(cfg.vocab_size)}
    del eng                         # the two engines do not fit together
    gc.collect()
    ref_eng, want = serve(cfg, REF_SPEC, "ref")
    matched = compare(got, ref_eng, want, prompts)
    print(f"[compare] {matched} of {REQUESTS * NEW_TOKENS} greedy tokens "
          f"match the ref engine", flush=True)


def four_chips() -> None:
    import jax
    from repro.configs.registry import get_config
    from repro.engine import QuantSpec
    from repro.kernels import ops
    from repro.parallel.apply import make_gemm_mesh, sharded_planned_apply
    from repro.parallel.plan import plan_sharded_weight
    spec = QuantSpec.parse(SPEC)
    w, x, bias = mlp_operands(get_config(ARCH))
    k, n = w.shape
    with jax.default_device(jax.devices()[0]):
        plan = ops.plan_dense_weight(w, spec)
        want = np.asarray(jax.jit(lambda xx: ops.planned_dense_apply(
            plan, xx, spec, n, bias=bias, activation="silu", fused=False,
            dispatch="auto"))(x))
    print(f"[sharded] {ARCH} MLP weight {k}x{n}, batch {BATCH}, spec "
          f"{spec}", flush=True)
    for shards in ((2, 2), (4, 1), (1, 4)):
        splan = plan_sharded_weight(w, spec, shards)
        mesh = make_gemm_mesh(shards)
        for reduce in ("psum", "psum_scatter"):
            f = jax.jit(lambda xx, splan=splan, mesh=mesh, reduce=reduce:
                        sharded_planned_apply(
                            splan, xx, spec, n, bias=bias,
                            activation="silu", mesh=mesh, reduce=reduce))
            y = jax.block_until_ready(f(x))
            got = np.asarray(y)
            err = float(np.abs(got - want).max())
            devices = len(y.sharding.device_set)
            ok = np.allclose(got, want, rtol=1e-6, atol=1e-6) and \
                devices == 4
            print(f"[sharded] mesh {shards[0]}x{shards[1]} {reduce}, tile "
                  f"({splan.block_m}, {splan.block_k}): max "
                  f"|sharded - device 0| {err:.3g}, output on {devices} "
                  f"devices, {'ok' if ok else 'MISMATCH'}", flush=True)
            if not ok:
                raise SystemExit(f"chip_smoke: sharded GEMM on mesh "
                                 f"{shards} ({reduce}) does not match")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: the sharded GEMM path "
                         "on four chips")
    args = ap.parse_args(argv)
    info = device_info(args.chips)
    from repro.launch.runtime import enable_compile_cache
    print(f"[cache] compilation cache at {enable_compile_cache()}",
          flush=True)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
