"""Batched serving example: prefill a batch of prompts, then decode with
greedy sampling over the KV cache / recurrent state — the serve_step that
the decode_32k / long_500k dry-run cells lower, at CPU smoke scale.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch granite-34b
      PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-3b --tokens 48
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_config
from repro.engine import get_engine
from repro.models.api import get_api
from repro.parallel.sharding import unbox
from repro.train.steps import make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="granite-34b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--quant-spec", default=None,
                    help="serve quantized, e.g. "
                         "'planes=3,encoding=ent,impl=pallas_fused'")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    spec = None
    if args.quant_spec:
        from repro.engine import QuantSpec
        spec = QuantSpec.parse(args.quant_spec)
        cfg = cfg.replace(quant=spec,
                          quant_planes=spec.planes if spec else 0)
        print(f"quant spec: {spec}")
    api = get_api(cfg)
    params = unbox(api.init(jax.random.PRNGKey(0), cfg))
    gemm = get_engine(spec.impl) if spec is not None else None
    if gemm is not None and gemm.uses_plans:
        # pre-plan the dense weights so the jit'd serve step runs the
        # Pallas kernel (instead of its int8-dot cost lowering)
        from repro.kernels import ops
        params, planned = ops.plan_params(params, spec, order=gemm.order)
        print(f"pre-planned {planned} dense weights for the kernel path")
    b = args.batch
    max_len = args.prompt_len + args.tokens + 1

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (b, args.prompt_len)), jnp.int32)

    # teacher-forced prefill through the decode path (exercise the cache)
    state = unbox(api.init_decode(cfg, b, max_len))
    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=(3,))
    t0 = time.time()
    nxt = prompts[:, :1]
    for i in range(args.prompt_len):
        tok = prompts[:, i:i + 1]
        nxt, state = serve_step(params, tok,
                                jnp.full((b,), i, jnp.int32), state)
    t_prefill = time.time() - t0

    # greedy generation
    out = [nxt]
    t0 = time.time()
    for i in range(args.prompt_len, args.prompt_len + args.tokens):
        nxt, state = serve_step(params, nxt,
                                jnp.full((b,), i, jnp.int32), state)
        out.append(nxt)
    t_decode = time.time() - t0
    gen = np.concatenate([np.asarray(t) for t in out], axis=1)

    tps = b * args.tokens / max(t_decode, 1e-9)
    print(f"arch={args.arch} batch={b}")
    print(f"prefill ({args.prompt_len} teacher-forced steps): "
          f"{t_prefill:.2f}s")
    print(f"decode  ({args.tokens} tokens x {b} seqs): {t_decode:.2f}s "
          f"= {tps:.1f} tok/s on CPU smoke")
    print(f"sample generations (token ids):\n{gen[:, :12]}")


if __name__ == "__main__":
    main()
