"""End-to-end trainer.

The same loop drives CPU smoke runs (mesh 1x1) and pod-scale runs (mesh
16x16 / 2x16x16) — only the mesh shape and batch change.  Demonstrates the
full production path: deterministic data pipeline -> pjit'd train step
(optionally microbatched + int8-compressed DP grads + the paper's
quantized BW-GEMM path) -> heartbeat/straggler monitor -> atomic
checkpoints -> resume.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 40 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --smoke \
        --steps 20 --quant-planes 3 --grad-compress
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS, get_config
from repro.launch import mesh as meshlib
from repro.parallel import sharding as sh
from repro.train import checkpoint as ckpt
from repro.train import data as datalib
from repro.train import fault
from repro.train import optimizer as opt
from repro.train import steps as st

__all__ = ["train", "main"]


def train(arch: str, *, smoke: bool = True, steps: int = 50,
          global_batch: int = 8, seq_len: int = 128,
          mesh_shape=(1, 1), lr: float = 3e-4, schedule: str = "cosine",
          quant_planes: int = 0, quant_spec=None,
          grad_compress: bool = False,
          microbatches: int = 1, ckpt_dir: str | None = None,
          ckpt_every: int = 20, resume: bool = False, seed: int = 0,
          log_every: int = 10, overrides: dict | None = None) -> dict:
    from repro.engine import QuantSpec, spec_from_flags
    cfg = get_config(arch, smoke=smoke, **(overrides or {}))
    # resolve the quantized-GEMM spec eagerly: the jit'd step closes over
    # it via cfg (quant_spec may be a QuantSpec or a CLI "k=v,..." string;
    # quant_planes alone is sugar for the trainable jnp oracle engine)
    if not isinstance(quant_spec, QuantSpec):
        quant_spec = spec_from_flags(quant_spec, quant_planes,
                                     quant_impl="planes")
    if quant_spec is not None:
        cfg = cfg.replace(quant=quant_spec, quant_planes=quant_spec.planes)
    ocfg = opt.OptConfig(peak_lr=lr, total_steps=steps,
                         warmup_steps=max(steps // 10, 1),
                         schedule=schedule,
                         moment_dtype=cfg.opt_state_dtype)
    mesh = meshlib.make_mesh(mesh_shape, ("data", "model"))
    rules = sh.default_rules(
        fsdp=cfg.fsdp and mesh.shape["data"] > 1,
        shard_kv_heads=cfg.n_kv_heads >= mesh.shape["model"])

    dcfg = datalib.DataConfig(
        vocab_size=cfg.vocab_size, global_batch=global_batch,
        seq_len=seq_len, seed=seed,
        frontend_tokens=cfg.frontend_tokens if cfg.frontend else 0,
        d_model=cfg.d_model)
    stream = datalib.SyntheticStream(dcfg)

    with sh.mesh_context(mesh, rules):
        state = st.init_train_state(jax.random.PRNGKey(seed), cfg, ocfg,
                                    grad_compress)
        start = 0
        if resume and ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
            (state, data_state), manifest = ckpt.restore_checkpoint(
                ckpt_dir, (state, stream.state_dict()))
            stream = datalib.SyntheticStream.from_state(dcfg, data_state)
            start = int(manifest["meta"]["train_step"])
            print(f"[train] resumed from step {start}")

        step_fn = jax.jit(st.make_train_step(
            cfg, ocfg, grad_compress=grad_compress,
            microbatches=microbatches), donate_argnums=(0,))

        mon = fault.HeartbeatMonitor(["host0"])
        losses = []
        for i in range(start, steps):
            batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            mon.record("host0", i, dt)
            losses.append(loss)
            if i % log_every == 0 or i == steps - 1:
                print(f"[train] step {i:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt * 1e3:.0f} ms")
            if ckpt_dir and (i + 1) % ckpt_every == 0:
                path = ckpt.save_checkpoint(
                    ckpt_dir, i + 1, (state, stream.state_dict()),
                    meta={"train_step": i + 1, "arch": arch,
                          "mesh": list(mesh_shape)})
                print(f"[train] checkpoint -> {path}")
        rep = mon.report()
        return {"arch": arch, "steps": steps, "final_loss": losses[-1],
                "first_loss": losses[0], "losses": losses,
                "median_step_s": rep.fleet_median_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", choices=["cosine", "wsd", "constant"],
                    default="cosine")
    ap.add_argument("--quant-planes", type=int, default=0)
    ap.add_argument("--quant-spec", default=None,
                    help="full quantized-GEMM spec, e.g. "
                         "'planes=3,encoding=ent,impl=planes'")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                global_batch=args.batch, seq_len=args.seq, lr=args.lr,
                schedule=args.schedule, quant_planes=args.quant_planes,
                quant_spec=args.quant_spec,
                grad_compress=args.grad_compress,
                microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, resume=args.resume,
                seed=args.seed)
    print(json.dumps({k: v for k, v in out.items() if k != "losses"},
                     indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
