"""Quickstart: the paper's pipeline end-to-end in ~60 seconds on CPU.

1. Encode operands in the bit-weight dimension (EN-T / MBE).
2. Execute the paper's OPT schedules bit-exactly through the notation.
3. Price the implied hardware with the SMIC-28nm model (Table VII).
4. Run the TPU-native Pallas kernel (interpret mode) with digit-plane
   block skipping.
5. Train a tiny LM with the quantized BW-GEMM path enabled.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

# --- 1. encodings -----------------------------------------------------------
from repro.core import encodings as enc

x = np.asarray([91, 124, -77])
print("EN-T digits (LSB first):")
for v in x:
    print(f"  {v:5d} -> {enc.encode_np(v, 'ent').tolist()}  "
          f"(NumPPs={int(enc.num_pps_np(v, 'ent'))})")

# --- 2. executable notation --------------------------------------------------
from repro.core import notation as nt
from repro.core.sparsity import quantize_normal_matrix

a = quantize_normal_matrix(1.0, (16, 64), seed=0)
b = np.random.default_rng(0).integers(-128, 128, (64, 8)).astype(np.int64)
print("\nSchedules (all bit-exact vs A@B):")
for name in ("baseline", "opt1", "opt2", "opt3", "opt4e"):
    r = nt.execute(nt.SCHEDULES[name], a, b, nt.ArrayGeometry(16, 8, 4))
    assert (r.c == a @ b).all()
    print(f"  {name:9s} cycles={r.cycles:5d}  "
          f"PPs={r.pp_processed}/{r.pp_total}  util={r.utilization:.2f}")

# --- 3. hardware model --------------------------------------------------------
from repro.core import hwmodel as hw

print("\nTable VII efficiency ratios (ours vs published baselines):")
for k, v in hw.efficiency_ratios().items():
    print(f"  {k:15s} area x{v['area_eff']:.2f}  energy x{v['energy_eff']:.2f}")

# --- 4. Pallas kernel ---------------------------------------------------------
import jax.numpy as jnp
from repro.kernels import bw_gemm as bwk, ops

aw = (np.random.default_rng(1).standard_t(4, (256, 256)) * 12) \
    .clip(-128, 127).astype(np.int8)
bw = np.random.default_rng(2).integers(-128, 128, (256, 128)).astype(np.int8)
planned = ops.plan_operand(aw)          # rows sorted by high-plane use
out = np.asarray(bwk.bw_gemm(planned.digits, jnp.asarray(bw), planned.mask,
                             block_m=planned.block_m,
                             block_k=planned.block_k, interpret=True))
out = out[planned.inv_perm]             # back to the operand's row order
want = (aw.astype(np.int64) @ bw.astype(np.int64)).astype(np.int32)
print(f"\nbw_gemm kernel exact: {(out == want).all()}  "
      f"MXU passes kept: {float(np.asarray(planned.mask).mean()):.0%}")

# --- 5. tiny quantized training ------------------------------------------------
from repro.launch.train import train

res = train("minicpm-2b", smoke=True, steps=15, global_batch=4, seq_len=32,
            lr=3e-3, quant_planes=3, log_every=5)
print(f"\nquantized-path training: loss {res['first_loss']:.3f} -> "
      f"{res['final_loss']:.3f}")
print("done.")
