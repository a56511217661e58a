"""The paper's technique as a TPU compute feature, end to end:

  1. quantize a weight matrix with plane-bounded symmetric quantization
     (repro.core.quant) — planes p makes EN-T digit planes >= p
     structurally empty;
  2. plan the operand (encode + magnitude-ordered row packing);
  3. run bw_gemm with per-(plane, block) MXU-pass skipping;
  4. report the kept-pass fraction vs the paper's Table III prediction
     (avg 2.2/4 non-zero digits) and the accuracy cost.

Run:  PYTHONPATH=src python examples/bw_quantized_gemm.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import quant
from repro.core.sparsity import avg_num_pps
from repro.kernels import bw_gemm as bwk, ops

rng = np.random.default_rng(0)

# the paper's test distribution: normally-distributed operands
w = (rng.standard_normal((1024, 512)) * 0.02).astype(np.float32)
x = (rng.standard_normal((512, 256)) / 23.0).astype(np.float32)

print(f"{'planes':>6} {'qmax':>5} {'kept MXU passes':>16} "
      f"{'avg NumPPs':>11} {'rel err':>9} {'sched steps':>12} "
      f"{'DMA vs dense':>13}")
want = w @ x
for planes in (4, 3, 2):
    qw, sw = quant.quantize_to_planes(jnp.asarray(w), planes)
    qx, sx = quant.quantize_to_planes(jnp.asarray(x), 4)
    planned = ops.plan_operand(np.asarray(qw), block_m=128, block_k=128)
    blocks = dict(block_m=128, block_k=128, interpret=True)
    # kernel rows come out in the plan's (magnitude-sorted) order
    acc = np.asarray(bwk.bw_gemm(planned.digits, qx, planned.mask,
                                 **blocks))[planned.inv_perm]
    # the compacted sparse schedule elides the skipped blocks' DMA too
    acc_sparse = np.asarray(bwk.bw_gemm_sparse(
        planned.digits, qx, jnp.asarray(planned.schedule),
        **blocks))[planned.inv_perm]
    assert (acc_sparse == acc).all()       # bit-identical dispatch
    got = acc.astype(np.float32) * float(sw) * float(sx)
    rel = np.abs(got - want).mean() / np.abs(want).mean()
    kept = float(np.asarray(planned.mask).mean())
    pps = avg_num_pps(np.asarray(qw).astype(np.int64), "ent")
    st = ops.schedule_stats(planned.schedule, planned.mask)
    # digit bytes the sparse schedule moves vs the dense kernel's
    # all-planes-every-block BlockSpec
    dma_ratio = st["steps"] / st["total_blocks"]
    print(f"{planes:>6} {quant.plane_qmax(planes):>5} {kept:>15.0%} "
          f"{pps:>11.2f} {rel:>9.4f} {st['steps']:>12} {dma_ratio:>12.0%}")

print("\nplanes=4: every block has some high-plane digit (element sparsity"
      " != block sparsity);\nplanes<=3 makes the top planes structurally "
      "empty -> guaranteed 25%/50% MXU-pass skips.")

print("\npaper Table III: EN-T averages 2.2-2.3 non-zero digit planes of 4 "
      "on normal data;\nplane-bounding turns that statistical sparsity into "
      "structural (guaranteed) block skips.")
