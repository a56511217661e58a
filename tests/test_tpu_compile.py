"""The serving kernels compile for a TPU v5e at minicpm-2b's widths.

Interpret mode runs any kernel the Mosaic compiler would refuse (int32
MXU operands, blocks the tiling does not allow, scalars read from VMEM),
so these tests compile the kernels of every GEMM route ahead of time for
a described v5e chip: the chip's compiler is installed here, the chip is
not needed.  Shapes are the decode shapes of minicpm-2b's MLP (weight
channels on the kernel M axis, 128 padded tokens on N) with the block
sizes the planner picks for them.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import QuantSpec
from repro.kernels import bw_gemm as bw
from repro.kernels import ops

SPEC = QuantSpec.parse("planes=3,encoding=ent,impl=pallas_fused,"
                       "act_quant=per_token")
N_TOKENS = 128
# (M, K) of the planned weight W^T: up/gate [d_ff, d_model] and
# down [d_model, d_ff]
SHAPES = ((5760, 2304), (2304, 5760))


@pytest.fixture(scope="module")
def one_chip():
    # the topology is described here, never at import: loading the TPU
    # library takes a process-wide lock that other test workers need
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _operands(one_chip, m, k):
    bm, bk, bn = ops.select_block_sizes(m, k, N_TOKENS, SPEC)
    m_pad, k_pad = -(-m // bm) * bm, -(-k // bk) * bk
    mb, kb = m_pad // bm, k_pad // bk
    bw_n = SPEC.num_digits

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return dict(
        blocks=dict(block_m=bm, block_k=bk, block_n=bn),
        digits=s((bw_n, m_pad, k_pad), jnp.int8),
        b=s((k_pad, N_TOKENS), jnp.int8),
        mask=s((bw_n, mb, kb), jnp.bool_),
        # worst case: every plane-block scheduled
        schedule=s((bw_n * mb * kb, len(bw.SCHED_COLS)), jnp.int32),
        scale=s((m_pad, 1), jnp.float32),
        scale_n=s((1, N_TOKENS), jnp.float32))


KERNELS = {
    "bw_gemm_fused": lambda o: (
        lambda d, b, mask, sc, sn: bw.bw_gemm_fused(
            d, b, mask, sc, None, sn, activation="silu", **o["blocks"]),
        ("digits", "b", "mask", "scale", "scale_n")),
    "bw_gemm_sparse_fused": lambda o: (
        lambda d, b, sched, sc, sn: bw.bw_gemm_sparse_fused(
            d, b, sched, sc, None, sn, activation="silu", **o["blocks"]),
        ("digits", "b", "schedule", "scale", "scale_n")),
    "bw_gemm_sparse_fused_pipelined": lambda o: (
        lambda d, b, sched, sc, sn: bw.bw_gemm_sparse_fused_pipelined(
            d, b, sched, sc, None, sn, activation="silu", **o["blocks"]),
        ("digits", "b", "schedule", "scale", "scale_n")),
}


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, shape):
    ops_ = _operands(one_chip, *shape)
    fn, names = KERNELS[kernel](ops_)
    compiled = jax.jit(fn).lower(*(ops_[n] for n in names)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the digit planes are the dominant operand: 4 int8 planes per weight
    assert mem.argument_size_in_bytes >= \
        SPEC.num_digits * shape[0] * shape[1]


# (M, K) of Mellum2's expert weights W^T: gate/up [896, 2304] and down
# [2304, 896], eight held experts, 64 tokens padded to 128
EXPERT_SHAPES = ((896, 2304), (2304, 896))


@pytest.mark.parametrize("shape", EXPERT_SHAPES,
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_expert_kernel_compiles_for_v5e(one_chip, shape):
    """The grouped expert kernel (the expert a grid axis) compiles, and
    its custom call carries the name the device trace matches."""
    import re
    ops_ = _operands(one_chip, *shape)
    held = 8

    def s(x, lead):
        return jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                    sharding=one_chip)
    args = [s(ops_[n], (held,)) for n in ("digits", "b", "mask", "scale",
                                          "scale_n")]
    counts = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)

    def fn(d, b, mask, sc, sn, cnt):
        return bw.bw_gemm_experts(d, b, mask, sc, cnt, sn, activation=None,
                                  **ops_["blocks"])
    compiled = jax.jit(fn).lower(*args, counts).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"\bbw_gemm_experts(\.\d+)? = ", text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= \
        held * SPEC.num_digits * shape[0] * shape[1]


@pytest.mark.parametrize("quant", [None, SPEC], ids=["bf16", "pallas_fused"])
def test_decode_step_updates_kv_cache_in_place(one_chip, monkeypatch, quant):
    """The decode step, compiled with its state donated, updates the KV
    cache in place: its output aliases the whole state, and no copy or
    temporary holds the stacked cache.  minicpm-2b's 64-wide heads and the
    chip cell's 32 slots x 640 positions, at 2 layers of d_model 256 (the
    layout the compiler picks for the cache follows the column updates,
    and the kernel path writes them batch-minor)."""
    import re
    from repro.configs.registry import get_config
    from repro.models.api import get_api
    from repro.parallel.sharding import unbox
    from repro.train.steps import make_serve_step
    cfg = get_config("minicpm-2b").replace(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
        vocab_size=1024, quant=quant,
        quant_planes=quant.planes if quant else 0)
    api = get_api(cfg)
    params = unbox(api.init(jax.random.PRNGKey(0), cfg))
    if quant is not None:
        params, _ = ops.plan_params(params, quant)
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def s(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    b, max_len = 32, 640
    state = jax.eval_shape(lambda: unbox(api.init_decode(cfg, b, max_len)))
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(3,)).lower(
        s(params), s(jax.ShapeDtypeStruct((b, 1), jnp.int32)),
        s(jax.ShapeDtypeStruct((b,), jnp.int32)), s(state)).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < state_bytes / 8
    assert not re.search(r"= bf16\[2,32,[\d,]+,640\]\S* copy\(",
                         compiled.as_text())
