"""Cross-device execution parity for the sharded planned GEMM.

``sharded_planned_apply`` (shard_map over a forced 8-device host mesh,
per-shard compacted schedules, psum / psum_scatter over the 'data' axis)
must match the single-device ``planned_dense_apply`` reference bit-for-
tolerance on every mesh shape, schedule order and plane budget.  Runs in
a subprocess so the forced device count binds before jax initializes and
the main test process keeps its single-device view.

Deliberately NOT slow-marked: this is the PR's core acceptance property.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import itertools

    import jax, jax.numpy as jnp
    import numpy as np

    from repro.engine import QuantSpec
    from repro.kernels import ops
    from repro.parallel.apply import make_gemm_mesh, sharded_planned_apply
    from repro.parallel.plan import plan_sharded_weight

    assert len(jax.devices()) == 8, jax.devices()

    M = K = 512
    BATCH = 16
    rng = np.random.default_rng(0)
    w = (rng.standard_t(4, size=(K, M)) * 0.02).astype(np.float32)
    x = rng.normal(0, 1, size=(BATCH, K)).astype(np.float32)
    bias = jnp.asarray(rng.normal(0, 0.1, size=(M,)).astype(np.float32))

    n_ok = 0
    cases = itertools.product((2, 3), ("m_major", "k_major"),
                              ((2, 4), (4, 2)))
    for planes, order, shards in cases:
        spec = QuantSpec(planes=planes, block_m=128, block_k=128,
                         act_quant="per_token")
        plan = ops.plan_dense_weight(w, spec, order=order)
        want = np.asarray(ops.planned_dense_apply(
            plan, jnp.asarray(x), spec, M, bias=bias, activation="silu",
            fused=False, dispatch="auto", order=order))

        splan = plan_sharded_weight(w, spec, shards, order=order)
        mesh = make_gemm_mesh(shards)
        # alternate explicit reduce modes so both collectives are covered
        reduce = "psum_scatter" if n_ok % 2 else "psum"
        got = np.asarray(sharded_planned_apply(
            splan, jnp.asarray(x), spec, M, bias=bias, activation="silu",
            dispatch="auto", mesh=mesh, reduce=reduce))

        err = float(np.abs(got - want).max())
        assert got.shape == want.shape, (got.shape, want.shape)
        assert np.allclose(got, want, rtol=1e-6, atol=1e-6), (
            planes, order, shards, reduce, err)
        print("PARITY_OK", planes, order, shards, reduce, err)
        n_ok += 1

    # 'model'-only mesh: no K split, no reduce traffic, still exact
    spec = QuantSpec(planes=3, block_m=128, block_k=128,
                     act_quant="per_token")
    plan = ops.plan_dense_weight(w, spec)
    want = np.asarray(ops.planned_dense_apply(
        plan, jnp.asarray(x), spec, M, fused=False, dispatch="auto"))
    splan = plan_sharded_weight(w, spec, (1, 8))
    got = np.asarray(sharded_planned_apply(
        splan, jnp.asarray(x), spec, M, mesh=make_gemm_mesh((1, 8))))
    assert np.allclose(got, want, rtol=1e-6, atol=1e-6)
    print("PARITY_OK", 3, "m_major", (1, 8), "none",
          float(np.abs(got - want).max()))
    n_ok += 1

    print("ALL_OK", n_ok)
""")


def test_sharded_apply_parity_all_meshes(tmp_path):
    script = tmp_path / "sharded_apply.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "ALL_OK 9" in r.stdout, r.stdout
    assert r.stdout.count("PARITY_OK") == 9, r.stdout


# ---------------------------------------------------------------------------
# the weight-streaming tiles of ``ops.select_block_sizes`` on a sharded plan
# ---------------------------------------------------------------------------

STREAM_SPEC = "planes=3,encoding=ent,impl=pallas_fused,act_quant=per_token"


def _up(x, m):
    return -(-x // m) * m


@pytest.mark.parametrize("shards", [(2, 2), (4, 1), (1, 4), (4, 2)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_streaming_tiles_split_over_the_shard_grid(shards):
    """minicpm-2b's MLP weight (the four-chip check of ``chip_smoke.py``,
    53 MB of planes) planned for a mesh: the rule's tiles pad K and M no
    further than the (128, 256) table tile under the same shard grid,
    and every shard holds live plane blocks, so splitting K over 'data'
    still splits the work."""
    from repro.engine import QuantSpec
    from repro.kernels import ops
    from repro.parallel.plan import plan_sharded_weight
    spec = QuantSpec.parse(STREAM_SPEC)
    k, n = 2304, 5760
    w = (np.random.default_rng(0).standard_t(4, size=(k, n)) * 0.02
         ).astype(np.float32)
    s_data, s_model = shards
    splan = plan_sharded_weight(w, spec, shards)
    assert (splan.block_m, splan.block_k) == \
        ops.select_block_sizes(n, k, 128, spec, shards)[:2]
    assert (splan.block_m, splan.block_k) != (128, 256)   # the rule's
    _, mb, kb = np.asarray(splan.plan["mask"]).shape
    assert kb * splan.block_k <= _up(k, 256 * s_data)
    assert mb * splan.block_m <= _up(n, 128 * s_model)
    for i in range(s_model):
        for j in range(s_data):
            assert splan.shard_mask(i, j).any(), (i, j)


_STREAM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    import jax, jax.numpy as jnp
    import numpy as np

    from repro.engine import QuantSpec
    from repro.kernels import ops
    from repro.parallel.apply import make_gemm_mesh, sharded_planned_apply
    from repro.parallel.plan import plan_sharded_weight

    K, M, BATCH = 2304, 1024, 16       # 9 MB of planes: the rule's tiles
    spec = QuantSpec.parse("%s")
    rng = np.random.default_rng(1)
    w = (rng.standard_t(4, size=(K, M)) * 0.02).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, size=(BATCH, K)).astype(np.float32))
    plan = ops.plan_dense_weight(w, spec)
    want = np.asarray(ops.planned_dense_apply(
        plan, x, spec, M, activation="silu", fused=False, dispatch="auto"))
    for shards, reduce in (((2, 2), "psum"), ((4, 2), "psum_scatter")):
        splan = plan_sharded_weight(w, spec, shards)
        assert (splan.block_m, splan.block_k) == \\
            ops.select_block_sizes(M, K, 128, spec, shards)[:2]
        got = np.asarray(sharded_planned_apply(
            splan, x, spec, M, activation="silu", dispatch="auto",
            mesh=make_gemm_mesh(shards), reduce=reduce))
        assert np.allclose(got, want, rtol=1e-6, atol=1e-6), (
            shards, float(np.abs(got - want).max()))
        print("PARITY_OK", shards, splan.block_m, splan.block_k)
""" % STREAM_SPEC)


def test_sharded_apply_parity_at_streaming_tiles(tmp_path):
    script = tmp_path / "sharded_stream.py"
    script.write_text(_STREAM_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert r.stdout.count("PARITY_OK") == 2, r.stdout
