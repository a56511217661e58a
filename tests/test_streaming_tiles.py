"""Weight-streaming tiles: the planner's rule for decode GEMMs and the
dense-grid kernels at those tiles.

A decode GEMM has at most one block of tokens on N and a weight of
megabytes, so its kernel streams the digit planes from HBM once a call.
``select_block_sizes`` gives such a weight tiles of a whole K and as many
rows as keep one grid step's digit tile within ``_STREAM_TILE_BYTES``; the
kernels must stay bit-exact at those tiles (one K step, so init and
epilogue in the same grid step; coarse plane masks; skipped expert token
blocks).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import vmem
from repro.engine import QuantSpec
from repro.kernels import bw_gemm as bw
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.obs import metrics as obs_metrics

TOKENS = 128
# (M, K) of the planned weights W^T of the two chip cells
CELL_SHAPES = {
    "minicpm.qkvo": (2304, 2304), "minicpm.gate_up": (5760, 2304),
    "minicpm.down": (2304, 5760),
    "mellum2.q": (4096, 2304), "mellum2.kv": (512, 2304),
    "mellum2.o": (2304, 4096), "mellum2.expert_gate_up": (896, 2304),
    "mellum2.expert_down": (2304, 896), "mellum2.head": (98304, 2304),
}
SPEC = QuantSpec.parse("planes=3,encoding=ent,impl=pallas_fused,"
                       "act_quant=per_token")


def _up(x, m):
    return -(-x // m) * m


def _steps(m, k, bm, bk):
    return (_up(m, bm) // bm) * (_up(k, bk) // bk)


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
def test_rule_streams_each_cell_weight(shape):
    m, k = CELL_SHAPES[shape]
    bm, bk, bn = ops.select_block_sizes(m, k, TOKENS, SPEC)
    assert (bm % 128, bk % 128, bn) == (0, 0, 128)
    # no more padding than the (128, 256) table tile: M as before, K at
    # most as far as a 256 multiple (usually only to 128)
    assert _up(m, bm) == _up(m, 128)
    assert _up(k, bk) <= _up(k, 256)
    # the double-buffered footprint fits the VMEM the kernels run with
    # (Mosaic's default scoped limit, the analysis pass's budget)
    need = vmem.vmem_footprint("dense", m, k, bn, block_m=bm, block_k=bk,
                               block_n=bn, n_planes=SPEC.num_digits)
    assert need["total"] <= vmem.DEFAULT_VMEM_BUDGET
    # tiles of 1-2.5 MiB, so a ninth of the table's grid steps or fewer
    # (a tile of 9 table tiles is the smallest the sweep would take); at
    # least _STREAM_MIN_STEPS where M has that many 128-row blocks
    tile = SPEC.num_digits * bm * bk
    assert 2 ** 20 <= tile <= ops._STREAM_TILE_BYTES
    old = _steps(m, k, 128, 256)
    new = _steps(m, k, bm, bk)
    assert new * 9 <= old
    assert new >= min(ops._STREAM_MIN_STEPS, _up(m, 128) // 128)


@pytest.mark.parametrize("m,k,n,want", [
    (512, 1920, 128, (128, 256, 128)),    # planes just under 4 MiB
    (2304, 2304, 256, (256, 512, 128)),   # more than one token block
])
def test_rule_leaves_small_or_wide_gemms_to_the_table(m, k, n, want):
    assert ops.select_block_sizes(m, k, n) == want


def test_autotune_cache_hit_wins_over_the_rule():
    from repro.kernels import autotune
    cache = autotune.AutotuneCache("mem", strict=False)
    cache.record(5760, 2304, TOKENS, SPEC,
                 {"block_m": 256, "block_k": 256, "block_n": 128,
                  "dispatch": "dense"})
    autotune.set_cache(cache)
    try:
        assert ops.select_block_sizes(5760, 2304, TOKENS, SPEC) == \
            (256, 256, 128)
    finally:
        autotune.reset_cache()
    assert ops.select_block_sizes(5760, 2304, TOKENS, SPEC) == \
        (128, 2304, 128)


def test_rule_splits_a_k_too_long_for_one_tile():
    # grok-1's down projection: K = 32768 does not fit one tile at 128
    # rows, so K splits into equal 128-multiples with no padding
    bm, bk, _ = ops.select_block_sizes(6144, 32768, TOKENS, SPEC)
    assert 32768 % bk == 0 and bk % 128 == 0
    assert SPEC.num_digits * bm * bk <= ops._STREAM_TILE_BYTES


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
def test_autotune_sweep_reaches_the_rule_tiles(shape):
    from repro.kernels import autotune
    m, k = CELL_SHAPES[shape]
    cands = autotune.candidate_configs(m, k, TOKENS)
    tiles = {(c["block_m"], c["block_k"], c["block_n"]) for c in cands}
    # the sweep measures the rule's tile: the VMEM filter keeps it
    kept, _ = vmem.filter_vmem_configs(m, k, TOKENS, cands,
                                       n_planes=SPEC.num_digits)
    assert ops.select_block_sizes(m, k, TOKENS, SPEC) in \
        {(c["block_m"], c["block_k"], c["block_n"]) for c in kept}
    assert (128, _up(k, 128), 128) in tiles            # the whole K
    assert max(bm for bm, _, _ in tiles) > 256         # past the table


def test_rule_applies_at_any_token_count_up_to_a_block():
    # planning asks with N = 128, a decode step with its batch: both get
    # the same tile and one token block
    want = ops.select_block_sizes(2304, 5760, TOKENS, SPEC)
    for n in (1, 32, 64):
        assert ops.select_block_sizes(2304, 5760, n, SPEC) == want


# ---------------------------------------------------------------------------
# the kernels at streaming tiles (interpret mode), bit-exact
# ---------------------------------------------------------------------------

def _digits(rng, lead, m, k, empty_top=True):
    d = rng.integers(-2, 3, size=lead + (4, m, k)).astype(np.int8)
    if empty_top:
        d[..., 3, :, :] = 0                 # planes=3: |q| <= 42
    return d


def _masked_ref(d, b, bm, bk):
    mask = ops.plane_block_mask(jnp.asarray(d), bm, bk)
    return kref.bw_gemm_masked_ref(jnp.asarray(d), jnp.asarray(b), mask,
                                   bm, bk), mask


@pytest.mark.parametrize("bm,bk", [(256, 768), (384, 384)],
                         ids=["whole_k", "split_k"])
@pytest.mark.parametrize("empty_top", [True, False],
                         ids=["planes3", "planes4"])
def test_fused_kernel_exact_at_streaming_tiles(bm, bk, empty_top):
    rng = np.random.default_rng(0)
    m, k = 768, 768
    d = _digits(rng, (), m, k, empty_top)
    b = rng.integers(-16, 17, size=(k, TOKENS)).astype(np.int8)
    scale = rng.uniform(0.5, 2, size=(m, 1)).astype(np.float32)
    scale_n = rng.uniform(0.5, 2, size=(1, TOKENS)).astype(np.float32)
    acc, mask = _masked_ref(d, b, bm, bk)
    assert bool(np.asarray(mask[3]).any()) is not empty_top
    got = bw.bw_gemm_fused(jnp.asarray(d), jnp.asarray(b), mask,
                           jnp.asarray(scale), None, jnp.asarray(scale_n),
                           block_m=bm, block_k=bk, block_n=TOKENS,
                           interpret=True)
    want = acc.astype(jnp.float32) * (jnp.asarray(scale)
                                      * jnp.asarray(scale_n))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bm,bk", [(256, 768), (384, 384)],
                         ids=["whole_k", "split_k"])
def test_expert_kernel_exact_at_streaming_tiles(bm, bk):
    rng = np.random.default_rng(1)
    held, m, k, n = 3, 768, 768, 2 * TOKENS
    d = _digits(rng, (held,), m, k)
    b = rng.integers(-16, 17, size=(held, k, n)).astype(np.int8)
    scale = rng.uniform(0.5, 2, size=(held, m, 1)).astype(np.float32)
    counts = np.array([5, 0, TOKENS + 3], np.int32)  # one expert unrouted
    masks, want = [], []
    for h in range(held):
        acc, mask = _masked_ref(d[h], b[h], bm, bk)
        y = np.array(acc.astype(jnp.float32) * jnp.asarray(scale[h]))
        live = -(-int(counts[h]) // TOKENS) * TOKENS   # whole token blocks
        y[:, live:] = 0.0
        masks.append(mask)
        want.append(y)
    got = bw.bw_gemm_experts(jnp.asarray(d), jnp.asarray(b),
                             jnp.stack(masks), jnp.asarray(scale),
                             jnp.asarray(counts), block_m=bm, block_k=bk,
                             block_n=TOKENS, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.stack(want))
    assert not np.asarray(got[1]).any()


def test_traced_kernels_record_their_tiles():
    rng = np.random.default_rng(2)
    d = _digits(rng, (), 512, 384)
    b = rng.integers(-16, 17, size=(384, TOKENS)).astype(np.int8)
    _, mask = _masked_ref(d, b, 256, 384)
    bw._M_TILE_BYTES.reset()
    bw._M_GRID_STEPS.reset()
    bw.bw_gemm_fused(jnp.asarray(d), jnp.asarray(b), mask,
                     jnp.ones((512, 1), jnp.float32), block_m=256,
                     block_k=384, block_n=TOKENS, interpret=True)
    snap = obs_metrics.snapshot()
    key = "k=384,kernel=bw_gemm_fused,m=512"
    assert snap["repro_bw_gemm_tile_bytes"]["values"][key] == 4 * 256 * 384
    assert snap["repro_bw_gemm_grid_steps"]["values"][key] == 2


def test_engine_serves_the_same_tokens_at_streaming_tiles():
    """A decoder wide enough for the rule (1024 x 1024 projections: 4 MiB
    of planes) serves token for token what it serves at the table's
    (128, 256) tiles, forced through the spec's block overrides."""
    from repro.configs.registry import get_config
    from repro.serving import ServeEngine, ServeRequest, Scheduler
    cfg = get_config("minicpm-2b", smoke=True).replace(
        n_layers=1, d_model=1024, n_heads=8, n_kv_heads=8, head_dim=128,
        d_ff=1024)
    assert ops.select_block_sizes(1024, 1024, TOKENS, SPEC)[:2] != \
        (128, 256)

    def serve(spec):
        eng = ServeEngine(cfg, 2, 12, seed=3, quant=spec)
        sched = Scheduler("fcfs", max_len=12)
        for rid, (p, n) in enumerate([(3, 4), (2, 5), (4, 3)]):
            sched.submit(ServeRequest(rid, [1 + 5 * rid + j for j in
                                            range(p)], n), 0.0)
        done = []
        while eng.has_work(sched):
            eng.admit_from(sched, 0.0)
            done.extend(eng.step())
        return {r.rid: list(r.out) for r in done}

    table = QuantSpec.parse("planes=3,encoding=ent,impl=pallas_fused,"
                            "act_quant=per_token,block_m=128,block_k=256")
    bw._M_TILE_BYTES.reset()           # read only what this engine traced
    got = serve(SPEC)
    tiles = obs_metrics.snapshot()["repro_bw_gemm_tile_bytes"]["values"]
    assert tiles["k=1024,kernel=bw_gemm_fused,m=1024"] == \
        SPEC.num_digits * 256 * 1024
    assert got == serve(table)
    assert sorted(got) == [0, 1, 2]
