"""Measured autotuner for the bw_gemm kernel path.

``select_block_sizes``' static dispatch table guesses block shapes from
(M, K, N) thresholds; this module replaces guessing with measurement, in
the spirit of the AWQ kernel work's measured-autotune discipline: sweep
``(block_m, block_k, block_n, dispatch, schedule order, pipelined)``
candidates on the real kernels (interpret mode off-TPU, compiled on
TPU), time them, and persist the winners to a JSON cache keyed by

    (M, K, N) x spec.plan_key() x measuring backend x density-bucket

Every key (and entry) carries the **measuring backend** — ``interpret``
off-TPU, the platform string (e.g. ``tpu``) on real hardware — so one
cache file can hold interpret-mode CI winners *and* TPU-measured winners
side by side: lookups only ever see entries measured on the backend they
will run on, and a TPU tuning run appends to the same file the CI lane
validates.  Entries without a backend tag fail validation (and loading —
the cache format version was bumped when tags landed).

The cache then *backs* the two dispatch seams of the execution path:

- ``ops.select_block_sizes`` consults the shape-level entry for tuned
  block sizes and falls back to the static table on a miss (with an
  ``AutotuneCacheMissWarning`` when the cache was explicitly configured
  through ``REPRO_AUTOTUNE_CACHE`` — never a crash);
- ``ops.planned_dense_apply``'s ``dispatch='auto'`` consults the
  density-bucket entry for a measured sparse/dense winner and falls back
  to the ``SPARSE_DENSITY_THRESHOLD`` heuristic.

A default cache covering the CI benchmark shapes is checked in next to
this module (``autotune_cache.json``); point ``REPRO_AUTOTUNE_CACHE`` at
a different file to use (and strictly expect) your own tuning run, or at
an empty path to tune from scratch.  Regenerate with::

    PYTHONPATH=src python -m repro.kernels.autotune --sweep

and validate (the CI autotune-cache lane) with::

    PYTHONPATH=src python -m repro.kernels.autotune --validate
"""
from __future__ import annotations

import argparse
import json
import os
import time
import warnings
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro import chaos as _chaos
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.calibrate import get_calibrator

__all__ = ["AutotuneCache", "AutotuneCacheMissWarning", "get_cache",
           "set_cache", "reset_cache", "cache_key", "density_bucket",
           "candidate_configs", "autotune_gemm", "current_backend",
           "CI_SHAPES", "DEFAULT_CACHE_PATH", "ENV_VAR"]

ENV_VAR = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(__file__),
                                  "autotune_cache.json")
# v2: backend-tagged keys/entries + (order, pipelined) config knobs
CACHE_FORMAT_VERSION = 2

# Upper edges of the plane-block density buckets a measurement is filed
# under (density = nnz plane-blocks / total plane-blocks of the plan).
DENSITY_BUCKETS = (0.1, 0.25, 0.5, 0.75, 1.0)

# (M, K, N) GEMM shapes the CI benchmark lanes exercise (M = kernel rows =
# output channels of the planned weight; N = tokens).  The checked-in
# cache must cover these — `--validate` (the CI autotune-cache lane)
# asserts it.
CI_SHAPES = (
    (256, 256, 128),    # kernel.bw_gemm_sparse density sweep
    (192, 256, 128),    # kernel.bw_gemm_fused / quantized_dense plan
)


class AutotuneCacheMissWarning(UserWarning):
    """An explicitly configured autotune cache had no entry for a shape;
    the static block-size table was used instead."""


# pre-bound obs counters (see repro.obs.metrics.GLOSSARY)
_M_HITS = obs_metrics.get_registry().counter(
    "repro_autotune_cache_hits_total")
_M_MISSES = obs_metrics.get_registry().counter(
    "repro_autotune_cache_misses_total")
_M_MISS_WARNINGS = obs_metrics.get_registry().counter(
    "repro_autotune_miss_warnings_total")
_M_VMEM_REJECTED = obs_metrics.get_registry().counter(
    "repro_autotune_vmem_rejected_total")
_M_LOAD_ERRORS = obs_metrics.get_registry().counter(
    "repro_autotune_cache_load_errors_total")

def current_backend() -> str:
    """The measuring-backend tag for this process.

    ``interpret`` anywhere the kernels run in interpret mode (any non-TPU
    backend: interpret timings rank scheduled *work*, not MXU wall time),
    else the platform string so distinct TPU generations could in
    principle carry distinct entries.
    """
    import jax
    backend = jax.default_backend()
    return backend if backend == "tpu" else "interpret"


def density_bucket(density: float) -> float:
    """File a measured plane-block density under its bucket's upper edge."""
    for edge in DENSITY_BUCKETS:
        if density <= edge:
            return edge
    return DENSITY_BUCKETS[-1]


def _plan_part(spec=None) -> str:
    if spec is None:
        return "default"
    planes, encoding, bits, bm, bk = spec.plan_key()
    part = f"p{planes}.{encoding}{bits}"
    if bm or bk:
        part += f".bm{bm}.bk{bk}"
    return part


def cache_key(m: int, k: int, n: int, spec=None,
              density: Optional[float] = None,
              backend: Optional[str] = None) -> str:
    """Cache key: shape x spec plan fields x measuring backend x optional
    density bucket.  backend=None uses this process's backend tag."""
    key = f"{m}x{k}x{n}|{_plan_part(spec)}|{backend or current_backend()}"
    if density is not None:
        key += f"|d{density_bucket(float(density))}"
    return key


class AutotuneCache:
    """JSON-backed winner store for the measured block-size sweep.

    strict=True (the cache path came from ``REPRO_AUTOTUNE_CACHE``) warns
    once per key on a lookup miss; the implicit default cache stays quiet
    so untuned shapes fall back to the static table silently.
    """

    def __init__(self, path: Optional[str] = None, strict: bool = False):
        self.path = path
        self.strict = strict
        self.entries: Dict[str, dict] = {}
        self._warned: set = set()
        self.hits = 0
        self.misses = 0

    def stats(self) -> dict:
        return {"entries": len(self.entries), "hits": self.hits,
                "misses": self.misses}

    @classmethod
    def load(cls, path: str, strict: bool = False,
             on_error: str = "raise") -> "AutotuneCache":
        """Load a cache file.

        on_error="raise" (default) propagates parse/validation errors —
        the ``--validate`` CI lane depends on a corrupt cache *failing*.
        on_error="fallback" — the runtime dispatch-seam policy
        (``get_cache``) — turns a corrupt, truncated, or partially
        written file into an *empty* cache: one
        ``AutotuneCacheMissWarning`` plus the
        ``repro_autotune_cache_load_errors_total`` counter, and every
        lookup falls back to the static block-size table.  A bad cache
        file must never take serving down.
        """
        if on_error not in ("raise", "fallback"):
            raise ValueError(f"on_error must be 'raise' or 'fallback', "
                             f"got {on_error!r}")
        cache = cls(path, strict=strict)
        if not path or not os.path.exists(path):
            return cache
        try:
            with open(path) as f:
                text = f.read()
            if _chaos.enabled():
                text = _chaos.corrupt_if_due("autotune.load", text)
            payload = json.loads(text)
            version = payload.get("version")
            if version != CACHE_FORMAT_VERSION:
                raise ValueError(
                    f"autotune cache {path!r} has format version "
                    f"{version!r}; this build reads {CACHE_FORMAT_VERSION}")
            entries = payload.get("entries", {})
            for key, entry in entries.items():
                cache._check_entry(key, entry)
        except (ValueError, OSError, AttributeError) as e:
            # json.JSONDecodeError is a ValueError subclass
            if on_error == "raise":
                raise
            _M_LOAD_ERRORS.inc()
            warnings.warn(
                f"autotune cache {path!r} failed to load ({e}); using "
                f"the static block-size table instead",
                AutotuneCacheMissWarning, stacklevel=2)
            return cls(path, strict=strict)
        cache.entries = dict(entries)
        return cache

    @staticmethod
    def _check_entry(key: str, entry: dict) -> None:
        for field in ("block_m", "block_k", "block_n"):
            v = entry.get(field)
            if not isinstance(v, int) or v <= 0 or v % 128:
                raise ValueError(
                    f"autotune cache entry {key!r}: {field}={v!r} is not a "
                    f"positive multiple of 128")
        if entry.get("dispatch") not in (None, "sparse", "dense",
                                         "pipelined"):
            raise ValueError(f"autotune cache entry {key!r}: bad dispatch "
                             f"{entry.get('dispatch')!r}")
        if entry.get("order") not in (None, "m_major", "k_major"):
            raise ValueError(f"autotune cache entry {key!r}: bad order "
                             f"{entry.get('order')!r}")
        backend = entry.get("backend")
        if not isinstance(backend, str) or not backend:
            raise ValueError(
                f"autotune cache entry {key!r} is missing its measuring-"
                f"backend tag (re-measure with --sweep; one cache file "
                f"carries interpret and TPU entries side by side)")

    def lookup(self, m: int, k: int, n: int, spec=None,
               density: Optional[float] = None) -> Optional[dict]:
        """Best entry for a GEMM *measured on this backend*: the
        density-bucket key when a density is given (falling back to the
        shape-level key), else the shape key."""
        keys = []
        if density is not None:
            keys.append(cache_key(m, k, n, spec, density))
        keys.append(cache_key(m, k, n, spec))
        for key in keys:
            hit = self.entries.get(key)
            if hit is not None:
                self.hits += 1
                _M_HITS.inc()
                return hit
        self.misses += 1
        _M_MISSES.inc()
        if self.strict and self.entries and keys[-1] not in self._warned:
            self._warned.add(keys[-1])
            _M_MISS_WARNINGS.inc()
            warnings.warn(
                f"autotune cache {self.path!r} has no entry for "
                f"{keys[-1]!r}; falling back to the static block table",
                AutotuneCacheMissWarning, stacklevel=3)
        return None

    def record(self, m: int, k: int, n: int, spec, config: dict,
               density: Optional[float] = None,
               backend: Optional[str] = None) -> None:
        backend = backend or current_backend()
        config = dict(config, backend=config.get("backend") or backend)
        self.entries[cache_key(m, k, n, spec, backend=backend)] = \
            dict(config)
        if density is not None:
            self.entries[cache_key(m, k, n, spec, density,
                                   backend=backend)] = dict(config)

    def coverage(self, shapes: Iterable[Tuple[int, int, int]], spec=None,
                 backend: Optional[str] = None) -> \
            List[Tuple[int, int, int]]:
        """Shapes with no shape-level entry for ``backend`` (CI check)."""
        return [s for s in shapes
                if cache_key(*s, spec=spec, backend=backend)
                not in self.entries]

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("no cache path to save to")
        payload = {"version": CACHE_FORMAT_VERSION,
                   "entries": {k: self.entries[k]
                               for k in sorted(self.entries)}}
        # write-then-rename: a reader (or a crash) mid-save sees either
        # the old complete file or the new complete file, never a torn one
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path


_CACHE: Optional[AutotuneCache] = None
_CACHE_SOURCE: Optional[Tuple[str, bool]] = None
_CACHE_PINNED = False


def get_cache() -> AutotuneCache:
    """Process-wide cache honoring ``REPRO_AUTOTUNE_CACHE`` (explicit path
    => strict miss warnings); defaults to the checked-in cache.  A cache
    installed with set_cache() stays pinned until reset_cache()."""
    global _CACHE, _CACHE_SOURCE
    if _CACHE_PINNED:
        return _CACHE
    env = os.environ.get(ENV_VAR)
    source = (env or DEFAULT_CACHE_PATH, env is not None)
    if _CACHE is None or _CACHE_SOURCE != source:
        # runtime resolution never raises on a bad file: a corrupt cache
        # degrades to the static block table, it does not stop serving
        _CACHE = AutotuneCache.load(source[0], strict=source[1],
                                    on_error="fallback")
        _CACHE_SOURCE = source
    return _CACHE


def set_cache(cache: Optional[AutotuneCache]) -> None:
    """Pin a cache instance (tests / in-process tuning runs); pass None
    (or call reset_cache) to return to env/default resolution."""
    global _CACHE, _CACHE_SOURCE, _CACHE_PINNED
    _CACHE = cache
    _CACHE_SOURCE = None
    _CACHE_PINNED = cache is not None


def reset_cache() -> None:
    """Drop the process-wide cache so the next get_cache() reloads."""
    set_cache(None)


# ---------------------------------------------------------------------------
# Measured sweep
# ---------------------------------------------------------------------------

# (dispatch, order, pipelined) route combos the sweep measures per block
# shape.  order/pipelined are first-class knobs: an m_major pipelined
# route prices pure double-buffering, the k_major one adds B-block reuse
# (the v2 'sparse' route requires m_major; 'dense' ignores the schedule).
ROUTE_CANDIDATES = (
    ("dense", "m_major", False),
    ("sparse", "m_major", False),
    ("pipelined", "m_major", True),
    ("pipelined", "k_major", True),
)


def candidate_configs(m: int, k: int, n: int) -> List[dict]:
    """Candidate (block_m, block_k, block_n, dispatch, order, pipelined)
    points.

    Blocks stay MXU-aligned (multiples of 128) and never exceed the padded
    problem dims by more than one block (bigger would be pure padding).
    ``block_k`` runs up to the whole K and ``block_m`` up to 1024 rows,
    so the sweep reaches the weight-streaming tiles that
    ``ops.select_block_sizes`` gives a decode GEMM.
    """
    def up(dim):
        return -(-dim // 128) * 128       # dim rounded up to 128

    def sizes(dim, options):
        picked = sorted({s for s in options if s <= up(dim)})
        return picked or [128]

    # K whole, halved or in thirds, wherever that leaves 128-multiples
    splits = tuple(up(k) // d for d in (1, 2, 3) if up(k) % (128 * d) == 0)
    out = []
    for bm in sizes(m, (128, 256, 384, 512, 768, 1024)):
        for bk in sizes(k, (128, 256, 512) + splits):
            for bn in sizes(n, (128, 256)):
                for dispatch, order, pipelined in ROUTE_CANDIDATES:
                    out.append({"block_m": bm, "block_k": bk, "block_n": bn,
                                "dispatch": dispatch, "order": order,
                                "pipelined": pipelined})
    return out


def _measure(fn, warmup: int = 1, iters: int = 3) -> float:
    """Best-of-``iters`` wall seconds of ``fn()`` (jit warm-up excluded)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_gemm(m: int, k: int, n: int, spec=None, a=None, b=None, *,
                  cache: Optional[AutotuneCache] = None, iters: int = 3,
                  seed: int = 0, interpret: Optional[bool] = None) -> dict:
    """Measure every candidate config on a real (planned) GEMM and record
    the winner.

    a: optional int8 [M, K] multiplicand (synthesized LLM-like — student-t
    weights quantized on the spec's grid — when omitted).  b: optional
    int8 [K, N].  Returns the winning config (with its measured seconds
    and the plan's density).  Off-TPU the timings are interpret-mode: they
    rank candidate *work* (grid steps, DMA'd blocks), not MXU wall time.
    """
    import jax.numpy as jnp
    from repro.core import quant as quantlib
    from repro.engine.spec import QuantSpec
    from . import ops

    spec = QuantSpec.coerce(spec) if spec is not None else None
    rng = np.random.default_rng(seed)
    if a is None:
        w = (rng.standard_t(4, size=(k, m)) * 0.02).astype(np.float32)
        if spec is not None:
            qw, _ = quantlib.quantize_for_spec(jnp.asarray(w), spec, axis=0)
        else:
            qw, _ = quantlib.quantize_to_planes(jnp.asarray(w), planes=3,
                                                axis=0)
        a = np.asarray(qw).T.astype(np.int8)
    if b is None:
        b = rng.integers(-128, 128, size=(k, n)).astype(np.int8)
    b = jnp.asarray(b, jnp.int8)
    encoding = spec.encoding if spec is not None else "ent"
    bits = spec.bits if spec is not None else 8
    x = b.T.astype(jnp.float32)          # the tokens the sweep multiplies
    sw = jnp.ones((m,), jnp.float32)
    # hard VMEM gate: candidates whose resident footprint cannot fit a TPU
    # core are never measured (interpret mode would happily "win" with a
    # config that OOMs on hardware); the filter never empties the pool
    from repro import analysis
    from repro.core import encodings as enc
    all_configs = candidate_configs(m, k, n)
    candidates, _ = analysis.filter_vmem_configs(
        m, k, n, all_configs, n_planes=enc.num_digits(encoding, bits))
    _M_VMEM_REJECTED.inc(len(all_configs) - len(candidates))
    # calibration: pair each candidate's measured seconds with the
    # impl's cost-model prediction for the same (shape, spec, density)
    # key — the CostCalibrator turns these into per-impl drift ratios
    calibrator = get_calibrator()
    cal_spec = spec if spec is not None else QuantSpec(planes=3)
    from repro.engine import engine_for_route
    sweep_sp = obs_trace.span("autotune.sweep", m=m, k=k, n=n,
                              candidates=len(candidates),
                              vmem_rejected=len(all_configs)
                              - len(candidates))
    results = []
    with sweep_sp:
        for config in candidates:
            planned = ops.plan_operand(a, encoding=encoding,
                                       block_m=config["block_m"],
                                       block_k=config["block_k"],
                                       bits=bits, order=config["order"])

            # the candidate through the served apply path, its route forced
            def fn(plan=ops._plan_record(planned, sw), config=config):
                return ops.planned_dense_apply(
                    plan, x, cal_spec, m, block_n=config["block_n"],
                    interpret=interpret, dispatch=config["dispatch"],
                    order=config["order"], verify=False)

            with obs_trace.span("autotune.measure", **config):
                secs = _measure(fn, iters=iters)
            # file the measurement under the same *schedule-length
            # proxy* (L / mask.size, sentinels included) that
            # planned_dense_apply's 'auto' dispatch computes at lookup
            # time — keying record and lookup on different density
            # metrics would scatter them across buckets
            proxy = planned.schedule.shape[0] / max(planned.mask.size, 1)
            results.append((secs, config, proxy))
            engine = engine_for_route(config["dispatch"])
            impl = engine.name
            # serving orientation: tokens on M, output channels on N —
            # the transpose of this sweep's (m=rows, n=tokens)
            predicted = engine.predict_seconds(
                n, k, m, cal_spec, plan=planned)
            if predicted > 0 and secs > 0:
                calibrator.record(impl, predicted, secs, shape=(m, k, n),
                                  density=planned.density(),
                                  source="autotune")
    secs, config, density = min(results, key=lambda r: r[0])
    winner = dict(config, us=round(secs * 1e6), density=round(density, 4),
                  candidates=len(results),
                  vmem_rejected=len(all_configs) - len(candidates),
                  backend=current_backend())
    cache = cache if cache is not None else get_cache()
    cache.record(m, k, n, spec, winner, density=density)
    return winner


# ---------------------------------------------------------------------------
# CLI: --validate (the CI autotune-cache lane) and --sweep (regeneration)
# ---------------------------------------------------------------------------

def validate(path: Optional[str] = None) -> List[str]:
    """Parse the cache and check CI-shape coverage; returns problems.

    Loading already rejects entries without a measuring-backend tag (the
    CI autotune-cache lane fails on any untagged entry); the coverage
    check asks for interpret-mode entries — the ones CI itself can
    exercise — regardless of the validating host's backend.
    """
    path = path or os.environ.get(ENV_VAR) or DEFAULT_CACHE_PATH
    try:
        # load is the tag gatekeeper: _check_entry raises on any entry
        # missing its measuring-backend tag, so an untagged cache surfaces
        # here as a parse failure naming the offending entry
        cache = AutotuneCache.load(path)
    except (ValueError, OSError, json.JSONDecodeError) as e:
        return [f"cache {path!r} failed to parse: {e}"]
    if not cache.entries:
        return [f"cache {path!r} is missing or empty"]
    return [f"cache {path!r} does not cover CI benchmark shape {shape} "
            f"for backend 'interpret' ({len(cache.entries)} entries)"
            for shape in cache.coverage(CI_SHAPES, backend="interpret")]


def _print_cache_stats(path: str) -> None:
    """Hit/miss + coverage stats for CI logs (beyond pass/fail)."""
    try:
        cache = AutotuneCache.load(path)
    except (ValueError, OSError, json.JSONDecodeError):
        return
    by_backend: Dict[str, int] = {}
    for entry in cache.entries.values():
        backend = entry.get("backend", "?")
        by_backend[backend] = by_backend.get(backend, 0) + 1
    # probe the CI shapes the way the dispatch seams would, so the log
    # shows lookup coverage, not just entry counts
    for shape in CI_SHAPES:
        cache.lookup(*shape)
    stats = cache.stats()
    print(f"cache stats: {stats['entries']} entries "
          f"(by backend: {dict(sorted(by_backend.items()))}); "
          f"CI-shape probe [{current_backend()}]: "
          f"hits={stats['hits']} misses={stats['misses']}")
    reg = obs_metrics.get_registry()
    print(f"process counters: autotune_cache_hits="
          f"{reg.counter('repro_autotune_cache_hits_total').value} "
          f"misses="
          f"{reg.counter('repro_autotune_cache_misses_total').value} "
          f"miss_warnings="
          f"{reg.counter('repro_autotune_miss_warnings_total').value}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--validate", action="store_true",
                    help="check the cache parses and covers CI_SHAPES")
    ap.add_argument("--sweep", action="store_true",
                    help="re-measure CI_SHAPES and write the cache")
    ap.add_argument("--cache", default=None,
                    help=f"cache path (default: ${ENV_VAR} or the "
                         f"checked-in {os.path.basename(DEFAULT_CACHE_PATH)})")
    ap.add_argument("--planes", type=int, default=3,
                    help="digit-plane budget of the sweep's synthetic "
                         "weights (default 3)")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)
    path = args.cache or os.environ.get(ENV_VAR) or DEFAULT_CACHE_PATH
    if args.sweep:
        from repro.engine.spec import QuantSpec
        cache = AutotuneCache(path)
        if os.path.exists(path):
            cache = AutotuneCache.load(path)
            cache.path = path
        backend = current_backend()
        for m, k, n in CI_SHAPES:
            # tune the default plan grid (spec=None) plus the spec'd grids
            # the benches sweep: one entry per density bucket reached
            for planes in sorted({1, 2, args.planes, 4}):
                spec = QuantSpec(planes=planes)
                win = autotune_gemm(m, k, n, spec, cache=cache,
                                    iters=args.iters, seed=0)
                print(f"[{backend}] {m}x{k}x{n} planes={planes}: {win}")
            win = autotune_gemm(m, k, n, None, cache=cache,
                                iters=args.iters, seed=0)
            print(f"[{backend}] {m}x{k}x{n} default: {win}")
        cache.save(path)
        print(f"wrote {path} ({len(cache.entries)} entries)")
        return 0
    if args.validate:
        problems = validate(path)
        for p in problems:
            print(f"FAIL: {p}")
        if not problems:
            print(f"OK: {path} parses and covers the CI benchmark shapes")
        _print_cache_stats(path)
        return 1 if problems else 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
