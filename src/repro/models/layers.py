"""Shared neural-net building blocks (pure functional JAX).

Every init_* returns a pytree of sharding.Boxed leaves (value + logical
axes); apply functions consume the unboxed value tree.  Compute runs in
cfg.dtype (bf16 by default), norms and softmax in fp32.

Quantized execution is configured per call by a
:class:`repro.engine.QuantSpec` passed to ``dense_apply`` (models thread
``cfg.quant_spec()``); the spec's ``impl`` selects a registered GemmEngine
strategy.  There is no process-global implementation switch — the old
``set_quant_impl`` / ``QUANT_IMPL`` API survives only as a deprecation
shim at the bottom of this module.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import box, constrain
from repro import engine as englib
from repro.engine import _compat as _quant_compat
from repro.engine.spec import QuantSpec

__all__ = [
    "dense_init", "dense_apply", "rmsnorm_init", "rmsnorm_apply",
    "layernorm_init", "layernorm_apply", "embed_init", "embed_apply",
    "rope", "rope_inv_freq", "yarn_inv_freq", "activation", "QuantState",
    "QuantSpec",
    "set_quant_impl", "QUANT_IMPLS",
]


def truncated_normal(key, shape, scale, dtype=jnp.float32):
    stddev = scale / np.sqrt(max(shape[0], 1))
    # scale in float32, then cast: a numpy scalar multiplied after the cast
    # would promote a bfloat16 result back to float32
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


# ---------------------------------------------------------------------------
# Dense / projection layers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, axes: Tuple[str, str],
               bias: bool = False, param_dtype=jnp.float32, scale: float = 1.0):
    p = {"w": box(truncated_normal(key, (d_in, d_out), scale, param_dtype),
                  axes)}
    if bias:
        p["b"] = box(jnp.zeros((d_out,), param_dtype), (axes[1],))
    return p


def dense_apply(p, x, dtype=jnp.bfloat16, quant=0,
                activation: Optional[str] = None):
    """y = act(x @ w (+ b)).

    quant: a repro.engine.QuantSpec (models pass ``cfg.quant_spec()``), or
    the legacy int plane budget (sugar for a default-grid spec whose impl
    comes from the deprecated global shim), or 0/None for the bf16 path.

    An enabled spec routes through the paper's BW-decomposed quantised
    matmul semantics (exact integer digit-plane GEMM on the spec's grid,
    per-tensor act scale and per-channel weight scale) via the GemmEngine
    the spec's ``impl`` names, with a straight-through gradient on the jnp
    engines.  The kernel engines consume a pre-planned ``w_plan`` record
    when one is attached to ``p`` (ops.plan_params; traceable under
    jit/scan), run the real Pallas kernel on eager concrete operands, and
    lower to a cost-representative int8 dot under tracing without a plan.

    activation: optional epilogue activation name (see layers.activation).
    None keeps the historical behaviour of returning the linear output.
    """
    w = p["w"]
    b = p.get("b")
    # the impl kwarg only applies to the legacy int sugar: it reads the
    # deprecated global-switch shim so un-migrated callers keep working
    spec = QuantSpec.coerce(quant, impl=_quant_compat.default_impl())
    if spec is not None:
        eng = englib.get_engine(spec.impl)
        plan = p.get("w_plan") if eng.uses_plans else None
        if plan is not None:
            return eng.apply(plan, x, spec, n_out=w.shape[-1], bias=b,
                             activation=activation, out_dtype=dtype)
        return eng.apply(w, x, spec, bias=b, activation=activation,
                         out_dtype=dtype)
    y = jax.lax.dot_general(x.astype(dtype), w.astype(dtype),
                            (((x.ndim - 1,), (0,)), ((), ())))
    if b is not None:
        y = y + b.astype(dtype)
    if activation is not None:
        from repro.kernels.bw_gemm import EPILOGUE_ACTIVATIONS
        y = EPILOGUE_ACTIVATIONS[activation](y)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, param_dtype=jnp.float32):
    return {"scale": box(jnp.ones((d,), param_dtype), ("embed_nofsdp",))}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def layernorm_init(d: int, param_dtype=jnp.float32):
    return {"scale": box(jnp.ones((d,), param_dtype), ("embed_nofsdp",)),
            "bias": box(jnp.zeros((d,), param_dtype), ("embed_nofsdp",))}


def layernorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int, param_dtype=jnp.float32):
    return {"table": box(
        truncated_normal(key, (vocab, d), scale=float(np.sqrt(d)),
                         dtype=param_dtype),
        ("vocab", "embed_nofsdp"))}


def embed_apply(p, tokens, dtype=jnp.bfloat16):
    out = jnp.take(p["table"].astype(dtype), tokens, axis=0)
    return constrain(out, "batch", "seq", None)


def embed_logits(p, x, dtype=jnp.bfloat16):
    """Tied decode head: x [.., d] @ table.T -> [.., vocab]."""
    logits = jax.lax.dot_general(
        x.astype(dtype), p["table"].astype(dtype),
        (((x.ndim - 1,), (1,)), ((), ())))
    return logits


# ---------------------------------------------------------------------------
# RoPE + activations
# ---------------------------------------------------------------------------

def rope_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """The default RoPE's inverse frequencies, float32 [head_dim // 2]."""
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0, attention_factor: float = 0.0):
    """YaRN's inverse frequencies and cos/sin scale (transformers'
    ``_compute_yarn_parameters``): frequencies below the
    ``beta_slow`` rotations over the original context are interpolated by
    1 / factor, those above ``beta_fast`` kept, with a linear ramp between
    floor and ceil of the two correction dimensions.  Returns
    (float32 [head_dim // 2], scale); a scale of 0 becomes
    0.1 ln(factor) + 1."""
    dim = head_dim

    def correction_dim(rotations):
        return dim * math.log(original_max_position /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolation = (1 - ramp).astype(np.float32)
    inv = (1.0 / (factor * pos_freqs)) * (1 - extrapolation) + \
        (1.0 / pos_freqs) * extrapolation
    scale = attention_factor or (0.1 * math.log(factor) + 1.0
                                 if factor > 1 else 1.0)
    return inv.astype(np.float32), float(scale)


def rope(q, k, positions, head_dim: int, theta: float = 1e4,
         inv_freq=None, scale=None):
    """Rotary embeddings.  q,k: [B, T, H, D]; positions: [B, T] int32.
    ``inv_freq`` [D // 2] and ``scale`` (of cos and sin) replace the
    default frequencies of ``theta`` where a layer has its own (YaRN)."""
    if inv_freq is None:
        inv_freq = rope_inv_freq(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return rot(q), rot(k)


def activation(name: str):
    # single source of truth shared with the kernels' fused epilogue, so a
    # new activation is automatically available in both places
    from repro.kernels.bw_gemm import EPILOGUE_ACTIVATIONS
    if name is None or name not in EPILOGUE_ACTIVATIONS:
        raise ValueError(name)
    return EPILOGUE_ACTIVATIONS[name]


@dataclasses.dataclass
class QuantState:
    """Quantized-execution state threaded through launchers/engines.

    A thin wrapper over the engine registry: ``spec()`` converts to the
    QuantSpec that actually configures execution (planes = digit-plane
    budget, 0 = bf16 path; impl = registered GemmEngine name, legacy
    aliases accepted).  plan_stats is filled by serving engines that
    pre-plan weights through the kernel path so callers can verify the
    kernel (not the oracle) served the traffic.
    """
    planes: int = 0
    impl: str = "planes"
    plan_stats: Optional[dict] = None

    def spec(self) -> Optional[QuantSpec]:
        """The QuantSpec this state describes (None when disabled)."""
        if not self.planes:
            return None
        return QuantSpec(planes=self.planes,
                         impl=englib.normalize_impl(self.impl))

    def activate(self) -> "QuantState":
        """DEPRECATED: pass ``spec()`` explicitly instead of activating a
        process-global default."""
        warnings.warn(
            "QuantState.activate() is deprecated: pass QuantState.spec() "
            "(a QuantSpec) explicitly to dense_apply / cfg.replace(quant=...) "
            "instead of mutating the process-global default",
            DeprecationWarning, stacklevel=2)
        _quant_compat.set_default_impl(self.impl)
        return self


# ---------------------------------------------------------------------------
# DEPRECATION SHIM -- the old process-global implementation switch.
# Everything below warns and proxies to repro.engine._compat, which only
# the legacy int-plane-budget sugar path consults.  Scheduled for removal
# after one release; new code passes QuantSpec explicitly.
# ---------------------------------------------------------------------------

QUANT_IMPLS = englib.IMPLS      # registered engine names (stable tuple)


def set_quant_impl(kind: str) -> None:
    """DEPRECATED: select the default impl for legacy int-budget callers.

    Only calls that pass a bare ``quant_planes`` int (no QuantSpec) see
    this default; spec-carrying callers are unaffected, so engines with
    different specs never interfere.  Use
    ``QuantSpec(impl=...)`` / ``--quant-spec impl=...`` instead.
    """
    warnings.warn(
        "set_quant_impl() is deprecated: pass QuantSpec(impl=...) "
        "explicitly (e.g. dense_apply(p, x, dtype, cfg.quant_spec()))",
        DeprecationWarning, stacklevel=2)
    if englib.normalize_impl(kind) not in englib.IMPLS:
        raise ValueError(f"unknown quant impl {kind!r}; one of "
                         f"{englib.IMPLS} (or legacy alias 'pallas')")
    _quant_compat.set_default_impl(kind)


def __getattr__(name: str):
    # module-level attribute shim (PEP 562) for the removed global
    if name == "QUANT_IMPL":
        warnings.warn(
            "layers.QUANT_IMPL is deprecated: quantized execution is "
            "configured per call by QuantSpec; this reads the legacy "
            "default used only by un-migrated int-budget callers",
            DeprecationWarning, stacklevel=2)
        return _quant_compat.legacy_name()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
