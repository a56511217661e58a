"""ServeEngine: the fixed-batch continuous-batching decode engine.

One engine owns one jit'd serve step closed over one ``QuantSpec`` (baked
into the cfg at construction — engines with different specs coexist in one
process without interfering), plus the host-side slot state, now managed by
``serving.slots.SlotAllocator`` instead of ad-hoc arrays.  The engine
exposes a stepping surface (``admit_from`` / ``step`` / ``has_work``) that
the async server drives, and keeps the legacy blocking ``run(requests)``
loop as a thin wrapper over it.

Correctness fixes over the legacy loop:

- A prompt that cannot fit ``max_len`` fails fast at admission (the old
  loop silently overran the KV cache — `dynamic_update_slice` clamping
  corrupted the last cache row — and truncated generation to one token).
  ``on_too_long="truncate"`` clips with a warning instead; the async
  server's schedulers default to rejecting.
- Recurrent-state families (rwkv, hybrid) get their per-slot state row
  reset to its initial value when a slot is *reused*: attention families
  mask stale cache rows by position, but a recurrence has no position
  mask, so the old loop leaked the previous occupant's state into the next
  request.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import QuantSpec, get_engine
from repro.models import layers as L
from repro.models.api import get_api
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.parallel.sharding import unbox
from repro.train.steps import make_serve_step

from .ckpt import DecodeSnapshot, SnapshotMismatch
from .metrics import dist, emit_request_trace
from .request import QUEUED, ServeRequest
from .scheduler import Scheduler
from .slots import SlotAllocator

__all__ = ["ServeEngine", "RESET_STATE_FAMILIES", "LOOKAHEAD_FAMILIES"]

_REG = obs_metrics.get_registry()
_M_STEPS = _REG.counter("repro_serve_engine_steps_total")
_M_SNAPSHOTS = _REG.counter("repro_serve_snapshots_total")
_M_RESTORES = _REG.counter("repro_serve_restores_total")
_M_TOK_RECOVERED = _REG.counter("repro_serve_tokens_recovered_total")
# counted every step whether or not obs is enabled (the chip benchmark's
# per-layer metrics read them); pre-bound, so a step does no lookup
_M_SLOTS_BOUND = _REG.counter("repro_serve_slot_steps_bound_total").labels()
_M_KV_LIVE = _REG.counter("repro_serve_kv_positions_live_total").labels()
_M_KV_READ = _REG.counter("repro_serve_kv_positions_read_total").labels()
_M_MOE_ROUTES = _REG.counter("repro_moe_routes_held_total").labels()
_M_MOE_ACTIVE = _REG.counter("repro_moe_experts_active_total").labels()
_M_ALIAS_BYTES = _REG.gauge("repro_serve_step_alias_bytes")
_M_TEMP_BYTES = _REG.gauge("repro_serve_step_temp_bytes")

# Families whose decode state is a recurrence (no position-masked cache):
# their per-slot state row must be re-initialized when a slot is reused.
RESET_STATE_FAMILIES = ("rwkv", "hybrid")
# Families whose engine dispatches the next step before reading back the
# one in flight (``ServeEngine(lookahead=True)``): a decoder-only cache
# masked by position, so a step that runs for a slot released or rebound
# meanwhile writes only rows its next occupant rewrites before it reads.
LOOKAHEAD_FAMILIES = ("dense", "moe", "vlm")


@jax.jit
def _feed(cur, prev):
    """The tokens a step dispatched ahead takes: ``cur`` from the host,
    or where it is -1 the token the step before sampled (``prev``, still
    on the device)."""
    return jnp.where(cur < 0, prev, cur)


@dataclasses.dataclass
class _Dispatched:
    """A step on its way: its sampled tokens and expert counts (device
    arrays, not yet read; counts None without experts), the
    ``SlotAllocator.live()`` it computes for, and its counter values."""
    nxt: jax.Array
    counts: Optional[jax.Array]
    live: np.ndarray
    bound: int
    kv_live: int


@jax.jit
def _reset_state_row(state, state0, slot):
    """Restore one batch row (axis 1: leaves are [L, B, ...]) of the decode
    state tree to its initial value."""
    def leaf(s, s0):
        if s.ndim < 2:
            return s
        upd = jax.lax.dynamic_slice_in_dim(s0, slot, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(s, upd, slot, axis=1)
    return jax.tree.map(leaf, state, state0)


@jax.jit
def _slice_state_row(state, slot):
    """Extract one batch row (axis 1, kept as extent-1) of every decode-
    state leaf — the device half of ``snapshot_slot``.  Leaves with
    ndim < 2 are shared (not per-slot) and pass through unchanged."""
    def leaf(s):
        if s.ndim < 2:
            return s
        return jax.lax.dynamic_slice_in_dim(s, slot, 1, axis=1)
    return jax.tree.map(leaf, state)


@jax.jit
def _write_state_row(state, row, slot):
    """Write a snapshot's [L, 1, ...] rows back into one batch row — the
    device half of ``restore_slot``.  ndim < 2 leaves are left alone."""
    def leaf(s, r):
        if s.ndim < 2:
            return s
        return jax.lax.dynamic_update_slice_in_dim(
            s, r.astype(s.dtype), slot, axis=1)
    return jax.tree.map(leaf, state, row)


class ServeEngine:
    """Fixed-batch continuous-batching engine over the decode state.

    quant: a repro.engine.QuantSpec, a legacy layers.QuantState, or None
    (None defers to cfg: an explicit cfg.quant spec, else the quant_planes
    sugar).  The resolved spec is baked into this engine's cfg, so the
    jit'd serve step closes over it — engines with different specs coexist
    in one process without interfering.

    With an impl whose GemmEngine ``uses_plans`` (the Pallas kernel
    engines) the engine serves through the kernel execution path: every
    dense weight is pre-planned once at init, in that GemmEngine's
    schedule order (encode -> digit planes -> occupancy mask ->
    magnitude-ordered channel permutation) and the plan records are
    attached to the param tree, so the jit'd serve step scans/slices them
    like any other parameter and each quantized matmul executes the Pallas
    bw_gemm kernel (interpret mode off-TPU) instead of the jnp oracle.

    With ``lookahead`` (the default; families in ``LOOKAHEAD_FAMILIES``
    and the engine's own step only) each ``step`` dispatches the next
    step before it reads back the one in flight, feeding the tokens that
    step samples on the device, so the host's work between steps runs
    while the device computes.  A request admitted while a step is in
    flight joins the step after it; the tokens served are the same.
    """

    def __init__(self, cfg, batch: int, max_len: int, seed: int = 0,
                 quant=None, on_too_long: str = "error",
                 audit: bool = False, lookahead: bool = True):
        if isinstance(quant, QuantSpec):
            spec = quant if quant.enabled else None
        elif isinstance(quant, L.QuantState):
            spec = quant.spec()
        elif quant is None:
            spec = cfg.quant_spec()
        else:
            raise TypeError(f"quant must be a QuantSpec, QuantState or "
                            f"None; got {type(quant).__name__}")
        self.spec = spec
        # QuantState view kept for stats compatibility (plan_stats etc.)
        self.quant = quant if isinstance(quant, L.QuantState) else \
            L.QuantState(planes=spec.planes if spec else 0,
                         impl=spec.impl if spec else "planes")
        # bake the spec into the cfg the step closes over: no global state
        cfg = cfg.replace(quant=spec,
                          quant_planes=spec.planes if spec else 0)
        self.cfg = cfg
        self.api = get_api(cfg)
        self.batch = batch
        self.max_len = max_len
        self.on_too_long = on_too_long
        with obs_trace.region("serve.build"):
            self._build(cfg, spec, seed)
        self.slots = SlotAllocator(batch, max_len, audit=audit)
        self.steps = 0
        self.lookahead = lookahead and \
            self.api.family in LOOKAHEAD_FAMILIES
        self._ahead: Optional[_Dispatched] = None
        # checkpoint/restore tallies (the async server folds the per-run
        # deltas into its failover stats)
        self.ckpt_stats = {"snapshots": 0, "restored": 0,
                           "reprefilled": 0, "tokens_recovered": 0,
                           "tokens_reprefilled": 0}

    def _build(self, cfg, spec, seed: int) -> None:
        """Weights, the decode state, the weights' digit-plane plans and
        the jitted step.  Each region blocks on its arrays, so its time is
        the work it dispatched, not the next region's wait."""
        with obs_trace.region("serve.init_params"):
            self.params = jax.block_until_ready(
                unbox(self.api.init(jax.random.PRNGKey(seed), cfg)))
        with obs_trace.region("serve.init_state"):
            self.state = unbox(self.api.init_decode(
                cfg, self.batch, self.max_len))
            self._state0 = jax.tree.map(jnp.copy, self.state) \
                if self.api.family in RESET_STATE_FAMILIES else None
            jax.block_until_ready((self.state, self._state0))
        gemm = get_engine(spec.impl) if spec is not None else None
        self._kernel_path = gemm is not None and gemm.uses_plans
        # measured plane-block density of the planned weights (the
        # schedule-aware cost input); None off the kernel path
        self.plan_density = None
        if self._kernel_path:
            # one-time planning step: encode every dense weight into digit
            # planes + occupancy mask + channel permutation and attach the
            # plan records to the param tree.  The jit'd serve step then
            # scans/slices them like any other parameter and every quantized
            # matmul executes the Pallas kernel.
            from repro.kernels import ops
            with obs_trace.region("serve.plan_params"):
                self.params, planned = ops.plan_params(
                    self.params, spec, order=gemm.order)
                jax.block_until_ready(self.params)
            self.plan_density = ops.plan_tree_density(self.params)
            self.quant.plan_stats = {
                "planned_weights": planned,
                "plane_block_density": self.plan_density,
                "schedules_verified": ops.verification_enabled(),
                **ops.plan_cache_stats()}
        # ``step_fn`` leaves the state it is given intact, for callers that
        # call it apart from the engine or wrap it (chip_smoke.py, the
        # chip benchmark's fault hooks).  The engine's own steps donate
        # the state, so the KV cache is updated in place; a ``step_fn``
        # set from outside may return or keep its input, so the engine
        # does not donate to one.
        serve_step = make_serve_step(cfg)
        self.step_fn = self._built_step_fn = jax.jit(serve_step)
        self._step_in_place = jax.jit(serve_step, donate_argnums=(3,))

    # -- stepping surface (driven by the async server) -----------------------

    @property
    def active(self) -> int:
        return self.slots.active

    def has_work(self, scheduler: Optional[Scheduler] = None) -> bool:
        return self.slots.active > 0 or \
            (scheduler is not None and scheduler.queue_depth > 0)

    def admit_from(self, scheduler: Scheduler, now: float = 0.0) -> int:
        """Fill free slots from the scheduler (per its admission policy);
        returns the number of requests admitted.

        A request carrying a decode snapshot (restore-mode failover) is
        restored bit-exactly when the snapshot is compatible with this
        engine (same QuantSpec / family / state geometry); otherwise —
        and for any request with committed tokens but no usable
        snapshot — it re-prefills prompt + committed output, so the
        tokens survive either way."""
        with obs_trace.region("serve.admit"):
            return self._admit_from(scheduler, now)

    def _admit_from(self, scheduler: Scheduler, now: float) -> int:
        admitted = 0
        for slot in self.slots.free_slots():
            req = scheduler.pop(now)
            if req is None:
                break
            snap, req.snapshot = req.snapshot, None
            if snap is not None and self.restorable(snap) is None:
                try:
                    self.restore_slot(slot, req, snap, now)
                    admitted += 1
                    continue
                except (SnapshotMismatch, ValueError):
                    # containment: a snapshot that fails mid-restore must
                    # read as "re-prefill", never escalate — the request
                    # is already off the scheduler, and an exception
                    # escaping here would be mistaken for a death of the
                    # healthy destination tier, losing the request
                    # without it ever being counted
                    if self.slots.request_at(slot) is req:
                        self.slots.evict(slot)
                    if req.state != QUEUED:
                        req.requeue(now, keep_tokens=True)
                    if obs_trace.enabled():
                        obs_trace.instant("serve.restore_failed",
                                          cat="serve", rid=req.rid)
            rebind = self.slots.bind(slot, req, now)
            if rebind and self._state0 is not None:
                # recurrent state: restore this row to its initial value so
                # the new occupant never sees the previous request's state
                self.state = _reset_state_row(
                    self.state, self._state0, jnp.int32(slot))
            if req.out:
                # token-preserving re-prefill (cross-spec demotion or a
                # snapshot that failed): committed tokens are replayed by
                # teacher forcing, never regenerated
                self.ckpt_stats["reprefilled"] += 1
                self.ckpt_stats["tokens_recovered"] += len(req.out)
                self.ckpt_stats["tokens_reprefilled"] += len(req.out)
                _M_RESTORES.labels(mode="cross_spec").inc()
                _M_TOK_RECOVERED.inc(len(req.out))
                if obs_trace.enabled():
                    obs_trace.instant("serve.restore", cat="serve",
                                      rid=req.rid, mode="cross_spec",
                                      tokens=len(req.out))
            admitted += 1
        return admitted

    # -- checkpoint/restore seam (repro.ckpt) --------------------------------

    def snapshot_slot(self, slot: int) -> DecodeSnapshot:
        """Capture everything ``slot`` owns as a ``DecodeSnapshot``: its
        decode-state rows (KV rows / recurrent-state row), the occupant's
        committed tokens, teacher-forcing cursor, next-step token, and
        lifecycle stamps."""
        req = self.slots.request_at(slot)
        if req is None:
            raise ValueError(f"slot {slot} is not bound; nothing to "
                             f"snapshot")
        if not self.slots.decode_ready(slot):
            raise ValueError(
                f"slot {slot} (request {req.rid}) is still "
                f"teacher-forcing its prefix: pos "
                f"{int(self.slots.pos[slot])} violates the snapshot "
                f"invariant pos == len(prompt) + len(out) - 1; migrate "
                f"it via the token-preserving re-prefill path instead")
        rows = [np.asarray(x) for x in
                jax.tree.leaves(_slice_state_row(self.state,
                                                 jnp.int32(slot)))]
        snap = DecodeSnapshot(
            rid=req.rid, spec=str(self.spec) if self.spec else None,
            family=self.api.family, max_len=self.max_len,
            pos=int(self.slots.pos[slot]),
            cursor=int(self.slots.cursor[slot]),
            cur=int(self.slots.cur[slot, 0]),
            prompt=list(req.prompt), out=list(req.out),
            rows=rows, arrival=req.arrival, admitted_at=req.admitted_at,
            first_token_at=req.first_token_at)
        self.ckpt_stats["snapshots"] += 1
        _M_SNAPSHOTS.inc()
        if obs_trace.enabled():
            obs_trace.instant("serve.snapshot", cat="serve", rid=req.rid,
                              pos=snap.pos, tokens=len(snap.out))
        return snap

    def restorable(self, snap: DecodeSnapshot) -> Optional[str]:
        """None when ``snap`` can be restored bit-exactly into this
        engine, else the reason it cannot (the caller then takes the
        re-prefill path)."""
        if not snap.out:
            return "no committed tokens to restore"
        if snap.pos != len(snap.prompt) + len(snap.out) - 1:
            # e.g. a snapshot taken mid-teacher-forcing: its pos/cursor
            # are partway through the forced prefix and bind_restored
            # would (rightly) refuse it — re-prefill keeps the tokens
            return (f"position invariant violated: pos {snap.pos} != "
                    f"len(prompt) + len(out) - 1 = "
                    f"{len(snap.prompt) + len(snap.out) - 1}")
        spec = str(self.spec) if self.spec else None
        if snap.spec != spec:
            return f"spec mismatch: snapshot {snap.spec!r} vs {spec!r}"
        if snap.family != self.api.family:
            return (f"family mismatch: snapshot {snap.family!r} vs "
                    f"{self.api.family!r}")
        if snap.max_len != self.max_len:
            return (f"max_len mismatch: snapshot {snap.max_len} vs "
                    f"{self.max_len}")
        if snap.sampling != "greedy":
            return f"unsupported sampling state {snap.sampling!r}"
        leaves = jax.tree.leaves(self.state)
        if len(snap.rows) != len(leaves):
            return (f"state tree mismatch: snapshot has {len(snap.rows)} "
                    f"rows, engine has {len(leaves)} leaves")
        for i, (row, leaf) in enumerate(zip(snap.rows, leaves)):
            want = (leaf.shape if leaf.ndim < 2
                    else leaf.shape[:1] + (1,) + leaf.shape[2:])
            if row.shape != want or str(row.dtype) != str(leaf.dtype):
                return (f"state leaf {i} mismatch: snapshot row "
                        f"{row.shape}/{row.dtype}, engine expects "
                        f"{want}/{leaf.dtype}")
        return None

    def restore_slot(self, slot: int, req: ServeRequest,
                     snap: DecodeSnapshot, now: float = 0.0) -> None:
        """Write ``snap`` back into ``slot`` bit-exactly and resume
        ``req`` mid-decode (no re-prefill steps).  Raises
        ``SnapshotMismatch`` when the snapshot is incompatible."""
        why = self.restorable(snap)
        if why is not None:
            raise SnapshotMismatch(f"request {req.rid}: {why}")
        if req.rid != snap.rid:
            raise SnapshotMismatch(f"snapshot belongs to request "
                                   f"{snap.rid}, not {req.rid}")
        self.slots.bind_restored(slot, req, pos=snap.pos,
                                 cursor=snap.cursor, cur=snap.cur,
                                 now=now)
        treedef = jax.tree.structure(self.state)
        row = jax.tree.unflatten(
            treedef, [jnp.asarray(r) for r in snap.rows])
        self.state = _write_state_row(self.state, row, jnp.int32(slot))
        self.ckpt_stats["restored"] += 1
        self.ckpt_stats["tokens_recovered"] += len(req.out)
        _M_RESTORES.labels(mode="same_spec").inc()
        _M_TOK_RECOVERED.inc(len(req.out))
        if obs_trace.enabled():
            obs_trace.instant("serve.restore", cat="serve", rid=req.rid,
                              mode="same_spec", pos=snap.pos,
                              tokens=len(req.out))

    def step(self, now: float = 0.0) -> List[ServeRequest]:
        """One batched decode step; returns requests finished this step.

        Always-on regions split it: ``serve.upload`` (tokens and
        positions to the device), ``serve.dispatch`` (the jitted step
        call, until it returns), ``serve.readback`` (waiting for the
        sampled tokens) and ``serve.advance`` (the slots' bookkeeping).
        The engine's first call is ``serve.compile`` in place of
        ``serve.dispatch``: JAX traces and compiles the step there.  The
        engine's own step takes the state by donation, so the previous
        state's arrays are deleted once it is dispatched.  A step with
        experts also returns its per-layer expert counts, read back with
        the tokens in one host read and added to
        ``repro_moe_routes_held_total`` and
        ``repro_moe_experts_active_total``.

        With lookahead the step this call completes was most often
        dispatched by the call before; this call dispatches the next one
        (fed by ``SlotAllocator.next_inputs``) before it reads back.  The
        counters count the step completed."""
        slots = self.slots
        with obs_trace.region("serve.step"):
            if obs_trace.enabled():
                _M_STEPS.inc()
            in_place = self.step_fn is self._built_step_fn
            run, self._ahead = self._ahead, None
            inputs = []             # (cur, pos, live) of each dispatch
            if run is None:
                inputs.append((slots.cur, slots.pos, slots.live()))
            if self.lookahead and in_place:
                ahead = slots.next_inputs(
                    inputs[0][2] if run is None else run.live)
                if (ahead[2] >= 0).any():
                    inputs.append(ahead)
            with obs_trace.region("serve.upload"):
                uploaded = [(jnp.asarray(cur), jnp.asarray(pos))
                            for cur, pos, _ in inputs]
            with obs_trace.region("serve.dispatch" if self.steps
                                  else "serve.compile"):
                for (_, pos, live), (cur_d, pos_d) in zip(inputs, uploaded):
                    if run is None:
                        run = self._dispatch(cur_d, pos_d, pos, live, None,
                                             in_place)
                    else:
                        self._ahead = self._dispatch(cur_d, pos_d, pos,
                                                     live, run.nxt, in_place)
            self.steps += 1
            with obs_trace.region("serve.readback"):
                if run.counts is not None:
                    nxt, counts = jax.device_get((run.nxt, run.counts))
                else:
                    nxt, counts = np.asarray(run.nxt), None
            with obs_trace.region("serve.advance"):
                _M_SLOTS_BOUND.inc(run.bound)
                _M_KV_LIVE.inc(run.kv_live)
                _M_KV_READ.inc(self.batch * self.max_len)
                if counts is not None:
                    _M_MOE_ROUTES.inc(int(counts[:, 0].sum()))
                    _M_MOE_ACTIVE.inc(int(counts[:, 1].sum()))
                return slots.advance(nxt, now, live=run.live)

    def _dispatch(self, cur, pos, pos_host: np.ndarray, live: np.ndarray,
                  prev, in_place: bool) -> _Dispatched:
        """Dispatch one step over the state with the uploaded ``cur`` and
        ``pos``; where ``cur`` is -1 the step takes ``prev``, the sampled
        tokens of the step before, on the device."""
        if prev is not None:
            cur = _feed(cur, prev)
        step_fn = self._step_in_place if in_place else self.step_fn
        out = step_fn(self.params, cur, pos, self.state)
        self.state = out[1]
        if in_place and not self.steps and prev is None:
            self._record_step_memory(cur, pos)
        bound = live >= 0
        return _Dispatched(nxt=out[0], counts=out[2] if len(out) > 2
                           else None, live=live, bound=int(bound.sum()),
                           kv_live=int(pos_host[bound].sum())
                           + int(bound.sum()))

    def _record_step_memory(self, cur, pos) -> None:
        """Put the compiled step's aliased (updated in place) and
        temporary bytes into the metrics registry.  The lowering hits the
        jit's cache of the step just compiled; the new state has the
        donated one's shapes."""
        mem = self._step_in_place.lower(
            self.params, cur, pos, self.state).compile().memory_analysis()
        if mem is not None:
            _M_ALIAS_BYTES.set(mem.alias_size_in_bytes)
            _M_TEMP_BYTES.set(mem.temp_size_in_bytes)

    # -- legacy blocking loop ------------------------------------------------

    def run(self, requests: List[ServeRequest], policy: str = "fcfs") -> dict:
        """Serve ``requests`` to completion (the legacy synchronous loop):
        admit into free slots per ``policy``, step, repeat."""
        sched = Scheduler(policy, max_len=self.max_len,
                          on_too_long=self.on_too_long)
        t0 = time.perf_counter()
        for req in requests:
            sched.submit(req, now=0.0)
        done: List[ServeRequest] = []
        while self.has_work(sched):
            now = time.perf_counter() - t0
            self.admit_from(sched, now)
            done.extend(self.step(now=time.perf_counter() - t0))
        dt = time.perf_counter() - t0
        if obs_trace.enabled():
            for r in done:
                emit_request_trace(r)
        gen = sum(len(r.out) for r in done)
        stats = {"requests": len(done), "generated_tokens": gen,
                 "engine_steps": self.steps, "wall_s": round(dt, 2),
                 "tok_per_s": round(gen / max(dt, 1e-9), 1),
                 "quant_spec": str(self.spec) if self.spec else None,
                 "quant_planes": self.spec.planes if self.spec else 0,
                 "quant_impl": self.spec.impl if self.spec else None,
                 "rejected": len(sched.rejected),
                 "admission_policy": sched.policy.name,
                 "ttft": dist(r.ttft for r in done),
                 "tpot": dist(r.tpot for r in done)}
        if self._kernel_path:
            from repro.kernels import ops
            stats["plan_cache"] = ops.plan_cache_stats()
        return stats
