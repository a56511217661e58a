"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the reduction needs, as plain lists: the operations of each TPU
device (``XLA Ops`` line; each event is named by its HLO text, from which
the instruction name and result type are kept) and the benchmark's own
host spans (names that start with ``bench.``), all on the profiler's one
clock.  Container operations (``while``, ``conditional``, ``call``) span
the operations they run: they count towards busy time, not per-name
time.  ``summarize`` reduces them over the traced window: busy time as
the union of operation intervals, time per operation name, and idle gaps
with the host span that was open during each.  Both work on the same
plain structure, so a small recorded trace checks the reduction without
a chip.
"""
from __future__ import annotations

import fnmatch
import glob
import os
import re
from typing import Dict, List, Optional

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
STEP_SPAN = "bench.step"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> tuple:
    """(instruction name, label) of an HLO text such as
    ``%copy.12 = bf16[1,32]{1,0} copy(...)``: ("copy.12",
    "copy bf16[1,32]").  The label drops the instruction's number, so
    it names the same operation in every compile."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    base = re.sub(r"\.\d+$", "", name)
    out = re.split(r"[{ ]", rest.strip(), maxsplit=1)[0] if rest else ""
    return name, (f"{base} {out}" if out and not out.startswith("(")
                  else base)


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns, label], ...]},
    "host": [[name, start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                "SparseCore" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, label = op_name(e.name)
                    ops.append([name, e.start_ns, e.duration_ns, label])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def summarize(events: dict, top: int = 10) -> Optional[dict]:
    """Reduce ``load``'s structure over the ``bench.traced`` span.

    Returns None when the trace holds no window span or no device
    operation inside it.  Times are in seconds, per device averaged over
    the devices traced: ``window_s``, ``busy_s``, ``steps`` (step spans
    wholly inside the window), ``op_s`` {instruction: seconds} and
    ``label_s`` {label: seconds} (leaf operations only), ``idle_by_host`` [[span, seconds]] (idle time by
    the innermost host span open at each gap's middle) and
    ``device_ops`` [[label, seconds]] (the ``top`` costliest labels)."""
    spans = [h for h in events["host"] if h[0] == WINDOW_SPAN]
    if not spans or not events["devices"]:
        return None
    lo = spans[0][1]
    hi = lo + spans[0][2]
    steps = sum(1 for n, s, d in events["host"]
                if n == STEP_SPAN and s >= lo and s + d <= hi)
    host = sorted((s, s + d, n) for n, s, d in events["host"]
                  if n != WINDOW_SPAN)
    n_dev = len(events["devices"])
    busy = 0.0
    op_ns: Dict[str, float] = {}
    label_ns: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for ops in events["devices"].values():
        iv = []
        for name, s, d, label in ops:
            s, e = _clip(s, s + d, lo, hi)
            if e <= s:
                continue
            iv.append((s, e))
            if label.split(" ")[0] in CONTAINERS:
                continue
            op_ns[name] = op_ns.get(name, 0.0) + (e - s)
            label_ns[label] = label_ns.get(label, 0.0) + (e - s)
        merged = _union(iv)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            open_ = [n for s, e, n in host if s <= mid < e]
            cause = open_[-1] if open_ else "no host span"
            idle[cause] = idle.get(cause, 0.0) + (ge - gs)
    if busy == 0.0:
        return None
    ns = 1e-9 / n_dev

    def ranked(d):
        return sorted(([k, v * ns] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * ns,
        "steps": steps,
        "op_s": {k: v * ns for k, v in op_ns.items()},
        "label_s": {k: v * ns for k, v in label_ns.items()},
        "idle_by_host": ranked(idle),
        "device_ops": ranked(label_ns),
    }


def matching_time(summary: dict, patterns) -> float:
    """Seconds of the operations whose instruction name matches one of
    the shell-style ``patterns``."""
    return sum(sec for name, sec in summary["op_s"].items()
               if any(fnmatch.fnmatchcase(name, p) for p in patterns))
