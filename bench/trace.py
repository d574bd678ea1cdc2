"""Reduction of a profiler trace to events, busy time and idle gaps.

`load(path)` reads the `.xplane.pb` a `jax.profiler` trace writes and
keeps three kinds of event, as [name, start_ns, end_ns] lists on the
trace's one clock:

  * `calls`   — the harness's own spans (`bench.call`), one per entry
                call of the measured window;
  * `host`    — every other host event that overlaps a call: what the
                host was doing (the runtime's spans), used to name
                idle gaps;
  * `devices` — per device plane, the operations of its "XLA Ops" line,
                each named by its HLO instruction (the text before
                " = ", e.g. `fusion.12`, `replay_blocks.1`) and its
                result type.

`reduce(events)` turns them into the numbers the per-layer metric
readers share: the window, each device's busy union inside it, device
busy time inside each call, time by operation name, and the idle gaps
labelled by the host event they fell in.  `read` loads events kept as
JSON, which is how the tests hold a small recorded trace.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

CALL = "bench.call"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "psum", "pmax",
               "collective")


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """Short name of a device op: its HLO instruction name and result
    type, from the instruction text the trace names it by."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    return f"{head.lstrip('%')} {rest.split(' ', 1)[0][:48]}"


def load(path: str) -> dict:
    """Events of one trace file (see module docstring)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    calls, host, devices = [], [], {}
    short: dict[str, str] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = e.name
                    if name not in short:
                        short[name] = op_name(name)
                    ops.append([short[name], e.start_ns, e.end_ns])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = [e.name, float(e.start_ns), float(e.end_ns)]
                    (calls if e.name == CALL else host).append(ev)
    calls.sort(key=lambda c: c[1])
    if calls:
        lo, hi = calls[0][1], calls[-1][2]
        host = [h for h in host if h[2] > lo and h[1] < hi
                and h[2] > h[1]]
    return {"calls": calls, "host": host, "devices": devices}


def read(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


# ------------------------------------------------------------ interval math
def merge(starts, ends, lo: float, hi: float):
    """Disjoint sorted (starts, ends) arrays covering what the intervals
    cover inside [lo, hi]."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, s.size - 1]
    return s[first], e[last]


def _pairs(intervals) -> np.ndarray:
    return np.asarray(list(intervals), np.float64).reshape(-1, 2)


def covered_below(ms, me, x) -> np.ndarray:
    """Length of the disjoint sorted intervals (ms, me) below each x."""
    x = np.asarray(x, np.float64)
    if not ms.size:
        return np.zeros_like(x)
    cum = np.r_[0.0, np.cumsum(me - ms)]
    k = np.searchsorted(ms, x, side="right")       # intervals from <= x
    j = np.maximum(k - 1, 0)
    part = np.clip(np.minimum(x, me[j]) - ms[j], 0.0, None)
    return np.where(k > 0, cum[j] + part, 0.0)


def gap_arrays(ms, me, lo: float, hi: float):
    """(starts, ends) of the parts of [lo, hi] the merged intervals
    leave uncovered."""
    gs, ge = np.r_[lo, me], np.r_[ms, hi]
    keep = ge > gs
    return gs[keep], ge[keep]


def label_gaps(gap_list, calls, host) -> tuple[np.ndarray, list[str]]:
    """What the host was doing in each idle gap, as (label index per gap,
    label names): the shortest host event that covers the gap's
    midpoint, else whether the gap fell inside an entry call or between
    two."""
    g = _pairs(gap_list)
    mid = 0.5 * (g[:, 0] + g[:, 1])
    order = np.argsort(mid, kind="stable")
    mids = mid[order]
    names = ["between calls", "bench.call (no host span)"]
    lab = np.zeros(mids.size, np.int64)
    for _, s, e in calls:
        lab[np.searchsorted(mids, s):np.searchsorted(mids, e)] = 1
    # longest first, so that the shortest covering event writes last
    for name, s, e in sorted(host, key=lambda h: h[1] - h[2]):
        a, b = np.searchsorted(mids, s), np.searchsorted(mids, e)
        if b > a:
            names.append(name)
            lab[a:b] = len(names) - 1
    out = np.empty(mids.size, np.int64)
    out[order] = lab
    return out, names


def is_collective(name: str) -> bool:
    return any(c in name.lower() for c in COLLECTIVES)


def reduce(events: dict, top: int = 10) -> dict:
    """Numbers shared by the metric readers (seconds unless named _ns):

    window_s, calls, call_s (list), busy_s (per device, in the window),
    busy_in_calls_s (per device), op_s (per device: name -> seconds in
    the window), collective_s (per device), device_ops (top names by
    seconds, averaged over devices), idle_gaps (top host labels by idle
    seconds, averaged over devices)."""
    calls = events["calls"]
    if not calls:
        return {}
    lo, hi = calls[0][1], calls[-1][2]
    call_iv = _pairs((s, e) for _, s, e in calls)
    out = {"window_s": (hi - lo) * 1e-9, "calls": len(calls),
           "call_s": [float(d) for d in (call_iv[:, 1] - call_iv[:, 0])
                      * 1e-9],
           "busy_s": {}, "busy_in_calls_s": {}, "op_s": {},
           "collective_s": {}}
    totals: dict[str, float] = {}
    idle: dict[str, float] = {}
    devs = events["devices"]
    for dev, ops in devs.items():
        st = np.asarray([o[1] for o in ops], np.float64)
        en = np.asarray([o[2] for o in ops], np.float64)
        ms, me = merge(st, en, lo, hi)
        out["busy_s"][dev] = float((me - ms).sum()) * 1e-9
        out["busy_in_calls_s"][dev] = float(
            (covered_below(ms, me, call_iv[:, 1])
             - covered_below(ms, me, call_iv[:, 0])).sum()) * 1e-9
        ids: dict[str, int] = {}
        inv = np.fromiter((ids.setdefault(o[0], len(ids)) for o in ops),
                          np.int64, len(ops))
        dur = np.clip(np.minimum(en, hi) - np.maximum(st, lo), 0.0, None)
        secs = np.bincount(inv, weights=dur, minlength=len(ids)) * 1e-9
        by_name = {k: float(secs[i]) for k, i in ids.items() if secs[i] > 0}
        out["op_s"][dev] = by_name
        out["collective_s"][dev] = sum(v for k, v in by_name.items()
                                       if is_collective(k))
        for k, v in by_name.items():
            totals[k] = totals.get(k, 0.0) + v / len(devs)
        gs, ge = gap_arrays(ms, me, lo, hi)
        lab, names = label_gaps(np.stack([gs, ge], 1), calls,
                                events["host"])
        per = np.bincount(lab, weights=(ge - gs) * 1e-9 / len(devs),
                          minlength=len(names))
        for name, v in zip(names, per):
            if v > 0:
                idle[name] = idle.get(name, 0.0) + float(v)
    out["device_ops"] = [[k, v] for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1])[:top]]
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        idle.items(), key=lambda kv: -kv[1])[:top]]
    return out


# ------------------------------------------------------------ metric helpers
def op_seconds(red: dict, match) -> float:
    """Seconds of the ops whose name satisfies `match`, summed over
    devices."""
    return sum(v for ops in red.get("op_s", {}).values()
               for k, v in ops.items() if match(k))


def mean_over_devices(per_device: dict) -> float | None:
    vals = list(per_device.values())
    return sum(vals) / len(vals) if vals else None


# device ops of each Pallas kernel: the HLO instruction of a Pallas
# call is named after the function that makes the call
KERNELS = {
    "replay": ("replay_blocks", "adaptive_blocks"),
    "charge_sim": ("margin_grid",),
}


def kernel_match(kernel: str):
    names = KERNELS[kernel]
    return lambda op: op.split(" ", 1)[0].rsplit(".", 1)[0] in names


def idle_share(red: dict) -> float | None:
    """Percent of the window in which no operation ran on a device,
    averaged over the devices."""
    busy = mean_over_devices(red.get("busy_s", {}))
    if busy is None or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - busy / red["window_s"])


def host_s_per_call(red: dict) -> float | None:
    """Seconds per call in which the call ran and no device was busy
    (call wall time minus device busy time inside the call, averaged
    over the devices)."""
    busy = mean_over_devices(red.get("busy_in_calls_s", {}))
    if busy is None or not red.get("calls"):
        return None
    return (sum(red["call_s"]) - busy) / red["calls"]


def kernel_s(red: dict, kernel: str) -> float:
    """Device seconds of a kernel in the window, summed over devices."""
    return op_seconds(red, kernel_match(kernel))
