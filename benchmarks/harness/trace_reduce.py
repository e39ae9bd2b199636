"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
per-layer metrics read: device busy and idle time, device time by
``jax.named_scope`` region, time inside collectives, and the idle gaps
attributed to what the host was doing.

Works on plain event tuples so that a small recorded trace (kept with
the tests as JSON) checks the arithmetic without a chip.

An *event* is ``(name, start_ns, duration_ns, scope)``: ``name`` the HLO
operation, ``scope`` the operation's framework name (the ``tf_op`` stat:
``jit(step)/jit(main)/npair/sim/dot_general``) or "".
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
HOST_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
_JIT = re.compile(r"\b(?:jit|pjit)\([^/()]*\)/?")
_OPEN = re.compile(r"\b\w+\(")


SCOPE_STATS = ("tf_op", "long_name", "name")


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    return paths[-1]


def short_name(text: str) -> str:
    """``%fusion.7 = f32[..] fusion(...)`` -> ``fusion.7``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(trace_dir: str):
    """{"devices": {index: [event]}, "host": [(name, start, dur)]} from the
    newest xplane file under ``trace_dir``."""
    from benchmarks.harness import xplane

    devices, host = {}, []
    for plane in xplane.read(newest_xplane(trace_dir)):
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            events = []
            for line in plane["lines"]:
                if line["name"] != OPS_LINE:
                    continue
                for name, start, dur, stats in line["events"]:
                    scope = next((stats[k] for k in SCOPE_STATS
                                  if isinstance(stats.get(k), str) and "/" in stats[k]), "")
                    events.append((short_name(name), start, dur, scope))
            devices[int(m.group(1))] = events
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for name, start, dur, _stats in line["events"]:
                    if name.startswith(HOST_PREFIX):
                        host.append((name, start, dur))
    return {"devices": devices, "host": host}


def region_of(scope: str) -> str:
    """The ``named_scope`` path of an operation: its framework name with
    the ``jit(name)`` segments, the transform wrappers (``jvp(``,
    ``transpose(`` ...) and the trailing primitive dropped:
    ``jit(step)/jit(main)/transpose(jvp(npair/sim))/dot_general`` is
    ``npair/sim``."""
    s = _JIT.sub("", scope)
    s = _OPEN.sub("", s).replace(")", "")
    parts = [p for p in s.split("/") if p]
    return "/".join(parts[:-1]) if len(parts) > 1 else ""


def union(intervals):
    """Merged, sorted [(start, end)] of possibly nested intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(events, lo, hi):
    """Events cut to the window [lo, hi)."""
    out = []
    for name, start, dur, *rest in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a, *rest))
    return out


def window_of(trace):
    """[lo, hi) of the ``bench/window`` host span, else the devices' span."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return spans[-1]
    every = [e for evs in trace["devices"].values() for e in evs]
    if not every:
        return (0, 0)
    return (min(e[1] for e in every), max(e[1] + e[2] for e in every))


def leaf_events(events):
    """Drop events that merely contain others (a ``while`` around its
    body), so that summed time counts each instant once."""
    out, stack = [], []  # stack of [event, contains another]

    def close(until):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= until:
            ev, parent = stack.pop()
            if not parent:
                out.append(ev)

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        close(ev[1])
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    close(float("inf"))
    return out


def reduce(trace, match_groups=None):
    """The whole reduction.  ``match_groups`` maps a group name to a list
    of region prefixes; an operation belongs to the first group one of
    whose prefixes occurs in its region ("rest" otherwise)."""
    lo, hi = window_of(trace)
    window_s = max(hi - lo, 0) / 1e9
    per_device, busy = {}, []
    for idx, events in sorted(trace["devices"].items()):
        evs = clip(events, lo, hi)
        merged = union([(s, s + d) for _, s, d, *_ in evs])
        per_device[idx] = {"events": evs, "busy": merged,
                           "busy_s": sum(b - a for a, b in merged) / 1e9}
        busy.append(per_device[idx]["busy_s"])
    if not per_device:
        return None
    fullest = max(per_device, key=lambda i: per_device[i]["busy_s"])
    dev = per_device[fullest]
    leaves = leaf_events(dev["events"])
    groups = {g: 0.0 for g in (match_groups or {})}
    groups["rest"] = 0.0
    by_op, collective_s = {}, 0.0
    for name, _s, dur, scope in leaves:
        region = region_of(scope)
        label = f"{region}/{name}" if region else name
        by_op[label] = by_op.get(label, 0.0) + dur / 1e9
        if COLLECTIVE.match(name):
            collective_s += dur / 1e9
        for g, prefixes in (match_groups or {}).items():
            if any(p in region + "/" for p in prefixes):
                groups[g] += dur / 1e9
                break
        else:
            groups["rest"] += dur / 1e9
    gaps = []
    edge = lo
    for a, b in dev["busy"] + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    by_host = {}
    for a, b in gaps:
        best, best_ov = "unannotated", 0
        for n, s, d in trace["host"]:
            if n == WINDOW_SPAN:
                continue
            ov = min(b, s + d) - max(a, s)
            if ov > best_ov:
                best, best_ov = n, ov
        by_host[best] = by_host.get(best, 0.0) + (b - a) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_fullest": dev["busy_s"],
        "idle_share": 1.0 - dev["busy_s"] / window_s if window_s else None,
        "group_s": groups,
        "collective_s": collective_s,
        "by_op": by_op,
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(by_host)},
        "devices": len(per_device),
    }


def describe(trace_dir: str, limit: int = 8) -> str:
    """A look at a trace by hand: planes, lines, a few events with stats."""
    from benchmarks.harness import xplane

    out = []
    for plane in xplane.read(newest_xplane(trace_dir)):
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            out.append(f"  LINE {line['name']} events={len(line['events'])}")
            for name, start, dur, stats in line["events"][:limit]:
                shown = {k: (v if not isinstance(v, (str, bytes)) else str(v)[:160])
                         for k, v in stats.items()}
                out.append(f"    {name[:90]} start={start} dur={dur} {shown}")
    return "\n".join(out)
