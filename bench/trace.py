"""From a profiler trace to numbers: device busy time, idle share, the time
of convolution operations, the operations that took most time, and the
longest idle gaps labelled by what the host was doing.

A trace is first flattened to plain events (``load``), so the reduction
(``reduce``) can be checked on a small recorded trace without a chip:

    {"device": [[start_ns, dur_ns, name, category], ...] per device,
     "host":   [[start_ns, dur_ns, name], ...]}

Device events are the operations on each TPU's "XLA Ops" line, named by
their HLO instruction and categorised by XLA (``hlo_category``, held in
the event metadata, which ``jax.profiler.ProfileData`` does not expose:
``load`` decodes the ``XSpace`` protobuf itself); host events are the
benchmark's own ``bench.*`` annotations.  Both share the trace's clock.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

WINDOW = "bench.window"
# operations that only hold others (a loop's body runs as its own ops)
CONTAINERS = ("while", "conditional", "call")


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield f, wt, v


def _varint(buf: bytes, i: int):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _map(entries) -> Dict[int, bytes]:
    out = {}
    for e in entries:
        kv = {f: v for f, _, v in _fields(e)}
        out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def _planes(xspace: bytes):
    """(name, lines, event metadata, stat metadata) of each plane of a
    serialized ``XSpace`` (tsl/profiler/protobuf/xplane.proto)."""
    for f, _, plane in _fields(xspace):
        if f != 1:
            continue
        name, lines, ev_md, st_md = "", [], [], []
        for g, _, v in _fields(plane):
            if g == 2:
                name = v.decode()
            elif g == 3:
                lines.append(v)
            elif g == 4:
                ev_md.append(v)
            elif g == 5:
                st_md.append(v)
        yield name, lines, _map(ev_md), _map(st_md)


def _event_names(ev_md: Dict[int, bytes], st_md: Dict[int, bytes]):
    """Event metadata id -> (name, hlo_category)."""
    stat_names = {}
    for k, v in st_md.items():
        stat_names[k] = next((x.decode() for g, _, x in _fields(v) if g == 2),
                             "")
    out = {}
    for k, v in ev_md.items():
        name, cat = "", ""
        for g, _, x in _fields(v):
            if g == 2:
                name = x.decode(errors="replace")
            elif g == 5:
                st = {h: y for h, _, y in _fields(x)}
                if stat_names.get(st.get(1)) == "hlo_category":
                    if 5 in st:                      # str_value
                        cat = st[5].decode()
                    elif 7 in st:                    # ref_value
                        cat = stat_names.get(st[7], "")
        out[k] = (name, cat)
    return out


def _line_events(line: bytes):
    """(line name, [(start_ns, dur_ns, metadata id)])."""
    name, t0, evs = "", 0, []
    for g, _, v in _fields(line):
        if g == 2:
            name = v.decode()
        elif g == 3:
            t0 = v
        elif g == 4:
            ev = {h: y for h, _, y in _fields(v)}
            evs.append((t0 + ev.get(2, 0) / 1000.0, ev.get(3, 0) / 1000.0,
                        ev.get(1, 0)))
    return name, evs


def load(trace_dir: str) -> Dict[str, Any]:
    """Flatten the newest ``*.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        xspace = f.read()
    device: List[List[list]] = []
    host: List[list] = []
    for name, lines, ev_md, st_md in _planes(xspace):
        is_dev = name.startswith("/device:TPU:")
        if not (is_dev or name.startswith("/host:")):
            continue
        names = _event_names(ev_md, st_md)
        ops = []
        for line in lines:
            lname, evs = _line_events(line)
            if is_dev and lname != "XLA Ops":
                continue
            for s, d, mid in evs:
                ename, cat = names.get(mid, ("", ""))
                if is_dev:
                    ops.append([s, d, ename.split(" = ")[0], cat])
                elif ename.startswith("bench."):
                    host.append([s, d, ename])
        if is_dev:
            device.append(ops)
    return dict(device=device, host=host)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _label(host: List[list], t: float, window: Tuple[float, float]) -> str:
    """The innermost benchmark annotation (other than the window) active
    at time ``t``."""
    best = None
    for s, d, name in host:
        if name == WINDOW or not (s <= t <= s + d):
            continue
        if best is None or d < best[1]:
            best = (s, d, name)
    return best[2] if best else "bench.none"


def is_conv(category: str, name: str) -> bool:
    return "convolution" in category.lower() or (
        not category and name.startswith("convolution"))


def reduce(events: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Numbers over the ``bench.window`` annotation: ``window_s``,
    ``busy_s`` (union of device-op intervals, averaged over devices),
    ``conv_s`` (summed duration of convolution operations, averaged over
    devices), and ``breakdown``: the operations that took most time (loop
    containers left out, their bodies counted) and the longest idle gaps,
    each labelled by the innermost ``bench.*`` annotation over it and its
    start within the window.  None when the trace holds no window or
    no device operation in it."""
    wins = [(s, s + d) for s, d, n in events["host"] if n == WINDOW]
    devices = [d for d in events["device"] if d]
    if not wins or not devices:
        return None
    w0, w1 = wins[0]
    busy_total, conv_total = 0.0, 0.0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for d_i, ops in enumerate(devices):
        ivs = []
        for s, d, name, cat in ops:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            ivs.append((s0, e0))
            if is_conv(cat, name):
                conv_total += e0 - s0
            if cat not in CONTAINERS:
                key = f"{name} [{cat}]" if cat else name
                per_op[key] = per_op.get(key, 0.0) + (e0 - s0)
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged)
        if d_i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n = len(devices)
    if busy_total <= 0:
        return None
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gap_by_label: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        lab = _label(events["host"], (s + e) / 2, (w0, w1))
        gap_by_label.setdefault(f"{lab}@{(s - w0) / 1e9:.6f}s", (e - s) / 1e9)
    return dict(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy_total / n / 1e9,
        conv_s=conv_total / n / 1e9,
        breakdown=dict(
            device_ops=[[k, v / n / 1e9] for k, v in ops_top],
            idle_gaps=[[k, v] for k, v in gap_by_label.items()]))
