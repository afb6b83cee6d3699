"""Reduction of a profiler trace to device busy time, idle gaps and
per-kernel device time.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain data (lists of dicts), so the reduction below can be tested on a
small recorded trace (``bench/tests/data/trace.json.gz``). What a TPU
trace holds, as read by hand:

- each chip is a plane ``/device:TPU:<n>``. Its line ``XLA Ops`` has one
  event per HLO instruction run, named by the instruction's text
  (``%fg_gemm_integer_scale.26 = f32[32,4096]... custom-call(...)``).
  A Pallas kernel is a ``custom-call`` named after its function. Events
  nest: a ``while`` (the scan over layers) spans the ops of its body.
  Its line ``XLA Modules`` has one event per program run
  (``jit_decode_fn(<fingerprint>)``), spanning that program's ops.
- the host plane ``/host:CPU`` holds the harness's own spans
  (``jax.profiler.TraceAnnotation``): ``bench.window`` spans the
  measured window, and ``bench.step``, ``bench.collect``,
  ``bench.submit`` and ``bench.idle`` say what the host was doing.

Only events that overlap the window are kept; times are ns on the
profile's clock.
"""
from __future__ import annotations

import glob
import gzip
import json
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"%([^ ]+?)(?:\.(?:\d+|clone))* = ")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] ...`` -> ``fusion`` (also
    ``%broadcast.31.clone = ...`` -> ``broadcast``); a module
    ``jit_decode_fn(123)`` -> ``jit_decode_fn``."""
    m = _INSTR.match(text)
    if m:
        return m.group(1)
    return text.split("(")[0]


def _newest(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(log_dir: str) -> dict:
    """-> {"window_ns": (start, stop), "events": [...]} from the newest
    trace under ``log_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_newest(log_dir))
    host, device = [], []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_dev and line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                if is_dev:
                    device.append({
                        "plane": plane.name, "line": line.name,
                        "name": op_name(ev.name),
                        "custom": " custom-call(" in ev.name,
                        "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns)})
                elif ev.name.startswith("bench."):
                    host.append({"plane": plane.name, "line": line.name,
                                 "name": ev.name,
                                 "start_ns": float(ev.start_ns),
                                 "dur_ns": float(ev.duration_ns)})
    spans = [e for e in host if e["name"] == WINDOW_SPAN]
    if spans:
        w = spans[0]
        window = (w["start_ns"], w["start_ns"] + w["dur_ns"])
    else:
        ends = [e["start_ns"] + e["dur_ns"] for e in device + host]
        window = (min(e["start_ns"] for e in device + host), max(ends))
    lo, hi = window
    events = [e for e in device + host
              if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo]
    return {"window_ns": window, "events": events}


def describe(log_dir: str) -> str:
    """Every plane and line of the newest trace under ``log_dir``, with
    a few of each line's events and their stats: for reading by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_newest(log_dir))
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r} stats={dict(plane.stats)!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:8]:
                out.append(f"    {ev.name[:300]!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} stats={dict(ev.stats)!r}")
    return "\n".join(out)


def dump(trace: dict, path) -> None:
    """Write a loaded trace as gzipped JSON (:func:`load_dump` reads it)."""
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load_dump(path) -> dict:
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    trace["window_ns"] = tuple(trace["window_ns"])
    return trace


def ops(trace: dict, plane: str | None = None) -> list:
    return [e for e in trace["events"] if e.get("line") == OPS
            and (plane is None or e["plane"] == plane)]


def modules(trace: dict, plane: str) -> list:
    return [e for e in trace["events"] if e.get("line") == MODULES
            and e["plane"] == plane]


def host_spans(trace: dict) -> list:
    return [e for e in trace["events"]
            if not e["plane"].startswith("/device:")]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(trace, e):
    lo, hi = trace["window_ns"]
    return max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)


def busy(trace: dict) -> dict:
    """Busy seconds per device plane: the union of its op intervals,
    clipped to the window."""
    per: dict = {}
    for e in ops(trace):
        s, t = _clip(trace, e)
        if t > s:
            per.setdefault(e["plane"], []).append((s, t))
    return {p: sum(b - a for a, b in _union(iv)) / 1e9
            for p, iv in per.items()}


def self_seconds(trace: dict, plane: str) -> dict:
    """{op name: seconds} of each op's own time inside the window: an
    op's span less the spans of the ops nested in it (a ``while`` keeps
    only what its body's ops leave uncovered)."""
    evs = sorted((_clip(trace, e) + (e["name"],) for e in ops(trace, plane)),
                 key=lambda x: (x[0], -x[1]))
    out: dict = {}
    stack: list = []  # [end, name, start, covered by nested ops]

    def close(item):
        end, name, start, kids = item
        out[name] = out.get(name, 0.0) + max(0.0, end - start - kids)
        if stack:
            stack[-1][3] += end - start

    for s, t, name in evs:
        if t <= s:
            continue
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([t, name, s, 0.0])
    while stack:
        close(stack.pop())
    return {k: v / 1e9 for k, v in out.items()}


def module_seconds(trace: dict, plane: str, module: str) -> float:
    """Device seconds of the runs of program ``module``, clipped to the
    window."""
    return sum(max(0.0, b - a) for a, b in (
        _clip(trace, m) for m in modules(trace, plane)
        if m["name"] == module)) / 1e9


def kernel_seconds(trace: dict, plane: str, *, kernel: str,
                   module: str) -> tuple[float, int]:
    """Device seconds of the custom call ``kernel`` inside the runs of
    the program named ``module`` that lie wholly in the window, and how
    many such runs there were."""
    lo, hi = trace["window_ns"]
    runs = [(m["start_ns"], m["start_ns"] + m["dur_ns"])
            for m in modules(trace, plane) if m["name"] == module
            and m["start_ns"] >= lo and m["start_ns"] + m["dur_ns"] <= hi]
    calls = sorted((e["start_ns"], e["dur_ns"]) for e in ops(trace, plane)
                   if e["custom"] and e["name"] == kernel)
    total, i = 0.0, 0
    for a, b in sorted(runs):
        while i < len(calls) and calls[i][0] < a:
            i += 1
        while i < len(calls) and calls[i][0] < b:
            total += calls[i][1]
            i += 1
    return total / 1e9, len(runs)


def idle_gaps(trace: dict, plane: str) -> list:
    """[(start_ns, end_ns, host label)] gaps between the device's ops in
    the window, labelled by the innermost ``bench.*`` host span (other
    than the window's) that covers the gap's middle, or ``"no host
    span"``."""
    lo, hi = trace["window_ns"]
    busy_iv = _union(_clip(trace, e) for e in ops(trace, plane))
    edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
    spans = sorted((s for s in host_spans(trace)
                    if s["name"] != WINDOW_SPAN), key=lambda e: e["dur_ns"])
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        label = next((s["name"] for s in spans
                      if s["start_ns"] <= mid <= s["start_ns"]
                      + s["dur_ns"]), "no host span")
        out.append((a, b, label))
    return out


def breakdown(trace: dict, plane: str, top: int = 10) -> dict:
    """The device operations with most self time in the window, and the
    longest idle gaps labelled by what the host was doing, each as
    ``[name, seconds]``."""
    own = sorted(self_seconds(trace, plane).items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_gaps(trace, plane), key=lambda g: g[0] - g[1])
    return {"device_ops": [[n, t] for n, t in own[:top]],
            "idle_gaps": [[lab, (b - a) / 1e9] for a, b, lab in gaps[:top]]}
