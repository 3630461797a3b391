"""From a profiler trace to device time: busy and idle, time per device
operation and per program, collective time, and idle gaps named by what
the harness was doing.

``extract`` reads the profiler's ``.xplane.pb`` into plain lists (the
form the tests keep as recorded data); ``reduce`` works on those lists
alone.
"""

from __future__ import annotations

import re
from pathlib import Path

HOST_PREFIX = "bench."               # the harness's own spans
WINDOW_SPAN = "bench.window"         # the traced window
TEXT = 240                           # characters of an op's HLO text kept
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def _device_index(plane_name: str):
    m = re.match(r"/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def extract(trace_dir) -> dict:
    """The newest trace under ``trace_dir`` as plain lists: per TPU
    device its operations (named by the start of their HLO text, which
    carries the output's shape) and programs, ``[name, start_ns,
    dur_ns]``, and the harness's host spans."""
    from jax.profiler import ProfileData

    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(paths[-1]))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            d = out["devices"].setdefault(str(dev), {"ops": [],
                                                    "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    d[key].append([ev.name[:TEXT], int(ev.start_ns),
                                   int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    """``jit__fused_decode(123)`` -> ``_fused_decode``."""
    name = re.sub(r"\(.*\)$", "", name)
    return re.sub(r"^jit_", "", name)


_OP = re.compile(r"%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")


def parse_op(text: str):
    """``(kind, dtype, dims)`` of an op from its HLO text: the name
    without its instance number, the dtype and dims of its (first)
    output.  ``%twopass_softmax_2d.21 = f32[98304,3072]{...} custom-call``
    -> ``("twopass_softmax_2d", "f32", (98304, 3072))``."""
    m = _OP.match(text)
    if m is None:
        return re.sub(r"[.\d]+$", "", text.split(" ")[0]), None, ()
    dims = tuple(int(x) for x in m.group(3).split(",") if x)
    return re.sub(r"\.\d+$", "", m.group(1)), m.group(2), dims


def _innermost(spans, t):
    """The latest-starting host span that covers time ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "outside harness spans"


def _self_times(ops):
    """Each op's duration less the time its nested ops cover (a
    ``while`` holds its body's ops on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [o[2] for o in ops]
    stack = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][1]
                                  + ops[stack[-1]][2]) - s
        stack.append(i)
    return own


def reduce(tr: dict, device: str = "0") -> dict:
    """Device time in the traced window (``bench.window``), in seconds.

    ``window_s``; ``busy_s`` per device (the union of its op intervals)
    and their mean; on ``device``: ``op_s`` (self time per op kind),
    ``module_s`` (per program), ``calls`` (per (program, kind) of every
    custom call, the kernels: ``[seconds, dtype, dims]``),
    ``collective_s`` (per program) and ``gaps_s`` (idle time by the
    innermost harness span the host was in)."""
    import bisect

    win = [s for s in tr["host"] if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        ev = [e for d in tr["devices"].values() for e in d["ops"]]
        lo = min(e[1] for e in ev)
        hi = max(e[1] + e[2] for e in ev)
    busy = {}
    for dev, d in tr["devices"].items():
        busy[dev] = sum(e - s for s, e in _union(
            [(e[1], e[1] + e[2]) for e in d["ops"]], lo, hi)) * 1e-9
    d = tr["devices"][device]
    ops = [[o[0], o[1], min(o[2], hi - o[1])] for o in d["ops"]
           if lo <= o[1] < hi]                 # started in it, clipped
    own = _self_times(ops)
    mods = sorted((m[1], m[1] + m[2], module_name(m[0]))
                  for m in d["modules"])
    starts = [m[0] for m in mods]
    op_s, calls, coll = {}, {}, {}
    for op, t in zip(ops, own):
        kind, dtype, dims = parse_op(op[0])
        op_s[kind] = op_s.get(kind, 0.0) + t * 1e-9
        i = bisect.bisect_right(starts, op[1]) - 1
        mod = (mods[i][2] if i >= 0 and op[1] < mods[i][1]
               else "no program")
        if "custom-call(" in op[0]:
            calls.setdefault((mod, kind), []).append(
                [op[2] * 1e-9, dtype, dims])
        if any(c in kind for c in COLLECTIVES):
            coll[mod] = coll.get(mod, 0.0) + t * 1e-9
    module_s = {}
    for s, e, name in mods:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            module_s[name] = module_s.get(name, 0.0) + (e - s) * 1e-9
    gaps = {}
    merged = _union([(o[1], o[1] + o[2]) for o in d["ops"]], lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    host = [h for h in tr["host"] if h[0] != WINDOW_SPAN]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            label = _innermost(host, (a + b) // 2)
            gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    return dict(window_s=(hi - lo) * 1e-9, busy_s=busy,
                busy_mean_s=sum(busy.values()) / len(busy),
                op_s=op_s, module_s=module_s, calls=calls,
                collective_s=coll, gaps_s=gaps)


def breakdown(red: dict, top: int = 10) -> dict:
    """The device operations that took most time and the idle time by
    what the host was doing, as the result line carries them."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
