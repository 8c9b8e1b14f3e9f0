"""From a profiler trace to the device numbers of a traced run.

`load(path)` reads the `.xplane.pb` the JAX profiler wrote into a plain
record: for each TPU device plane the events of its "XLA Ops" line (HLO
op name, start, duration, and whether the op is a Mosaic kernel, i.e. a
Pallas `tpu_custom_call`),
and the benchmark's own host spans (`scan.verify`, `scan.outside`,
written with `jax.profiler.TraceAnnotation`) on the same clock.
`reduce(events)` turns that record into busy and idle time, Mosaic and
XLA op time, the ops that took most time and the longest idle gaps with
what the host was doing in them.  Both are kept with the benchmark so
that every run reduces a trace the same way.
"""

import glob
import json
import os

HOST_SPANS = ("scan.verify", "scan.outside")
OPS_LINE = "XLA Ops"


def op_name(hlo_text: str) -> str:
    """An op event's name is its HLO instruction; keep the part before
    ` = ` (`%fusion.12`, `%custom-call.3`)."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def is_mosaic(hlo_text: str) -> bool:
    """A Pallas kernel: an op whose HLO is a `tpu_custom_call`."""
    return "tpu_custom_call" in hlo_text


def load(trace_dir: str) -> dict:
    """Read the newest `.xplane.pb` under `trace_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, seen = [], {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = ev.name
                    info = seen.get(text)
                    if info is None:
                        info = seen[text] = (op_name(text), is_mosaic(text))
                    ops.append((info[0], ev.start_ns, ev.duration_ns,
                                info[1]))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    return {"devices": devices, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(spans):
    """[(start, end, name, mosaic)] -> [(name, mosaic, self time)]: an op
    that encloses others on its line (a `while` around its body) keeps
    only the time none of them covers, so times add up to busy time."""
    out, stack = [], []     # stack: [end, name, mosaic, start, covered]

    def close(top):
        out.append((top[1], top[2], max(0.0, top[0] - top[3] - top[4])))

    for a, b, name, mosaic in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][4] += min(b, stack[-1][0]) - a
        stack.append([b, name, mosaic, a, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce(events: dict) -> dict:
    """-> busy_s, window_s, pallas_s, xla_s (self times, each averaged
    over the devices), the ops with most self time and the longest idle
    gaps.  The window is the span of the benchmark's host spans (the
    measured window); ops are clipped to it."""
    host = events["host"]
    if not host:
        raise ValueError("trace holds none of the benchmark's host spans")
    w0 = min(s for _, s, _ in host)
    w1 = max(s + d for _, s, d in host)
    devs = events["devices"]
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    busy = pallas = xla = 0.0
    per_op = {}
    gaps = []
    for ops in devs.values():
        spans = []
        for name, s, d, mosaic in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                spans.append((a, b, name, mosaic))
        for name, mosaic, t in _self_times(spans):
            if mosaic:
                pallas += t
            else:
                xla += t
            per_op[name] = per_op.get(name, 0.0) + t
        merged = _union([(a, b) for a, b, _, _ in spans])
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for i in range(0, len(edges), 2):
            a, b = edges[i], edges[i + 1]
            if b > a:
                gaps.append((a, b))
    n = len(devs)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, key=lambda ab: ab[0] - ab[1])[:10]
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "pallas_s": pallas / n * 1e-9,
        "xla_s": xla / n * 1e-9,
        "device_ops": [[k, v * 1e-9] for k, v in top],
        "idle_gaps": [[_host_at(host, a, b, w0), (b - a) * 1e-9]
                      for a, b in longest],
    }


def _host_at(host, a: float, b: float, w0: float) -> str:
    """The host span that covers most of [a, b), with the gap's offset
    into the window."""
    best, cover = "none", 0.0
    for name, s, d in host:
        c = min(b, s + d) - max(a, s)
        if c > cover:
            best, cover = name, c
    return f"{best}@{(a - w0) * 1e-9:.3f}s"


def load_events(path: str) -> dict:
    """A record as `load` returns it, kept as JSON (the tests' data)."""
    with open(path) as f:
        return json.load(f)
