"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
time per program and per kernel, and the run's ``breakdown``.

Device planes are those named ``/device:<platform>:<n>``; their ``XLA
Ops`` line holds one event per operation run and their ``XLA Modules``
line one event per program run. Host spans are the events of the
thread that opened the ``bench.window`` annotation: the benchmark's own
``bench.*`` spans, the program's ``serve.*`` annotations and JAX's
dispatch events. Times are nanoseconds on the trace's clock; the window
is the ``bench.window`` span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


@dataclass
class Ev:
    name: str
    start: float  # ns
    end: float
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    window: tuple  # (start, end) ns
    ops: dict  # device index → [Ev] on the "XLA Ops" line
    modules: dict  # device index → [Ev] on the "XLA Modules" line
    host: list  # [Ev] of the window's thread
    layout: dict  # plane → {line: count}, for reading by hand


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> list:
    out = []
    for e in line.events:
        try:
            stats = {k: v for k, v in e.stats}
        except Exception:
            stats = {}
        out.append(Ev(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns), stats))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, layout = {}, {}, {}
    host_lines = []
    for plane in pd.planes:
        lines = list(plane.lines)
        layout[plane.name] = {ln.name: sum(1 for _ in ln.events) for ln in lines}
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(2))
            for ln in lines:
                if ln.name == "XLA Ops":
                    ops[idx] = _events(ln)
                elif ln.name == "XLA Modules":
                    modules[idx] = _events(ln)
        elif plane.name.startswith("/host:"):
            host_lines.extend(lines)
    host, window = [], None
    for ln in host_lines:
        evs = _events(ln)
        win = [e for e in evs if e.name == WINDOW_SPAN]
        if win:
            host = evs
            window = (win[0].start, win[0].end)
            break
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return Trace(window, ops, modules, host, layout)


def clip(evs, window) -> list:
    a, b = window
    return [Ev(e.name, max(e.start, a), min(e.end, b), e.stats) for e in evs if e.end > a and e.start < b]


def union(intervals) -> list:
    """Merged (start, end) pairs of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(evs, window) -> float:
    return sum(e - s for s, e in union((x.start, x.end) for x in clip(evs, window)))


def device_busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not tr.ops:
        return 0.0
    return sum(busy_ns(v, tr.window) for v in tr.ops.values()) / len(tr.ops) / 1e9


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def op_name(ev: Ev) -> str:
    """An operation's HLO name without its instance number: the device
    line names an event by its whole HLO text (``%fusion.12 = ...``)."""
    head = ev.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", head)


def module_base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def module_of(tr: Trace, device: int = 0) -> dict:
    """id(op event) → the program (module event) it ran in: a device runs
    one program at a time, so the module whose interval holds the op's
    start."""
    mods = sorted(tr.modules.get(device, []), key=lambda e: e.start)
    starts = [m.start for m in mods]
    out = {}
    for e in tr.ops.get(device, []):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < mods[i].end:
            out[id(e)] = mods[i]
    return out


def leaves(evs) -> list:
    """The operations that hold no other: a ``while`` or a ``call``
    spans the operations of its body on the same line."""
    evs = sorted(evs, key=lambda e: (e.start, -e.end))
    return [e for i, e in enumerate(evs) if i + 1 == len(evs) or evs[i + 1].start >= e.end]


def is_kernel(ev: Ev) -> bool:
    """A Pallas kernel: the device runs it as a TPU custom call."""
    return KERNEL_CALL in ev.name


def op_label(ev: Ev, module: Ev | None = None) -> str:
    """An operation's name with its program's."""
    base = op_name(ev)
    return f"{module_base(module.name)}/{base}" if module is not None else base


def modules_matching(tr: Trace, pattern: str, device: int = 0) -> list:
    rx = re.compile(pattern)
    return [e for e in clip(tr.modules.get(device, []), tr.window) if rx.search(e.name)]


def ops_matching(tr: Trace, pred, device: int = 0) -> list:
    return [e for e in clip(tr.ops.get(device, []), tr.window) if pred(e)]


def kernel_modules(tr: Trace, device: int = 0) -> list:
    """The programs, inside the window, that ran a Pallas kernel."""
    where = module_of(tr, device)
    hit = {id(where[id(e)]) for e in tr.ops.get(device, []) if is_kernel(e) and id(e) in where}
    mods = [m for m in tr.modules.get(device, []) if id(m) in hit]
    return clip(mods, tr.window)


def host_labels(tr: Trace, times) -> list:
    """The innermost host span open at each of ``times`` (ns), other
    than the window itself. A thread's spans nest, so one sweep with a
    stack of open spans finds them all."""
    evs = sorted(
        (e for e in tr.host if e.name != WINDOW_SPAN), key=lambda e: (e.start, -e.end)
    )
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = ["host:none"] * len(times)
    stack, j = [], 0
    for i in order:
        t = times[i]
        while j < len(evs) and evs[j].start <= t:
            while stack and stack[-1].end <= evs[j].start:
                stack.pop()
            stack.append(evs[j])
            j += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        if stack:
            out[i] = stack[-1].name
    return out


def _gaps(tr: Trace, device: int) -> list:
    """(length, start, end) of every idle interval in the window."""
    ops = clip(tr.ops.get(device, []), tr.window)
    gaps = []
    prev = tr.window[0]
    for s, e in union((x.start, x.end) for x in ops) + [[tr.window[1], tr.window[1]]]:
        if s > prev:
            gaps.append((s - prev, prev, s))
        prev = max(prev, e)
    return gaps


def breakdown(tr: Trace, device: int = 0, top: int = 10) -> dict:
    """Top device operations by time, and the longest idle gaps, each
    labelled by the host span open at its middle."""
    per = defaultdict(float)
    where = module_of(tr, device)
    for e in leaves(tr.ops.get(device, [])):
        c = clip([e], tr.window)
        if c:
            per[op_label(e, where.get(id(e)))] += c[0].dur / 1e9
    device_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(_gaps(tr, device), reverse=True)[:top]
    labels = host_labels(tr, [(a + b) / 2 for _, a, b in gaps])
    return {
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[lab, d / 1e9] for lab, (d, _, _) in zip(labels, gaps)],
    }


def idle_by_label(tr: Trace, device: int = 0) -> dict:
    """Idle seconds in the window, summed by the host span open at each
    gap's middle."""
    gaps = _gaps(tr, device)
    out = defaultdict(float)
    for lab, (d, _, _) in zip(host_labels(tr, [(a + b) / 2 for _, a, b in gaps]), gaps):
        out[lab] += d / 1e9
    return dict(out)


def inventory(tr: Trace, device: int = 0, top: int = 12) -> dict:
    """What the trace holds, for reading by hand: its programs by count
    and mean milliseconds, and its kernels' names."""
    mods = defaultdict(list)
    for e in clip(tr.modules.get(device, []), tr.window):
        mods[module_base(e.name)].append(e.dur)
    common = sorted(mods.items(), key=lambda kv: -sum(kv[1]))[:top]
    kernels = sorted({op_name(e) for e in tr.ops.get(device, []) if is_kernel(e)})
    return {
        "modules": {k: [len(v), sum(v) / len(v) / 1e6] for k, v in common},
        "kernels": kernels,
    }
