"""Reduction of a JAX profiler trace (``.xplane.pb``) to per-layer numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. The window is the
span from the first ``bench.batch`` host annotation to the last
``bench.read`` one (the benchmark's own spans around the batch, the step
call and the loss read; ``run.py`` writes them). On a TPU's device plane
the "XLA Modules" line holds one event per program execution and the "XLA
Ops" line one per executed HLO instruction, named by the instruction's
text ("%fusion.3 = f32[...] fusion(...)"). The op events carry no JAX
name stack, so the optimized step program's own text (``compiled.
as_text()``) gives it: each instruction's ``metadata={op_name=...}``.
Each op is put in one class:

  collective  all-to-all, all-gather, all-reduce, reduce-scatter,
              collective-permute (by the instruction's opcode)
  fwd_bwd     an op of a step execution whose name stack holds ``jvp(``
              or ``transpose(`` (forward and backward of the loss; the
              recomputed forward under ``remat`` too)
  optimizer   every other op of a step execution
  other       ops of other programs (the batch generator, reads)

while/conditional/call ops are left out: the ops they run are events of
their own. Device time of a class is the sum of its events' durations
inside the window, averaged over the devices; busy time is the union of
all events' intervals; the exposed part of the collectives is their time
during which no other op runs on that device.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

COLLECTIVE = re.compile(
    r"\b(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)"
    r"(-start|-done)?\(")
CONTAINER = re.compile(r"\b(while|conditional|call)\(")
HOST_SPANS = ("bench.batch", "bench.step", "bench.read")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def parse_hlo(hlo: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction: JAX name stack}) of an optimized HLO
    module's text (``compiled.as_text()``)."""
    m = re.match(r"\s*HloModule\s+([^\s,]+)", hlo)
    if not m:
        raise ValueError("not the text of an HLO module")
    stacks = {}
    for line in hlo.splitlines():
        i = _INSTR.match(line)
        if i:
            s = _OP_NAME.search(line)
            stacks[i.group(1)] = s.group(1) if s else ""
    return m.group(1), stacks


def instruction(event_name: str) -> str:
    """The HLO instruction an "XLA Ops" event ran ("%fusion.3 = ..." ->
    "fusion.3")."""
    return event_name.split(" ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    """A trace as the profiler wrote it (``.xplane.pb``), or an XSpace in
    text form (``.pbtxt``, optionally gzipped) as the tests keep one."""
    from jax.profiler import ProfileData
    if path.endswith((".pbtxt", ".pbtxt.gz")):
        import gzip
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # a stat of a type the binding cannot convert
        return {}


def device_planes(pd) -> list:
    """The accelerator planes ("/device:TPU:<n>"), in device order; other
    "/device:" planes (such as "/device:CUSTOM:...") hold no ops."""
    found = []
    for p in pd.planes:
        m = re.match(r"^/device:[A-Z]+:(\d+)$", p.name)
        if m and _line(p, "XLA Ops") is not None:
            found.append((int(m.group(1)), p))
    return [p for _, p in sorted(found, key=lambda x: x[0])]


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def host_spans(pd) -> List[Tuple[str, float, float]]:
    """(name, start_ns, end_ns) of the benchmark's host annotations."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda s: s[1])


def classify(event_name: str, in_step: bool, stacks: Dict[str, str]) -> str:
    """collective / fwd_bwd / optimizer / other / container (a while,
    conditional or call op, whose own ops are events of their own)."""
    if COLLECTIVE.search(event_name):
        return "collective"
    if CONTAINER.search(event_name):
        return "container"
    if not in_step:
        return "other"
    ns = stacks.get(instruction(event_name), "")
    if "jvp(" in ns or "transpose(" in ns:
        return "fwd_bwd"
    return "optimizer"


def _union(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    tot, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                tot += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            tot += e - cur
    return tot


def _inside(t: float, iv: List[Tuple[float, float]]) -> bool:
    lo, hi = 0, len(iv)
    while lo < hi:
        mid = (lo + hi) // 2
        if iv[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    return lo < len(iv) and iv[lo][0] <= t


def summarize(path: str, n_steps: int, n_devices: int, hlo: str) -> dict:
    """Per-step device time of each class, busy time, exposed collective
    time and the breakdown, in seconds, each averaged over the devices.
    ``hlo`` is the optimized step program's text: its module name finds
    the step's executions on the "XLA Modules" line, and its ops' JAX
    name stacks split forward/backward from the optimizer."""
    module, stacks = parse_hlo(hlo)
    pd = load(path)
    spans = host_spans(pd)
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    w0 = min(s for n, s, _ in spans if n == "bench.batch")
    w1 = max(e for n, _, e in spans if n == "bench.read")
    planes = device_planes(pd)[:n_devices]
    if not planes:
        raise ValueError("the trace holds no device plane")
    cls_ns = {"fwd_bwd": 0.0, "optimizer": 0.0, "collective": 0.0,
              "other": 0.0}
    busy_ns = exposed_ns = 0.0
    op_time: Dict[str, float] = {}
    gaps = []
    step_runs = 0
    for di, plane in enumerate(planes):
        mods = _line(plane, "XLA Modules")
        steps = _union((ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in (mods.events if mods is not None else [])
                       if ev.name.startswith(module + "(")
                       and w0 <= ev.start_ns < w1)
        step_runs += len(steps)
        coll, comp = [], []
        line = _line(plane, "XLA Ops")
        for ev in (line.events if line is not None else []):
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if e <= s:
                continue
            c = classify(ev.name, _inside(ev.start_ns, steps), stacks)
            if c == "container":
                continue
            cls_ns[c] += e - s
            (coll if c == "collective" else comp).append((s, e))
            key = instruction(ev.name)
            op_time[key] = op_time.get(key, 0.0) + (e - s)
        busy = _union(coll + comp)
        busy_ns += _length(busy)
        exposed_ns += _minus(_union(coll), _union(comp))
        if di == 0:
            prev = w0
            for s, e in busy + [(w1, w1)]:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
    nd = float(len(planes))
    per_step = {k: v / nd / n_steps * 1e-9 for k, v in cls_ns.items()}
    gap_rows = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = 0.5 * (s + e)
        doing = "idle"
        for n, hs, he in spans:
            if hs <= mid < he:
                doing = n
        gap_rows.append([doing, (e - s) * 1e-9])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    label = lambda i: f"{i} {stacks.get(i, '')[-90:]}".strip()
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / nd * 1e-9,
        "per_step_s": per_step,
        "exchange_exposed_s": exposed_ns / nd / n_steps * 1e-9,
        "step_module": module,
        "step_runs": step_runs / nd,
        "breakdown": {"device_ops": [[label(n), t / nd * 1e-9]
                                     for n, t in top],
                      "idle_gaps": gap_rows},
    }
