"""What the program's own tracing adds to a trace: the step program's
scopes and the host's compile counters (``repro.telemetry``).

- ``optimizer_scopes`` splits the ops that ``trace.classify`` calls
  ``optimizer`` by their scope (``hlo_scopes``: the innermost
  ``opt.*``/``model.*`` scope of the op's JAX name stack, which the
  program sets with ``jax.named_scope``). Ops with no scope are
  ``unscoped``. The values sum to ``per_step_s["optimizer"]`` of
  ``trace.summarize`` on the same trace. ``unscoped_ops`` lists those
  ops by instruction and opcode.
- ``compile_window`` reads the program's ``CompileCounters`` over the
  traced window of ``ctx["spans"]`` (host ``perf_counter`` seconds).
- ``anchor_offsets_ns`` and ``idle_in_compile_share`` put the counters'
  wall-clock spans on the trace's clock by the program's
  ``telemetry.anchor`` spans, and measure how much of device 0's idle
  time in the window lies inside a compile-or-load or lowering span.

``optimizer_scopes`` and ``compile_window`` return None where the program
holds nothing to read (a program older than its scopes and counters);
the rest needs ``repro.telemetry``.
"""
from __future__ import annotations

import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import trace as TR

try:
    from repro import telemetry
except ImportError:      # a program older than its tracing
    telemetry = None

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%([\w.\-]+)")
_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = \S+ ([\w-]+)\(", re.M)


def hlo_scopes(hlo: str) -> Dict[str, Optional[str]]:
    """{instruction: scope or None} of an optimized HLO module's text. A
    fusion whose own metadata names no scope (XLA gives the fusion its
    root's, and a convert or copy XLA made has none) takes the scope of
    the last scoped instruction of the computation it calls."""
    _, stacks = TR.parse_hlo(hlo)
    inner, calls, comp = {}, {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        i = TR._INSTR.match(line)
        if not i:
            continue
        sc = telemetry.innermost_scope(stacks.get(i.group(1), ""))
        if sc:
            inner[comp] = sc
        c = _CALLS.search(line)
        if c:
            calls[i.group(1)] = c.group(1)
    out = {}
    for name, stack in stacks.items():
        sc = telemetry.innermost_scope(stack)
        out[name] = sc or inner.get(calls.get(name))
    return out


def _window(pd) -> Tuple[float, float]:
    spans = TR.host_spans(pd)
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    return (min(s for n, s, _ in spans if n == "bench.batch"),
            max(e for n, _, e in spans if n == "bench.read"))


def optimizer_events(path: str, n_devices: int, hlo: str):
    """(instruction, device ns inside the window) of each event of an op
    that ``trace.classify`` calls ``optimizer``, on the first
    ``n_devices`` devices, windowed and clipped as ``trace.summarize``
    does; and the number of device planes read."""
    module, stacks = TR.parse_hlo(hlo)
    pd = TR.load(path)
    w0, w1 = _window(pd)
    planes = TR.device_planes(pd)[:n_devices]
    if not planes:
        raise ValueError("the trace holds no device plane")
    out = []
    for plane in planes:
        mods = TR._line(plane, "XLA Modules")
        steps = TR._union((ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in (mods.events if mods is not None else [])
                          if ev.name.startswith(module + "(")
                          and w0 <= ev.start_ns < w1)
        line = TR._line(plane, "XLA Ops")
        for ev in (line.events if line is not None else []):
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if e > s and TR.classify(ev.name, TR._inside(ev.start_ns, steps),
                                     stacks) == "optimizer":
                out.append((TR.instruction(ev.name), e - s))
    return out, len(planes)


def optimizer_scopes(path: str, n_steps: int, n_devices: int,
                     hlo: str) -> Optional[Dict[str, float]]:
    """Device seconds per step of the optimizer class's ops, by scope,
    averaged over the devices. None where no op of the step program
    carries a scope."""
    if telemetry is None:
        return None
    scope = hlo_scopes(hlo)
    if not any(scope.values()):
        return None
    events, nd = optimizer_events(path, n_devices, hlo)
    out: Dict[str, float] = {}
    for name, ns in events:
        key = scope.get(name) or "unscoped"
        out[key] = out.get(key, 0.0) + ns
    return {k: v / nd / n_steps * 1e-9 for k, v in sorted(out.items())}


def unscoped_ops(path: str, n_steps: int, n_devices: int,
                 hlo: str) -> Dict[str, Tuple[str, float]]:
    """{instruction: (opcode, device seconds per step)} of the optimizer
    class's ops that no scope claims: ops XLA made (copies, zero fills,
    casts), which carry no JAX name stack."""
    scope = hlo_scopes(hlo)
    opcode = dict(_OPCODE.findall(hlo))
    events, nd = optimizer_events(path, n_devices, hlo)
    out: Dict[str, float] = {}
    for name, ns in events:
        if not scope.get(name):
            out[name] = out.get(name, 0.0) + ns / nd / n_steps * 1e-9
    return {k: (opcode.get(k, ""), v) for k, v in out.items()}


def compile_window(ctx: dict) -> Optional[dict]:
    """``CompileCounters.window`` over the traced steps of ``ctx``, with
    ``steps``: the first step's batch to the last step's read, moved from
    ``perf_counter`` to wall-clock seconds."""
    c = telemetry and telemetry.CompileCounters.installed()
    spans = ctx.get("spans")
    if c is None or not spans:
        return None
    off = time.time() - time.perf_counter()
    w = c.window(spans[0][0] + off, spans[-1][2] + off)
    w["steps"] = len(spans)
    return w


def anchor_offsets_ns(pd, bounds: Sequence[Tuple[int, int]]) -> List[int]:
    """For each ``telemetry.clock_anchor()`` of the trace, in order, the
    trace's clock minus ``time.time_ns()``: the midpoint of the anchor's
    span in the trace less the midpoint of the bounds it returned, to the
    nanosecond."""
    evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                 for plane in pd.planes if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name == telemetry.ANCHOR)
    if len(evs) != len(bounds):
        raise ValueError(f"{len(evs)} anchor spans in the trace, "
                         f"{len(bounds)} anchors taken")
    return [(round(s + e) - t0 - t1) // 2
            for (s, e), (t0, t1) in zip(evs, bounds)]


def idle_in_compile_share(path: str, compile_spans_s, bounds) -> float:
    """Share (%) of device 0's idle time in the window that lies inside
    the counters' compile-or-load and lowering spans (wall-clock seconds),
    placed on the trace's clock by the mean offset of the anchors."""
    pd = TR.load(path)
    w0, w1 = _window(pd)
    off = sum(anchor_offsets_ns(pd, bounds)) / len(bounds)
    plane = TR.device_planes(pd)[0]
    line = TR._line(plane, "XLA Ops")
    busy = TR._union((max(ev.start_ns, w0),
                      min(ev.start_ns + ev.duration_ns, w1))
                     for ev in (line.events if line is not None else [])
                     if ev.start_ns + ev.duration_ns > w0
                     and ev.start_ns < w1
                     and not TR.CONTAINER.search(ev.name))
    comp = TR._union((max(s * 1e9 + off, w0), min(e * 1e9 + off, w1))
                     for s, e in compile_spans_s
                     if e * 1e9 + off > w0 and s * 1e9 + off < w1)
    idle = (w1 - w0) - TR._length(busy)
    return 100.0 * TR._minus(comp, busy) / idle if idle > 0 else 0.0
