"""The program's tracing: the step program's scope names, host spans, and
counters of the host's compiles.

Scopes name the step program's layers inside its HLO: each op's
``op_name`` metadata carries the name stack, and the innermost ``opt.*``
or ``model.*`` segment of it names the op's layer (scopes nest inside
``lax.cond`` branches: ``.../cond/branch_1_fun/opt.encode/abs``). They are
trace-time metadata; nothing runs differently under them.

Host spans (:func:`span`) are ``jax.profiler.TraceAnnotation``s: about a
microsecond each when no trace is active, a named interval on the
profiler's host plane when one is.

:class:`CompileCounters` listens to ``jax.monitoring``'s compile events:
jaxpr tracing, lowering to MLIR, and compile-or-load
(``backend_compile_duration`` wraps ``compile_or_get_cached``, so a load
from the persistent cache counts as one), with the persistent cache's hits
and misses. ``jax.monitoring``'s listeners belong to the process, so the
counters do too: :meth:`CompileCounters.install` registers one instance
per process and returns it on every later call. They keep everything in
memory and write nothing.
"""
from __future__ import annotations

import collections
import re
import threading
import time
from typing import Dict, Iterable, NamedTuple, Optional, Tuple

import jax

MODEL_FWD_BWD = "model.fwd_bwd"
OPT_LOCAL_STEP = "opt.local_step"
OPT_ENCODE = "opt.encode"
OPT_EXCHANGE = "opt.exchange"
OPT_DECODE = "opt.decode"
OPT_SYNC_UPDATE = "opt.sync_update"
OPT_VAR_ROUND = "opt.var_round"
SCOPES = (MODEL_FWD_BWD, OPT_LOCAL_STEP, OPT_ENCODE, OPT_EXCHANGE,
          OPT_DECODE, OPT_SYNC_UPDATE, OPT_VAR_ROUND)

_SCOPE = re.compile(r"\b(?:opt|model)\.[a-z_]+")

ANCHOR = "telemetry.anchor"

# jax.monitoring's events (jax._src.dispatch, jax._src.compiler)
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
_KIND = {TRACE: "trace", LOWER: "lower", COMPILE: "compile"}
_CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}
# spans kept: a long run that compiles every step keeps the latest only
MAX_SPANS = 4096


def innermost_scope(name_stack: str) -> Optional[str]:
    """The layer of an op: the last ``opt.*``/``model.*`` scope of its name
    stack, also where a transform wraps it (``vmap(opt.encode)``)."""
    found = _SCOPE.findall(name_stack)
    return found[-1] if found else None


def span(name: str):
    """A host span on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name)


def clock_anchor() -> Tuple[int, int]:
    """One ``telemetry.anchor`` span. Returns the ``time.time_ns()`` bounds
    taken inside it: the span's start and end in a trace lie around them,
    which puts the counters' wall-clock spans on the trace's clock."""
    with span(ANCHOR):
        t0 = time.time_ns()
        t1 = time.time_ns()
    return t0, t1


class Snapshot(NamedTuple):
    counts: Dict[Tuple[str, str], int]       # (kind, fun_name) -> events
    seconds: Dict[Tuple[str, str], float]    # (kind, fun_name) -> seconds
    cache: Dict[str, int]                    # cache_hits, cache_misses


class CompileCounters:
    """Counts and seconds of the host's tracing, lowering and
    compile-or-load per function name, the persistent cache's hits and
    misses, and the wall-clock (start, end) of the latest ``MAX_SPANS``
    events (``time.time()`` seconds, as ``jax.monitoring`` gives them)."""

    _installed = None
    _install_lock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, str], int] = collections.Counter()
        self._seconds: Dict[Tuple[str, str], float] = collections.Counter()
        self._cache: Dict[str, int] = collections.Counter()
        self.spans = collections.deque(maxlen=MAX_SPANS)

    @classmethod
    def install(cls) -> "CompileCounters":
        """The process's counters, listening from the first call on."""
        with cls._install_lock:
            if cls._installed is None:
                c = cls()
                jax.monitoring.register_event_time_span_listener(c._on_span)
                jax.monitoring.register_event_listener(c._on_event)
                cls._installed = c
            return cls._installed

    @classmethod
    def installed(cls):
        """The process's counters if installed, else None."""
        return cls._installed

    def _on_span(self, event, start, end, fun_name="", **_):
        kind = _KIND.get(event)
        if kind is None:
            return
        with self._lock:
            self._counts[kind, fun_name] += 1
            self._seconds[kind, fun_name] += end - start
            self.spans.append((kind, fun_name, start, end))

    def _on_event(self, event, **_):
        name = _CACHE.get(event)
        if name is not None:
            with self._lock:
                self._cache[name] += 1

    def snapshot(self) -> Snapshot:
        with self._lock:
            return Snapshot(dict(self._counts), dict(self._seconds),
                            dict(self._cache))

    def since(self, snap: Snapshot) -> dict:
        """What happened after ``snap``: ``compiles``, ``lowerings`` and
        ``traces`` (events), their ``seconds`` by kind, ``cache_hits``,
        ``cache_misses``, and ``compiles_by_fun``."""
        now = self.snapshot()
        d = {k: n - snap.counts.get(k, 0) for k, n in now.counts.items()}
        s = {k: x - snap.seconds.get(k, 0.0) for k, x in now.seconds.items()}
        total = lambda src, kind: sum(v for (k, _), v in src.items()
                                      if k == kind)
        return {
            "compiles": total(d, "compile"),
            "lowerings": total(d, "lower"),
            "traces": total(d, "trace"),
            "seconds": {kind: total(s, kind) for kind in _KIND.values()},
            "cache_hits": now.cache.get("cache_hits", 0)
            - snap.cache.get("cache_hits", 0),
            "cache_misses": now.cache.get("cache_misses", 0)
            - snap.cache.get("cache_misses", 0),
            "compiles_by_fun": {f: n for (k, f), n in d.items()
                                if k == "compile" and n},
        }

    def window(self, t0: float, t1: float) -> dict:
        """The kept spans that start in ``[t0, t1]`` (wall seconds):
        ``compiles`` (compile-or-load events) and ``busy_s`` (the union of
        every kind's spans there; nested traces count once)."""
        with self._lock:
            inside = [s for s in self.spans if t0 <= s[2] <= t1]
        return {"compiles": sum(k == "compile" for k, _, _, _ in inside),
                "busy_s": _union_length((s, e) for _, _, s, e in inside)}


def _union_length(iv: Iterable[Tuple[float, float]]) -> float:
    tot, cur = 0.0, None
    for s, e in sorted(iv):
        if cur is None or s > cur[1]:
            if cur is not None:
                tot += cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    return tot + (cur[1] - cur[0] if cur is not None else 0.0)

