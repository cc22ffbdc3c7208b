"""``scopes.py`` on traces with known answers, the readers of the program's
compile counters, and ``trace.summarize`` pinned on the recorded trace it
was written against."""
import gzip
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import scopes as SC
from benchmarks.chip import trace as TR
from benchmarks.chip.metrics import (compiles_per_step, encode_ms,
                                     host_compile_ms, state_copy_ms)
from benchmarks.chip.test_chip_trace import HLO, HOST, MODULES, OPS, _xspace
from repro import telemetry

HERE = pathlib.Path(__file__).resolve().parent
DATA = HERE / "testdata"

SCOPED_HLO = """HloModule jit_body, is_scheduled=true
%fused_computation.7 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %convert.1 = bf16[8]{0} convert(%p), metadata={op_name="jit(body)/opt.local_step/opt.var_round/opt.exchange/convert_element_type"}
  ROOT %convert.2 = f32[8]{0} convert(%convert.1)
}
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/model.fwd_bwd/jvp()/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/model.fwd_bwd/reduce_sum"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/opt.local_step/cond/branch_1_fun/opt.sync_update/opt.encode/abs"}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/opt.local_step/cond/branch_1_fun/vmap(opt.decode)/mul"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/opt.local_step/sub"}
  %convert_fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.7
  %copy.8 = f32[8]{0} copy(%p)
  %all-to-all.9 = u8[4,8]{1,0} all-to-all(%q), replica_groups={{0,1,2,3}}, metadata={op_name="jit(body)/opt.local_step/cond/branch_1_fun/opt.sync_update/opt.exchange/all_to_all"}
}
"""
_F = "f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
SCOPED_OPS = [(f"%fusion.1 = {_F}", 10, 14),          # fwd_bwd class
              (f"%fusion.2 = {_F}", 14, 15),          # model.fwd_bwd
              (f"%fusion.3 = {_F}", 15, 17),          # opt.encode
              (f"%fusion.4 = {_F}", 17, 17.5),        # opt.decode
              (f"%fusion.5 = {_F}", 17.5, 19),        # opt.local_step
              (f"%convert_fusion.6 = {_F}", 19, 19.25),  # opt.exchange
              ("%copy.8 = f32[8]{0} copy(f32[8]{0} %p)", 19.25, 20),
              ("%all-to-all.9 = u8[4,8]{1,0} all-to-all(u8[4,8]{1,0} %q)",
               20, 21),
              ("%fusion.3 = s32[4]{0} fusion(s32[4]{0} %k), kind=kLoop",
               21.5, 22)]                              # another program


def test_scope_names_are_the_programs():
    for name in telemetry.SCOPES:
        assert telemetry.innermost_scope(f"jit(f)/vmap({name})/add") == name


def test_hlo_scopes():
    sc = SC.hlo_scopes(SCOPED_HLO)
    assert sc["fusion.2"] == "model.fwd_bwd"
    assert sc["fusion.4"] == "opt.decode"
    # a fusion XLA rooted in a convert of its own takes its ops' scope
    assert sc["convert_fusion.6"] == "opt.exchange"
    assert sc["copy.8"] is None


def test_optimizer_split_by_scope(tmp_path):
    p = tmp_path / "scoped.pbtxt"
    p.write_text(_xspace(SCOPED_OPS, MODULES, HOST))
    ps = TR.summarize(str(p), 1, 1, SCOPED_HLO)["per_step_s"]
    sc = SC.optimizer_scopes(str(p), 1, 1, SCOPED_HLO)
    assert sc == pytest.approx({"model.fwd_bwd": 1e-3, "opt.encode": 2e-3,
                                "opt.decode": 0.5e-3,
                                "opt.local_step": 1.5e-3,
                                "opt.exchange": 0.25e-3,
                                "unscoped": 0.75e-3})
    assert sum(sc.values()) == pytest.approx(ps["optimizer"], rel=1e-12)
    # per step, over two steps
    half = SC.optimizer_scopes(str(p), 2, 1, SCOPED_HLO)
    assert half["opt.encode"] == pytest.approx(1e-3)


def test_unscoped_program_reads_nothing(tmp_path, monkeypatch):
    p = tmp_path / "hand.pbtxt"
    p.write_text(_xspace(OPS, MODULES, HOST))
    assert SC.optimizer_scopes(str(p), 1, 1, HLO) is None
    monkeypatch.setattr(SC, "telemetry", None)
    assert SC.optimizer_scopes(str(p), 1, 1, SCOPED_HLO) is None
    assert compiles_per_step.read({"spans": [(0.0, 1.0, 2.0)]}) is None


def test_recorded_trace_reads_as_before():
    """``summarize`` on the recorded trace returns the values it returned
    when the benchmark's readers were accepted; the program that made the
    trace had no scopes."""
    hlo = gzip.open(DATA / "bert-large.sync1.step_hlo.txt.gz", "rt").read()
    path = str(DATA / "bert-large.sync1.trace.pbtxt.gz")
    s = TR.summarize(path, 2, 1, hlo)
    assert s["per_step_s"] == {"fwd_bwd": 0.07093864250000001,
                               "optimizer": 0.0811571935,
                               "collective": 0.0, "other": 0.000247274}
    assert (s["window_s"], s["busy_s"], s["exchange_exposed_s"],
            s["step_module"], s["step_runs"]) == (
        0.453200737, 0.30468622, 0.0, "jit_body", 2.0)
    ops = s["breakdown"]["device_ops"]
    assert [o[0].split()[0] for o in ops] == [
        "fusion.674", "bitcast_dynamic-update-slice_fusion.7",
        "convolution_add_fusion.23", "multiply_reduce_fusion.34",
        "fusion.661", "fusion.646", "fusion.260", "abs_reduce_fusion.3",
        "abs_reduce_fusion.2", "multiply_reduce_fusion.14"]
    assert ops[0][1] == 0.018488859 and ops[6][1] == 0.007412753
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.batch", 0.056583237]
    assert [g[0] for g in gaps[:4]] == ["bench.batch", "bench.batch",
                                        "bench.step", "bench.step"]
    assert SC.optimizer_scopes(path, 2, 1, hlo) is None


B = 1_700_000_000_000_000_000            # ns since the epoch, made up
MS = 1_000_000


def test_anchor_puts_compiles_on_the_trace_clock(tmp_path):
    host = HOST + [("telemetry.anchor", 9.5, 9.51),
                   ("telemetry.anchor", 22.9, 22.91)]
    p = tmp_path / "anchored.pbtxt"
    p.write_text(_xspace(OPS, MODULES, host))
    # each anchor's own clock read inside its span, the trace's clock
    # B ns behind the wall clock
    bounds = [(B + 9_502_000, B + 9_506_000),
              (B + 22_902_000, B + 22_908_000)]
    pd = TR.load(str(p))
    assert SC.anchor_offsets_ns(pd, bounds) == pytest.approx(
        [-B + 1000, -B], abs=1, rel=0)
    # device 0 is idle in 9-10, 21-21.5, 22-23 ms (busy 10-21, 21.5-22):
    # a compile at 20.5-22.5 ms covers 0.5 + 0.5 of the 2.5 ms idle
    wall = lambda ms: (B + ms * MS) / 1e9
    share = SC.idle_in_compile_share(str(p), [(wall(20.5), wall(22.5))],
                                     bounds)
    assert share == pytest.approx(40.0, rel=1e-3)
    # a compile outside the window counts nothing
    assert SC.idle_in_compile_share(str(p), [(wall(30), wall(31))],
                                     bounds) == 0.0
    with pytest.raises(ValueError):
        SC.anchor_offsets_ns(pd, bounds[:1])


def test_compile_readers_count_the_window():
    """A step that builds a new program every call reads one compile per
    step."""
    telemetry.CompileCounters.install()
    x = jnp.ones(4)
    jax.jit(lambda v: v + 1.0)(x).block_until_ready()      # before
    spans = []
    for i in range(3):
        t0 = time.perf_counter()
        jax.jit(lambda v, i=i: v * i)(x).block_until_ready()
        t1 = time.perf_counter()
        spans.append((t0, t1, time.perf_counter()))
    ctx = {"spans": spans}
    assert compiles_per_step.read(ctx) == 1.0
    ms = host_compile_ms.read(ctx)
    assert 0 < ms <= 1e3 * (spans[-1][2] - spans[0][0]) / 3


def test_recorded_scoped_trace():
    """Two window steps of bert-large.sync1 recorded on one v5e from the
    scoped program (``breakdown.py --cut ... 5 2``), with its optimized
    HLO. The scopes split the optimizer's 81.16 ms; most of what no scope
    claims is XLA's copies of the optimizer state."""
    hlo = gzip.open(DATA / "bert-large.sync1.scoped.step_hlo.txt.gz",
                    "rt").read()
    path = str(DATA / "bert-large.sync1.scoped.trace.pbtxt.gz")
    ps = TR.summarize(path, 2, 1, hlo)["per_step_s"]
    assert ps["optimizer"] == pytest.approx(81.160e-3, rel=1e-4)
    assert ps["fwd_bwd"] == pytest.approx(70.844e-3, rel=1e-4)
    sc = SC.optimizer_scopes(path, 2, 1, hlo)
    assert sum(sc.values()) == pytest.approx(ps["optimizer"], rel=1e-12)
    assert sc["opt.encode"] == pytest.approx(24.349e-3, rel=1e-4)
    assert sc["opt.sync_update"] == pytest.approx(7.225e-3, rel=1e-4)
    assert sc["opt.decode"] == pytest.approx(0.130e-3, rel=1e-3)
    # XLA fuses the local half-step into the encode and sync passes, and
    # a fusion's time goes to its root's scope
    assert sc["opt.local_step"] < 1e-6
    assert sc["unscoped"] == pytest.approx(49.456e-3, rel=1e-4)
    loose = SC.unscoped_ops(path, 2, 1, hlo)
    assert sum(t for _, t in loose.values()) == pytest.approx(
        sc["unscoped"], rel=1e-12)
    copies = sum(t for op, t in loose.values()
                 if op in ("copy", "copy-start", "copy-done"))
    assert copies == pytest.approx(43.321e-3, rel=1e-4)


def test_hlo_opcodes():
    ops = SC.hlo_opcodes(SCOPED_HLO + """
  %copy-start.2 = (f32[8]{0:T(8,128)}, f32[8]{0}, u32[]) copy-start(%p)
  %fusion.9 = (f32[]{:T(128)}, f32[8]{0:T(8,128)S(1)}) fusion(%p), kind=kLoop
""")
    assert ops["fusion.1"] == "fusion" and ops["copy.8"] == "copy"
    assert ops["all-to-all.9"] == "all-to-all"
    assert ops["convert.1"] == "convert"
    assert ops["copy-start.2"] == "copy-start"
    assert ops["fusion.9"] == "fusion"


def _ctx(path, hlo, n_steps):
    return {"trace": TR.summarize(path, n_steps, 1, hlo),
            "trace_path": path, "hlo": hlo, "kinds": ["sync"] * n_steps,
            "chips": 1}


def test_encode_and_copy_readers_on_a_hand_trace(tmp_path):
    p = tmp_path / "scoped.pbtxt"
    p.write_text(_xspace(SCOPED_OPS, MODULES, HOST))
    ctx = _ctx(str(p), SCOPED_HLO, 1)
    assert encode_ms.SCOPE in telemetry.SCOPES
    assert encode_ms.read(ctx) == pytest.approx(2.0)
    assert state_copy_ms.read(ctx) == pytest.approx(0.75)
    assert SC.optimizer_opcodes(str(p), 1, 1, SCOPED_HLO) == pytest.approx(
        {"fusion": 5.25e-3, "copy": 0.75e-3})
    # a step program with no copy reads 0 ms of copies; one with no
    # scopes reads no encode
    no_copy = SCOPED_HLO.replace("%copy.8 = f32[8]{0} copy(%p)",
                                 "%copy.8 = f32[8]{0} negate(%p)")
    assert state_copy_ms.read(_ctx(str(p), no_copy, 1)) == 0.0
    q = tmp_path / "hand.pbtxt"
    q.write_text(_xspace(OPS, MODULES, HOST))
    assert encode_ms.read(_ctx(str(q), HLO, 1)) is None


def test_encode_and_copy_readers_on_the_recorded_traces():
    """On the two recorded bert-large.sync1 steps: the encode's 24.35 ms,
    and 45.02 ms of state copies outside the forward and backward, of
    which 43.32 carry no scope (``test_recorded_scoped_trace``). The
    older, unscoped program's trace has the same copies and no encode
    reading."""
    hlo = gzip.open(DATA / "bert-large.sync1.scoped.step_hlo.txt.gz",
                    "rt").read()
    ctx = _ctx(str(DATA / "bert-large.sync1.scoped.trace.pbtxt.gz"), hlo, 2)
    assert encode_ms.read(ctx) == pytest.approx(24.349, rel=1e-4)
    assert state_copy_ms.read(ctx) == pytest.approx(45.024, rel=1e-4)
    hlo = gzip.open(DATA / "bert-large.sync1.step_hlo.txt.gz", "rt").read()
    ctx = _ctx(str(DATA / "bert-large.sync1.trace.pbtxt.gz"), hlo, 2)
    assert encode_ms.read(ctx) is None
    assert state_copy_ms.read(ctx) == pytest.approx(45.014, rel=1e-4)
