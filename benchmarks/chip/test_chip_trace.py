"""``trace.py`` on XSpaces with known answers: a hand-made one whose every
interval is written out below, and a recorded one from the chip."""
import pathlib

import pytest

from benchmarks.chip import trace as TR

HERE = pathlib.Path(__file__).resolve().parent
MS = 10 ** 9  # picoseconds in a millisecond

HLO = """HloModule jit_body, is_scheduled=true
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/jvp()/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/transpose(jvp())/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(body)/cond/branch_1_fun/add"}
  %all-to-all.4 = u8[4,8]{1,0} all-to-all(%q), replica_groups={{0,1,2,3}}
  ROOT %conditional.9 = (f32[8]{0}) conditional(%r, %s, %t)
}
"""
# (event name, start ms, end ms) on device 0
OPS = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 10, 14),
       ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 14, 18),
       ("%conditional.9 = (f32[8]{0}) conditional(pred[] %r)", 18, 21),
       ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 18, 20),
       ("%all-to-all.4 = u8[4,8]{1,0} all-to-all(u8[4,8]{1,0} %q)", 20, 21),
       ("%fusion.1 = s32[4]{0} fusion(s32[4]{0} %k), kind=kLoop", 21.5, 22)]
MODULES = [("jit_body(1234)", 10, 21.2), ("jit_scan(77)", 21.5, 22)]
HOST = [("bench.batch", 9, 10), ("bench.step", 10, 21.6),
        ("bench.read", 21.6, 23)]


def _xspace(ops, modules, host) -> str:
    planes = []
    for pid, (pname, lines) in enumerate(
            [("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)]),
             ("/host:CPU", [("python", host)])], 1):
        meta, body = {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for n, s, e in events:
                mid = meta.setdefault(n, len(meta) + 1)
                evs.append(f"events {{ metadata_id: {mid} "
                           f"offset_ps: {int(s * MS)} "
                           f"duration_ps: {int((e - s) * MS)} }}")
            body.append(f'lines {{ id: {lid} name: "{lname}" '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        em = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                      f'name: "{n}" }} }}' for n, i in meta.items())
        planes.append(f'planes {{ id: {pid} name: "{pname}" '
                      f'{" ".join(body)} {em} }}')
    return "\n".join(planes) + "\n"


@pytest.fixture
def hand(tmp_path):
    p = tmp_path / "hand.pbtxt"
    p.write_text(_xspace(OPS, MODULES, HOST))
    return str(p)


def test_parse_hlo():
    module, stacks = TR.parse_hlo(HLO)
    assert module == "jit_body"
    assert stacks["fusion.2"] == "jit(body)/transpose(jvp())/dot_general"
    assert stacks["conditional.9"] == ""
    assert TR.instruction(OPS[0][0]) == "fusion.1"


def test_window_busy_and_classes(hand):
    s = TR.summarize(hand, 1, 1, HLO)
    assert s["window_s"] == pytest.approx(14e-3)
    assert s["busy_s"] == pytest.approx(11.5e-3)
    assert s["step_runs"] == 1
    ps = s["per_step_s"]
    assert ps["fwd_bwd"] == pytest.approx(8e-3)
    assert ps["optimizer"] == pytest.approx(2e-3)
    assert ps["collective"] == pytest.approx(1e-3)
    # an op named like a step op, run by another program
    assert ps["other"] == pytest.approx(0.5e-3)
    assert s["exchange_exposed_s"] == pytest.approx(1e-3)


def test_breakdown(hand):
    b = TR.summarize(hand, 2, 1, HLO)["breakdown"]
    assert b["device_ops"][0] == ["fusion.1 jit(body)/jvp()/dot_general",
                                  pytest.approx(4.5e-3)]
    assert len(b["device_ops"]) == 4
    # idle gaps, longest first, each named by the host span it fell in
    assert [g[0] for g in b["idle_gaps"]] == ["bench.batch", "bench.read",
                                              "bench.step"]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [1e-3, 1e-3, 0.5e-3])


def test_overlap_hides_the_exchange(tmp_path):
    ops = OPS[:4] + [("%all-gather.7 = f32[8] all-gather(f32[2] %x)",
                      17, 19)]
    p = tmp_path / "overlap.pbtxt"
    p.write_text(_xspace(ops, MODULES, HOST))
    s = TR.summarize(str(p), 1, 1, HLO)
    assert s["per_step_s"]["collective"] == pytest.approx(2e-3)
    assert s["exchange_exposed_s"] == pytest.approx(0.0)


def test_interval_arithmetic():
    assert TR._union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert TR._minus([(0, 10)], [(2, 3), (5, 7)]) == pytest.approx(7)
    assert TR._minus([(0, 1), (4, 6)], [(0.5, 5)]) == pytest.approx(1.5)
    assert TR._inside(2.5, [(0, 1), (2, 3)])
    assert not TR._inside(1.5, [(0, 1), (2, 3)])


def test_recorded_trace():
    """Two window steps of bert-large.sync1 recorded on one v5e in a
    ``--trace 1`` run, cut by ``cut_trace.py`` to steps 5 and 6, with the
    step program's optimized HLO of that run. The classes were checked by
    hand against the HLO's name stacks: the forward and backward loops
    (while.12, while.11) hold 16.8 and 47.1 ms of the 70.9 ms of forward
    and backward ops."""
    import gzip
    hlo = gzip.open(HERE / "testdata" / "bert-large.sync1.step_hlo.txt.gz",
                    "rt").read()
    s = TR.summarize(str(HERE / "testdata"
                         / "bert-large.sync1.trace.pbtxt.gz"), 2, 1, hlo)
    assert s["step_module"] == "jit_body" and s["step_runs"] == 2
    ps = s["per_step_s"]
    assert ps["fwd_bwd"] == pytest.approx(70.939e-3, rel=1e-4)
    assert ps["optimizer"] == pytest.approx(81.157e-3, rel=1e-4)
    assert ps["collective"] == 0.0
    assert s["window_s"] == pytest.approx(0.4532, rel=1e-3)
    assert s["busy_s"] == pytest.approx(0.30469, rel=1e-3)
    # the device waits for the host's batch most of all
    assert [g[0] for g in s["breakdown"]["idle_gaps"][:2]] == [
        "bench.batch", "bench.batch"]
