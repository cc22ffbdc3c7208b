"""The program's tracing (``repro.telemetry``): the compile counters, the
scopes of the step program's ops, the named Pallas kernels, and the
training loop's step annotation and spans."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get
from repro.core import OptimizerConfig, schedules as S
from repro.data import DataConfig, SyntheticLM
from repro.launch import train as LT
from repro.train import Trainer

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s+=\s+(\S+)\s+([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")


def test_counts_one_compile_per_program():
    c = telemetry.CompileCounters.install()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones(7)
    snap = c.snapshot()
    f(x)
    f(x)
    assert c.since(snap)["compiles"] == 1

    # a closure made anew on every call is a new program every time
    snap = c.snapshot()
    for i in range(3):
        jax.jit(lambda x, i=i: x * i)(x)
    done = c.since(snap)
    assert done["compiles"] == 3 and done["lowerings"] == 3
    assert done["seconds"]["compile"] > 0


def test_install_is_once_per_process():
    c = telemetry.CompileCounters.install()
    assert telemetry.CompileCounters.install() is c
    assert telemetry.CompileCounters.installed() is c
    x = jnp.ones(3)
    snap = c.snapshot()
    jax.jit(lambda x: x - 2.5)(x)
    assert c.since(snap)["compiles"] == 1


def test_window_counts_spans_that_start_inside():
    c = telemetry.CompileCounters()
    c._on_span(telemetry.TRACE, 10.0, 10.5, fun_name="f")
    c._on_span(telemetry.TRACE, 10.1, 10.2, fun_name="g")   # nested
    c._on_span(telemetry.COMPILE, 10.5, 11.0, fun_name="f")
    c._on_span(telemetry.COMPILE, 20.0, 21.0, fun_name="h")
    c._on_span("/jax/other", 10.0, 12.0)
    w = c.window(10.0, 12.0)
    assert w["compiles"] == 1
    assert w["busy_s"] == pytest.approx(1.0)
    assert c.since(telemetry.Snapshot({}, {}, {}))["compiles_by_fun"] == {
        "f": 1, "h": 1}


def _step_hlo(optimizer="zero_one_adam", use_pallas=False):
    """The optimized step program of a smoke model on a one-device mesh."""
    cfg = get("bert-base").smoke
    opt = OptimizerConfig(name=optimizer, lr=S.ConstantLr(1e-3),
                          use_pallas=use_pallas,
                          sync_policy=S.EveryStepSyncPolicy(),
                          var_policy=S.FixedWarmupPolicy(2))
    from repro.launch.mesh import make_local_mesh, worker_axes
    from repro.train import TrainerConfig
    mesh = make_local_mesh()
    tr = Trainer(cfg, opt, mesh=mesh, trainer_cfg=TrainerConfig(
        worker_axes=worker_axes(mesh)))
    params, state = tr.mesh_init(jax.random.PRNGKey(0))
    step, _ = tr.mesh_step_fn()
    batch = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                   global_batch=4, kind="mlm")).batch(0)
    return step.lower(params, state, batch).compile().as_text()


def _elements(shape: str) -> int:
    m = re.match(r"\w+\[([\d,]*)\]", shape)
    if not m:
        return 2          # a tuple
    return int(np.prod([int(d) for d in m.group(1).split(",") if d]))


def _computations(hlo: str):
    """{computation: [(instruction, shape, opcode, name stack or None,
    called computation)]} of an HLO module's text."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", line)
        if head:
            comp = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            s, c = _OP_NAME.search(line), _CALLS.search(line)
            comp.append(m.groups() + (s.group(1) if s else None,
                                      c.group(1) if c else None))
    return comps


def test_every_optimizer_op_is_scoped():
    """Each op the step program runs (not a fused computation's insides)
    whose result has more than one element lies under ``model.fwd_bwd``
    or an ``opt.*`` scope, except the ops named below."""
    comps = _computations(_step_hlo())
    fused = {c for ins in comps.values() for *_, c in ins
             if c is not None}
    scoped = lambda comp: any(telemetry.innermost_scope(st or "")
                              for _, _, _, st, _ in comps.get(comp, []))
    found, loose = set(), []
    for comp, ins in comps.items():
        if comp in fused:
            continue
        for name, shape, opcode, stack, calls in ins:
            if opcode in ("parameter", "get-tuple-element", "tuple",
                          "bitcast", "constant", "while", "conditional",
                          "call"):
                continue
            scope = telemetry.innermost_scope(stack or "")
            if scope:
                found.add(scope)
                continue
            if _elements(shape) <= 1:
                continue
            # XLA's own instructions carry no JAX metadata: a fusion rooted
            # in a convert it made is scoped by the ops it fuses; the
            # copies it puts at loop and branch boundaries, its splats of a
            # constant (zero fills) and the grouped sums it rewrites as
            # reduce-windows are not
            if stack is None and (scoped(calls) or opcode == "copy"
                                  or name.startswith(
                                      ("wrapped_broadcast",
                                       "wrapped_reduce-window"))):
                continue
            # the rotary angles: JAX hoists the layer scan's loop-invariant
            # ops out of it, and the hoisted ops lose the scopes around it
            if re.fullmatch(r"jit\(body\)/(cos|sin|pow|broadcast_in_dim)",
                            stack or ""):
                continue
            loose.append((name, stack))
    assert not loose, loose[:10]
    assert {telemetry.MODEL_FWD_BWD, telemetry.OPT_LOCAL_STEP,
            telemetry.OPT_ENCODE, telemetry.OPT_EXCHANGE,
            telemetry.OPT_SYNC_UPDATE, telemetry.OPT_VAR_ROUND} <= found


def _kernel_scopes(stacks, kernel):
    """Innermost scopes of the whole name stacks (a reduction's scalar
    computation holds a relative one) that hold ``kernel`` as a segment
    of its own (the Pallas call's name, not ``jit(kernel)``)."""
    return {telemetry.innermost_scope(s) for s in stacks
            if s.startswith("jit(") and re.search(rf"/{kernel}/", s)}


@pytest.mark.parametrize("optimizer,kernels", [
    ("zero_one_adam",
     ("abs_rowsum", "ef_quantize", "decompress", "fused_local_step")),
    ("zero_one_sgd", ("fused_local_step_sgd",)),
])
def test_pallas_kernels_carry_their_names(optimizer, kernels):
    """The interpret-mode ``use_pallas=True`` step names each kernel in its
    ops' name stacks, inside the optimizer's scopes."""
    stacks = _OP_NAME.findall(_step_hlo(optimizer, use_pallas=True))
    for k in kernels:
        under = _kernel_scopes(stacks, k)
        assert under and under <= {telemetry.OPT_ENCODE, telemetry.OPT_DECODE,
                                   telemetry.OPT_LOCAL_STEP}, (k, under)


def test_single_pass_kernel_carries_its_name():
    """``ef_compress`` runs only for per-row scales of a model-sharded 3-D
    view, which a one-device step does not hold: lower its dispatch."""
    from jax.sharding import PartitionSpec as P
    from repro.core import compressor as C
    from repro.kernels import dispatch as K
    lo = C.make_layout((16, 40), P(None, "model"), 2)
    z = jnp.ones(lo.view_shape)
    hlo = jax.jit(lambda z, e: K.ef_compress_view(z, e, lo, "row")).lower(
        z, z).compile().as_text()
    assert _kernel_scopes(_OP_NAME.findall(hlo), "ef_compress") == {None}


def test_train_run_counts_compiles_and_annotates_steps(tmp_path):
    args = LT.parse_args(["--arch", "bert-base", "--smoke", "--steps", "3",
                          "--batch", "2", "--seq", "16", "--mode", "sim",
                          "--workers", "1", "--log-every", "100"])
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec = LT.run(args)
    finally:
        jax.profiler.stop_trace()
    assert len(rec["compiles"]) == 3
    assert rec["compiles"][0] >= 2       # the step program and the batch's
    # after the first step, no step builds a new program
    assert rec["compiles"][1:] == [0, 0]
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    names = [ev.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for ev in line.events]
    for span in ("train", "train.batch", "train.step", "train.read",
                 "data.batch"):
        assert names.count(span) == 3, (span, names.count(span))


def test_clock_anchor_brackets_its_clock():
    t0, t1 = telemetry.clock_anchor()
    assert 0 <= t1 - t0 < 10 ** 9
