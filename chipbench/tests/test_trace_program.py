"""The program's spans and scopes read from a profiler trace: launch pairing,
the chips' clock offset, causal idle-gap labels, op self time and scopes,
and the metrics that read them."""

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import chipbench_smoke as smoke
from chipbench import common, trace_program, trace_reduce
from chipbench import run as harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
ONE_CHIP = DATA / "v5e_one_chip.xplane.pb"
SERVE = DATA / "v5e_serve.xplane.pb"
RECORDED = sorted(DATA.glob("*.xplane.pb"))
NEW_METRICS = ("host_dispatch_ms.serve", "host_dispatch_ms.train", "prefill_ms_per_token.serve",
               "attention_ms.serve", "unembed_loss_ms.train", "grad_sync_ms.train4")
LINKAGE = "PJRT_LoadedExecutable_Execute linkage"
EXECUTE = "PJRT_LoadedExecutable_Execute"


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _ms(name, a, b, **stats):
    return Ev(name, a * 1e6, (b - a) * 1e6, list(stats.items()))


def _launch(python, main, device, flow, at, dev_start, dev_end, module, chip=0, wait=0.0):
    """A launch from the Python thread at ``at`` ms: the linkage event there,
    the runtime's execute and enqueue events on the main thread 0.1 ms
    later (``wait`` ms later still where the execute first waits for room
    in the device's queue), and the program's execution on the device."""
    python.append(_ms(LINKAGE, at, at + 0.01, _pt=14, _p=flow))
    main.append(_ms(EXECUTE, at + 0.05, at + 0.2 + wait, _ct=14, _c=flow))
    main.append(_ms(trace_program.QUEUE_WAIT, at + 0.06, at + 0.08 + wait))
    main.append(_ms("Handle inputs", at + 0.06, at + 0.08))
    main.append(_ms(trace_program.ENQUEUE, at + 0.1 + wait, at + 0.15 + wait, _pt=12,
                    _p=1000 + flow, run_id=flow, device_ordinal=chip))
    device.append(_ms(module, dev_start, dev_end, _ct=12, _c=1000 + flow, run_id=flow))


def serving():
    """One generate call, 0-100 ms, on one chip whose clock runs 1 ms behind
    the host's.  Executions (device ms): e1 0.1-5.2 and e2 10.1-45 launched
    in prefill steps, e3 47-50 launched in a decode step at 42 (queued behind
    e2), e4 (the fetch's concatenation) 90.1-92.  A garbage collection holds
    the host 60-85 ms."""
    py = [_ms("cb.window", 0, 100), _ms("cb.generate", 0, 98),
          _ms("serve.generate", 0, 97, call=0, batch=2, prompt=2, output=1),
          _ms("serve.prefill", 0, 40, batch=2, prompt=2),
          _ms("serve.step", 0, 2, step=0), _ms("serve.h2d", 0.5, 0.9),
          _ms("serve.step", 10, 12, step=1), _ms("serve.h2d", 10.2, 10.5),
          _ms("serve.decode", 40, 96, batch=2, output=1),
          _ms("serve.step", 41, 44, step=2), _ms("host.gc", 42.5, 43.5, generation=0),
          _ms("host.gc", 60, 85, generation=2, collected=7),
          _ms("serve.fetch", 90, 95)]
    main, modules = [], []
    _launch(py, main, modules, 1, 1.0, 0.1, 5.2, "jit_serve_step(7)")
    _launch(py, main, modules, 2, 11.0, 10.1, 45.0, "jit_serve_step(7)")
    _launch(py, main, modules, 3, 42.0, 47.0, 50.0, "jit_serve_step(7)")
    _launch(py, main, modules, 4, 91.0, 90.1, 92.0, "jit_concatenate(9)")
    # a flow of another type with e2's id, on a thread read first
    other = [_ms("tpu::System::AllocateAndFillTupleIndexTable", 0.3, 0.4, _pt=7, _p=2)]
    ops = [_ms("%while.1 = (s32[]) while(...)", 0.2, 5.1),
           _ms("%fusion.1 = bf16[2] fusion(...)", 0.3, 2.3),
           _ms("%fusion.2 = bf16[2] fusion(...)", 2.5, 5.0),
           _ms("%copy-done.4 = bf16[2] copy-done(...)", 2.3, 2.4),
           _ms("%fusion.1 = bf16[2] fusion(...)", 10.2, 44.9),
           _ms("%fusion.2 = bf16[2] fusion(...)", 47.1, 49.9),
           _ms("%concatenate.3 = s32[2,1] concatenate(...)", 90.2, 91.9)]
    return [Plane("/host:CPU", [Line("tasks/2", other), Line("python", py), Line("main/1", main)]),
            Plane("/device:TPU:0", [Line("XLA Ops", ops), Line("XLA Modules", modules)]),
            Plane("/host:metadata", [])]


SERVE_OPS = {"jit_serve_step(7)": {
    "while.1": "jit(serve_step)/layers/while",
    "fusion.1": "jit(serve_step)/while/body/closed_call/attention/bsq,qd->bsd/dot_general",
    "fusion.2": "jit(serve_step)/while/body/closed_call/mlp/mul"}}


def training():
    """Two steps on two chips, 0-100 ms, each execution starting 1.9 ms
    after its enqueue: each step's batch, placement and dispatch spans, and
    ops of the unembedding, the loss, the gradient sync and one with no
    scope."""
    py = [_ms("cb.window", 0, 100)]
    main, planes = [], []
    devices = {0: ([], []), 1: ([], [])}
    for k, t in enumerate((0.0, 50.0)):
        py += [_ms("cb.step", t, t + 10), _ms("train.batch", t + 1, t + 4, step=k),
               _ms("train.place", t + 4, t + 5), _ms("train.step", t + 5, t + 9)]
        for chip, (ops, modules) in devices.items():
            flow = 10 * k + chip + 1
            _launch(py, main, modules, flow, t + 6, t + 8, t + 48, "jit_train_step(5)", chip)
            ops += [_ms("%fusion.7 = f32[] fusion(...)", t + 8, t + 20),
                    _ms("%fusion.8 = f32[] fusion(...)", t + 20, t + 25),
                    _ms("%collective-permute.2 = f32[] collective-permute(...)", t + 25, t + 40),
                    _ms("%copy.3 = f32[] copy(...)", t + 40, t + 47)]
    for chip, (ops, modules) in devices.items():
        planes.append(Plane(f"/device:TPU:{chip}", [Line("XLA Ops", ops),
                                                    Line("XLA Modules", modules)]))
    return [Plane("/host:CPU", [Line("python", py), Line("main/1", main)])] + planes


TRAIN_OPS = {"jit_train_step(5)": {
    "fusion.7": "jit(train_step)/transpose(jvp(unembed))/bsd,dv->bsv/dot_general",
    "fusion.8": "jit(train_step)/jvp(loss)/reduce_sum",
    "collective-permute.2": "jit(train_step)/shard_map/grad_sync/while/body/ppermute"}}


def test_scope_of_takes_the_innermost_scope():
    assert trace_program.scope_of("jit(f)/transpose(jvp(attention))/dot_general") == "attention"
    assert trace_program.scope_of("jit(f)/jvp(unembed)/x/jvp(loss)/reduce_sum") == "loss"
    assert trace_program.scope_of("jit(f)/while/body/closed_call/norm/rsqrt") == "norm"
    # a function or primitive whose name merely contains a scope's is no scope
    for op in ("jit(attention_decode)/dot_general", "jit(rms_norm)/mul", "loss_fn/add", ""):
        assert trace_program.scope_of(op) == ""


def test_self_time_leaves_out_nested_ops():
    ops = [(0.0, 10.0), (1.0, 3.0), (4.0, 9.0), (5.0, 6.0), (12.0, 13.0)]
    own, parent, _ = trace_program._self_times(ops)
    assert own == pytest.approx([3.0, 2.0, 4.0, 1.0, 1.0])
    assert parent == [-1, 0, 0, 2, -1]


def test_launches_pair_with_their_spans_and_give_the_clock_offset():
    r = trace_program.read(serving(), SERVE_OPS, gaps=True)
    assert r.clock_offset_ms == pytest.approx(1.0)
    paths = [x.path for x in sorted(r.executions, key=lambda x: x.start)]
    step = ("cb.generate", "serve.generate", "serve.prefill", "serve.step")
    assert paths == [step, step, ("cb.generate", "serve.generate", "serve.decode", "serve.step"),
                     ("cb.generate", "serve.generate", "serve.decode", "serve.fetch")]
    assert [x.enqueue for x in r.executions] == pytest.approx([0.0011, 0.0111, 0.0421, 0.0911])
    # flows of another type that share an id do not mislead it (e2 above);
    # pairing by run_id and chip where the flow ids are missing
    planes = serving()
    for ev in planes[1].lines[1].events:
        ev.stats = [(k, v) for k, v in ev.stats if k != "_c"]
    assert [x.path for x in trace_program.read(planes).executions] == paths


def test_causal_labels_of_the_idle_gaps():
    r = trace_program.read(serving(), SERVE_OPS, gaps=True)
    gen = "cb.generate>serve.generate"
    labels = {round(s * 1e3, 3): lab for lab, s in r.idle_gaps}
    # gaps between ops (device ms); e4 launched at 91.1, its gap began at
    # 49.9 (host 50.9): the collection held the host for longest
    assert labels[40.3] == f"{gen}>serve.decode>host.gc"
    # e2 launched at 11.1, its gap began at 5.1 (host 6.1): the host waited in prefill
    assert labels[5.1] == f"{gen}>serve.prefill"
    # e3 launched at 42.1, before its gap (44.9-47.1) began: queued
    assert labels[2.2] == trace_program.QUEUED
    # the last gap ends with no execution: the path that held the host over it
    assert labels[8.1] == f"{gen}>serve.decode>serve.fetch"
    assert labels[0.2] == f"{gen}>serve.prefill>serve.step"
    # the same gaps and lengths as trace_reduce's, in its order
    old = trace_reduce.reduce(serving()).idle_gaps
    assert [g[1] for g in r.idle_gaps] == [g[1] for g in old]


def test_scopes_take_the_self_time_of_the_window_steps():
    r = trace_program.read(serving(), SERVE_OPS)
    assert r.steps == {"jit_serve_step": 3, "jit_concatenate": 1}
    by = r.scope_s["jit_serve_step"]
    assert by["attention"] == pytest.approx(0.002 + 0.0347)
    assert by["mlp"] == pytest.approx(0.0025 + 0.0028)
    # the while's own time, not its body's, and the copy the compiler added
    # inside it (no op_name) under the while's scope
    assert by["layers"] == pytest.approx(0.0003 + 0.0001)
    assert "" not in by and "jit_serve_step" not in r.unscoped
    assert r.per_step_ms("serve_step", ["attention"]) == pytest.approx(36.7 / 3)
    assert r.per_step_ms("serve_step", ["loss"]) is None
    assert r.scoped_share("serve_step") == 1.0
    assert r.unscoped["jit_concatenate"] == pytest.approx({"<concatenate.3>": 0.0017})


def test_any_named_scope_is_read_under_its_op_names():
    """A scope nested under ``mlp`` that ``SCOPES`` does not know is read by
    ``per_step_ms_under``, with an op the compiler added inside it; what
    ``per_step_ms`` reads does not change."""
    experts = "jit(serve_step)/while/body/closed_call/mlp/experts/gate/dot_general"
    ops = {"jit_serve_step(7)": dict(SERVE_OPS["jit_serve_step(7)"], **{"fusion.2": experts})}
    planes = serving()
    planes[1].lines[0].events.append(_ms("%copy.9 = bf16[2] copy(...)", 3.0, 3.5))
    r = trace_program.read(planes, ops)
    before = trace_program.read(serving(), SERVE_OPS)
    for scopes in (["mlp"], ["attention"], ["layers"], ["mlp", "attention"]):
        assert r.per_step_ms("serve_step", scopes) == pytest.approx(
            before.per_step_ms("serve_step", scopes))
    for program, by_scope in before.scope_s.items():
        assert r.scope_s[program] == pytest.approx(by_scope)
    # fusion.2's self time in two steps, the copy inside it among it
    assert r.op_s["jit_serve_step"][experts] == pytest.approx(0.0025 + 0.0028)
    for names in (["experts"], ["gate"], ["mlp"], ["mlp", "experts"]):
        assert r.per_step_ms_under("serve_step", names) == pytest.approx(5.3 / 3)
    assert r.per_step_ms_under("serve_step", ["experts", "attention"]) == pytest.approx(
        (5.3 + 36.7) / 3)
    # the while's own time and the copy-done it runs: as per_step_ms reads them
    assert r.per_step_ms_under("serve_step", ["layers"]) == pytest.approx(0.4 / 3)
    # a name a component merely contains is not that component
    assert r.per_step_ms_under("serve_step", ["expert"]) is None
    assert r.per_step_ms_under("concatenate", ["experts"]) is None


def _read_metrics(monkeypatch, reading, names=NEW_METRICS):
    monkeypatch.setattr(trace_program, "of", lambda reduced: reading)
    peaks = common.peaks("TPU v5 lite")
    return {n: harness.load_metric(smoke.REPO, n).read(None, {}, peaks, {}) for n in names}


def test_serving_metrics_on_the_synthetic_trace(monkeypatch):
    got = _read_metrics(monkeypatch, trace_program.read(serving(), SERVE_OPS))
    # three serve.step spans of 2 ms of their own; the h2d copies count, the collection not
    assert got["host_dispatch_ms.serve"] == pytest.approx(2.0)
    # e1 and e2 (5.1 + 34.9 ms) over the prefill's 2 x 2 tokens
    assert got["prefill_ms_per_token.serve"] == pytest.approx(40.0 / 4)
    assert got["attention_ms.serve"] == pytest.approx(36.7 / 3)
    for n in ("host_dispatch_ms.train", "unembed_loss_ms.train", "grad_sync_ms.train4"):
        assert got[n] is None


def queued_serving():
    """Four decode steps, 0-100 ms: the first two dispatch at once, the last
    two find the device's queue full and wait 20 ms each in the launch call
    for a step to end, as a host that runs ahead of the chip does."""
    py = [_ms("cb.window", 0, 100), _ms("cb.generate", 0, 99),
          _ms("serve.generate", 0, 98, call=0, batch=4, prompt=1, output=4),
          _ms("serve.decode", 0, 97, batch=4, output=4),
          _ms("serve.step", 0, 1, step=1), _ms("serve.step", 1, 2, step=2),
          _ms("serve.step", 2, 23, step=3), _ms("serve.step", 23, 44, step=4),
          _ms("serve.fetch", 90, 96)]
    main, modules = [], []
    _launch(py, main, modules, 1, 0.5, 1.0, 22.0, "jit_serve_step(7)")
    _launch(py, main, modules, 2, 1.5, 22.0, 43.0, "jit_serve_step(7)")
    _launch(py, main, modules, 3, 2.5, 43.0, 64.0, "jit_serve_step(7)", wait=20.0)
    _launch(py, main, modules, 4, 23.5, 64.0, 85.0, "jit_serve_step(7)", wait=20.0)
    ops = [_ms("%fusion.1 = bf16[2] fusion(...)", a + 0.1, a + 20.9) for a in (1, 22, 43, 64)]
    return [Plane("/host:CPU", [Line("python", py), Line("main/1", main)]),
            Plane("/device:TPU:0", [Line("XLA Ops", ops), Line("XLA Modules", modules)])]


def test_host_dispatch_leaves_out_the_wait_for_the_device_queue(monkeypatch):
    r = trace_program.read(queued_serving(), SERVE_OPS)
    waits = [x.wait for x in sorted(r.executions, key=lambda x: x.start)]
    assert waits == pytest.approx([0.0, 0.0, 0.02, 0.02])
    steps = r.spans_named("serve.step")
    assert [s.queue_wait for s in steps] == pytest.approx([0.0, 0.0, 0.02, 0.02])
    # the spans around the steps hold their launches' waits too
    assert [s.queue_wait for s in r.spans if s.name == "serve.decode"] == pytest.approx([0.04])
    # each step spent 1 ms of its own; the 20 ms in the queue are the device's
    got = _read_metrics(monkeypatch, r, ["host_dispatch_ms.serve"])
    assert got["host_dispatch_ms.serve"] == pytest.approx(1.0)


def test_training_metrics_on_a_synthetic_two_chip_trace(monkeypatch):
    r = trace_program.read(training(), TRAIN_OPS, gaps=True)
    # the least shift under which no execution starts before its enqueue
    assert r.clock_offset_s == pytest.approx({0: -0.0019, 1: -0.0019})
    assert r.steps == {"jit_train_step": 4}
    got = _read_metrics(monkeypatch, r)
    assert got["host_dispatch_ms.train"] == pytest.approx(3 + 1 + 4)
    assert got["unembed_loss_ms.train"] == pytest.approx(12 + 5)
    assert got["grad_sync_ms.train4"] == pytest.approx(15)
    assert r.unscoped["jit_train_step"] == pytest.approx({"<copy.3>": 4 * 0.007})
    for n in ("host_dispatch_ms.serve", "prefill_ms_per_token.serve", "attention_ms.serve"):
        assert got[n] is None


def test_the_old_synthetic_trace_keeps_its_labels():
    """No launch in the trace: each gap keeps the span that held the host."""
    import test_chipbench_trace as old

    r = trace_program.read(old.synthetic(), gaps=True)
    assert r.clock_offset_ms is None
    assert r.idle_gaps == trace_reduce.reduce(old.synthetic()).idle_gaps


def test_clock_offset_and_launches_on_the_recorded_one_chip_trace():
    r = trace_program.read_file(str(ONE_CHIP))
    assert 1.25 <= r.clock_offset_ms <= 1.29
    assert len(r.executions) == 5
    assert all(x.path == ("cb.step",) for x in r.executions)
    # each by a cb.step of its own, in order
    steps = [i for i, s in enumerate(r.spans) if s.name == "cb.step"]
    assert [x.span for x in sorted(r.executions, key=lambda x: x.start)] == steps
    # every execution starts after its enqueue once the offset is added
    assert all(x.start + r.clock_offset_s[0] >= x.enqueue for x in r.executions)
    # the program's HLO carried by the trace names each op
    ops = trace_program.hlo_op_names(ONE_CHIP.read_bytes())
    ops = ops["jit__lambda(6866697371735154988)"]
    assert ops["fusion"] == "jit(<lambda>)/dot_general"
    # the compiler's prefetch of w has no op_name: it takes its operand's
    assert ops["copy-start"] == ops["copy-done"] == ops["w.1"] == "w"
    assert r.steps == {"jit__lambda": 5}
    assert r.unscoped["jit__lambda"]["jit(<lambda>)/dot_general"] > 0


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_traces_keep_the_reductions_gaps(path):
    old = trace_reduce.reduce_file(str(path))
    new = trace_program.read_file(str(path))
    assert [g[1] for g in new.idle_gaps] == [g[1] for g in old.idle_gaps]
    assert new.window[1] - new.window[0] == pytest.approx(old.window_s, abs=1e-12)
    assert new.chips == old.chips


def test_every_serve_step_of_the_recorded_serving_trace_pairs_with_its_span():
    expect = json.loads(SERVE.with_suffix("").with_suffix(".json").read_text())
    r = trace_program.read_file(str(SERVE))
    steps = sorted((x for x in r.executions if x.program.endswith("serve_step")),
                   key=lambda x: x.start)
    assert len(steps) == expect["serve_steps"]
    # each launched by the serve.step span of its own step, in its phase
    assert [r.spans[x.span].args["step"] for x in steps] == list(range(len(steps)))
    for x in steps:
        assert x.path[-1] == "serve.step"
        assert x.path[-2] == ("serve.prefill" if r.spans[x.span].args["step"] < 4
                              else "serve.decode")
    assert r.clock_offset_ms == pytest.approx(expect["clock_offset_ms"])
    # each launch call's queue wait is found, and short: nothing was in flight
    assert all(0 < x.wait < 2e-5 for x in steps)
    names = {s.name for s in r.spans}
    assert {"serve.generate", "serve.prefill", "serve.decode", "serve.step", "serve.h2d",
            "serve.fetch", "host.gc"} <= names
    scopes = r.scope_s["jit_serve_step"]
    assert {"embed", "attention", "mlp", "norm", "unembed"} <= set(scopes)


def test_new_metrics_read_nothing_from_a_trace_without_the_programs_names(monkeypatch):
    """The parent's program has no span and no scope: every new metric is
    absent, none raises."""
    got = _read_metrics(monkeypatch, trace_program.read_file(str(ONE_CHIP)))
    assert got == {n: None for n in NEW_METRICS}


def test_of_reads_the_newest_trace_of_its_window(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "OUT", tmp_path)
    assert trace_program.of(trace_reduce.reduce_file(str(ONE_CHIP))) is None
    dst = tmp_path / "trace" / "cell" / "plugins" / "profile" / "1"
    dst.mkdir(parents=True)
    shutil.copy(ONE_CHIP, dst / "x.xplane.pb")
    reduced = trace_reduce.reduce_file(str(ONE_CHIP))
    reading = trace_program.of(reduced)
    assert reading.window[1] - reading.window[0] == pytest.approx(reduced.window_s, abs=1e-12)
    assert len(reading.executions) == 5
    assert reading.clock_offset_ms == pytest.approx(1.2802640)
    # a run's metrics need no gap labels
    assert reading.idle_gaps is None
    assert trace_program.of(dataclasses.replace(reduced, window_s=1.0)) is None
    # a trace that cannot be read fails the run rather than drop its metrics
    (dst / "x.xplane.pb").write_bytes(b"\x0a\x05junk")
    with pytest.raises(Exception):
        trace_program.of(reduced)


GRAD_SYNC_HLO = """
import sys
import jax.numpy as jnp
from repro.configs import get_config
from repro.launch import train as T
cfg = get_config("minicpm-2b-smoke")
mesh, params, opt_state, step = T.build(cfg, steps=10, sync="bidir")
b = T.place_batch({"tokens": jnp.zeros((4, 16), jnp.int32),
                   "labels": jnp.zeros((4, 16), jnp.int32)}, mesh)
exe = step.lower(params, opt_state, b).compile().runtime_executable()
open(sys.argv[1], "wb").write(exe.hlo_modules()[0].as_serialized_hlo_module_proto())
"""


def test_ops_without_an_op_name_inherit_the_gradient_syncs_scope(tmp_path):
    """The bidir train step compiled for four CPU devices: the instructions
    the compiler adds to the ring (copies of what it exchanges) have no
    op_name; they take grad_sync from what they read, and nothing the ring
    runs takes another scope."""
    out = tmp_path / "train_step.hlo.pb"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(smoke.REPO / "src"), str(smoke.REPO)]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", GRAD_SYNC_HLO, str(out)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    b = memoryview(out.read_bytes())
    comps, instr = trace_program._module_instructions(b, (0, len(b)))
    names = trace_program._module_op_names(b, (0, len(b)))

    def scope(i, own=False):
        name, op = instr[i][:2]
        return trace_program.scope_of(op if own else names[name])

    # the ring's computations: those its loops call, at any depth
    ring, todo = set(), [c for i in instr if scope(i, own=True) == "grad_sync"
                         and instr[i][0].startswith("while") for c in instr[i][3]]
    while todo:
        c = todo.pop()
        if c not in ring:
            ring.add(c)
            todo += [d for i in comps.get(c, ()) for d in instr[i][3]]
    in_ring = {i for c in ring for i in comps[c]}
    assert in_ring and {scope(i) for i in in_ring} <= {"grad_sync", ""}
    inherited = [i for i in instr if not instr[i][1] and scope(i) == "grad_sync"]
    assert any(instr[i][0].startswith("copy") for i in inherited)
    for i in inherited:
        # each runs in the ring's loops, or reads or calls what grad_sync emitted
        assert (i in in_ring or any(scope(o) == "grad_sync" for o in instr[i][2])
                or any(scope(j) == "grad_sync" for c in instr[i][3] for j in comps[c]))
