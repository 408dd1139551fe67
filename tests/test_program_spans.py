"""The program's spans and scopes as a profiler sees them (ISSUE 25).

- a ``jax.profiler`` session around a four-step run holds every span of
  the training loop as a ``bigdl:host:*`` annotation on the host plane,
  the leaves nested inside the ``train_iteration`` of their thread;
- the ``jax.named_scope``s of ``make_train_step`` and ``Sequential``
  are debug metadata only: the lowered step is the same text with them
  patched out;
- the compiled step's instruction -> scope table, which is how a TPU
  trace (device operations named by HLO instruction only) is joined to
  those scopes, reaches the session as one ``bigdl:compile:step_scopes``
  annotation, with what the compiler says the step holds (``memory``);
- the experts' routing telemetry of the module state reaches a session
  at every loss drain as one ``bigdl:optim:expert_state`` annotation,
  in the drain's ONE ``device_get`` (ISSUE 35).

No share of time is asserted: on the CPU a toy step is all Python.
"""
import contextlib
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import Sample, SampleToBatch, array
from bigdl_tpu.observability import trace
from bigdl_tpu.observability import tracing

BATCH = 32
LEAVES = {"input_wait", "step_lookup", "compile_step", "device_step",
          "loss_drain", "emit_steps", "validation", "model_sync",
          "checkpoint_handoff", "input_produce"}


def _samples(n=128, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, 2).astype(np.float32)
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(np.int64) + 1
    return [Sample(x[i], y[i]) for i in range(n)]


def _host_annotations(trace_dir):
    """``[(line index, name, stats, start_ns, end_ns)]`` of the
    ``bigdl:`` events of the host plane; a line is a thread."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("bigdl:"):
                    out.append((i, ev.name, dict(ev.stats), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


@pytest.fixture(scope="module", params=["local", "distri"])
def profiled_run(request, tmp_path_factory):
    """Four steps of a toy model through ``Optimizer`` inside a profiler
    session; validation and a checkpoint fire after step 4. Once on one
    device and once over a mesh: the readers of ``benchmarks/`` hold
    both optimizers to the one span contract of the one loop."""
    from bigdl_tpu.parallel import Engine
    tmp = tmp_path_factory.mktemp("program_spans")
    train = array(_samples()) >> SampleToBatch(BATCH)
    val = array(_samples(64, seed=4)) >> SampleToBatch(BATCH)
    model = nn.Sequential(nn.Linear(2, 16), nn.Tanh(),
                          nn.Linear(16, 2), nn.LogSoftMax())
    Engine.reset()
    request.addfinalizer(Engine.reset)
    mesh = (Engine.init(axes={"data": 8}) if request.param == "distri"
            else None)
    o = optim.Optimizer(model=model, dataset=train,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
    assert type(o).__name__ == {"local": "LocalOptimizer",
                                "distri": "DistriOptimizer"}[request.param]
    o.set_optim_method(optim.SGD(learning_rate=0.5))
    o.set_end_when(optim.max_iteration(4))
    o.set_validation(optim.every_epoch(), val, [optim.Top1Accuracy()])
    o.set_checkpoint(str(tmp / "ckpt"), optim.every_epoch())
    trace_dir = str(tmp / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        o.optimize()
    finally:
        jax.profiler.stop_trace()
    return _host_annotations(trace_dir)


def test_every_iteration_is_one_span_with_its_step(profiled_run):
    its = [e for e in profiled_run
           if e[1] == "bigdl:host:train_iteration"]
    assert len({e[0] for e in its}) == 1          # one thread: the loop's
    steps = [e[2]["step"] for e in its]
    # the fifth holds only the end_when call that ended the run
    assert steps == [1, 2, 3, 4, 5]
    for a, b in zip(its, its[1:]):
        assert a[4] <= b[3]                       # one after the other


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_leaf_span_is_there_and_inside_an_iteration(profiled_run, leaf):
    its = [e for e in profiled_run
           if e[1] == "bigdl:host:train_iteration"]
    loop_line = its[0][0]
    got = [e for e in profiled_run if e[1] == f"bigdl:host:{leaf}"]
    assert got, f"no bigdl:host:{leaf} in the trace"
    on_loop = [e for e in got if e[0] == loop_line]
    if leaf == "input_produce":
        assert not on_loop                        # the prefetch worker's
        assert {e[2]["pipeline"] for e in got} >= {"train"}
        return
    assert on_loop == got
    for _, _, _, start, end in got:
        assert any(s <= start and end <= t for _, _, _, s, t in its)


def test_span_arguments_become_annotation_stats(profiled_run):
    drains = [e[2] for e in profiled_run
              if e[1] == "bigdl:host:loss_drain"]
    assert [(d["first_step"], d["last_step"]) for d in drains] \
        == [(1, 2), (3, 4)]
    assert all(d["depth"] == 2 and d["reason"] == "window full"
               and d["host_sync"] == "packed loss readback"
               for d in drains)
    emits = [e[2] for e in profiled_run
             if e[1] == "bigdl:host:emit_steps"]
    assert [d["depth"] for d in emits] == [2, 2]


def test_step_scope_table_reaches_the_session_once(profiled_run):
    tables = [e for e in profiled_run
              if e[1] == "bigdl:compile:step_scopes"]
    # the step compiled inside the session, in iteration 1: the table
    # is written at the start of iteration 2, and never again
    assert len(tables) == 1
    table = json.loads(tables[0][2]["long_name"])
    assert table["program"] == "jit_train_step"
    assert any("optimizer_update" in k for k in table["scopes"])
    assert any("jvp(model)" in k for k in table["scopes"])


def test_the_steps_memory_rides_its_scope_table(profiled_run):
    """Once a program: the compiler's count of what the step holds, in
    the table the step already writes."""
    table, = [json.loads(e[2]["long_name"]) for e in profiled_run
              if e[1] == "bigdl:compile:step_scopes"]
    memory = table["memory"]
    assert set(memory) == {"arg_bytes", "output_bytes", "alias_bytes",
                           "temp_bytes", "code_bytes", "peak_hbm_bytes"}
    assert memory["arg_bytes"] > 0 and memory["temp_bytes"] >= 0
    assert memory["peak_hbm_bytes"] == pytest.approx(
        memory["arg_bytes"] + memory["output_bytes"] + memory["temp_bytes"]
        - memory["alias_bytes"])


# ---------------------------------------------------------------------------
# the experts' routing, stated at a loss drain inside a session
# ---------------------------------------------------------------------------

def _drained_run(tmp, expert, session):
    """Four steps of a toy model through ``Optimizer``: ``(what each
    drain handed its ``jax.device_get`` calls, the session's
    annotations)``."""
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.parallel.expert import ExpertShare
    middle = ExpertShare(16, 8, 4, 2, experts_held=2) if expert \
        else nn.Tanh()
    model = nn.Sequential(nn.Linear(2, 16), middle, nn.Linear(16, 2),
                          nn.LogSoftMax())
    o = optim.Optimizer(model=model,
                        dataset=array(_samples()) >> SampleToBatch(BATCH),
                        criterion=nn.ClassNLLCriterion())
    o.set_optim_method(optim.SGD(learning_rate=0.5))
    o.set_end_when(optim.max_iteration(4))
    drains, calls = [], []
    real_get, real_drain = jax.device_get, Optimizer._drain_pending

    def device_get(tree):
        calls.append(tree)
        return real_get(tree)

    def drain(self, pending, *args):
        if pending:
            seen = len(calls)
            real_drain(self, pending, *args)
            drains.append(calls[seen:])

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "device_get", device_get)
        patch.setattr(Optimizer, "_drain_pending", drain)
        if session:
            jax.profiler.start_trace(str(tmp))
        try:
            o.optimize()
        finally:
            if session:
                jax.profiler.stop_trace()
    return drains, _host_annotations(str(tmp)) if session else []


@pytest.fixture(scope="module")
def expert_run(tmp_path_factory):
    return _drained_run(tmp_path_factory.mktemp("expert_state"),
                        expert=True, session=True)


def test_expert_state_is_stated_at_each_drain_inside_a_session(expert_run):
    from bigdl_tpu.parallel.expert import SHARE_STATE_KEYS
    _, annotations = expert_run
    stated = [json.loads(e[2]["long_name"]) for e in annotations
              if e[1] == "bigdl:optim:expert_state"]
    # the state the window's LAST step left: steps 2 and 4 of 1-2, 3-4
    assert [s["step"] for s in stated] == [2, 4]
    for s in stated:
        (layer, stats), = s["layers"].items()
        assert layer == "1" and set(stats) == set(SHARE_STATE_KEYS)
        assert stats["moe_chunks_run"] == 1.0
        assert 0.0 <= stats["moe_product_row_share"] <= 1.0
        assert stats["moe_held_load_max"] >= stats["moe_held_load_mean"]
    drains = [e for e in annotations if e[1] == "bigdl:host:loss_drain"]
    line, = {e[0] for e in drains}
    assert all(e[0] == line for e in annotations
               if e[1] == "bigdl:optim:expert_state")


@pytest.mark.parametrize("expert,session", [(True, True), (True, False),
                                            (False, True)],
                         ids=["stated", "no-session", "no-expert-state"])
def test_a_drain_is_one_device_get_whatever_it_states(
        expert, session, expert_run, tmp_path):
    """The routing telemetry rides the losses' readback; outside a
    session, or where the state holds none, the readback is the losses'
    alone and nothing is stated."""
    drains, annotations = expert_run if expert and session \
        else _drained_run(tmp_path, expert, session)
    assert len(drains) == 2
    for calls in drains:
        (losses, experts), = calls             # ONE device_get a drain
        assert len(losses) == 2
        assert bool(experts) == (expert and session)
    stated = [e for e in annotations
              if e[1] == "bigdl:optim:expert_state"]
    assert len(stated) == (2 if expert and session else 0)


@pytest.mark.parametrize("kind", ["local", "distri"])
def test_expert_telemetry_is_published_where_the_state_holds_it(kind,
                                                                caplog):
    """Neither optimizer asks for ``expert_parallel``: what the module
    state holds decides, and the log line names the keys that are
    there."""
    from bigdl_tpu.observability.registry import default_registry
    from bigdl_tpu.parallel import Engine
    from bigdl_tpu.parallel.expert import SHARE_STATE_KEYS, ExpertShare
    Engine.reset()
    mesh = Engine.init(axes={"data": 8}) if kind == "distri" else None
    try:
        for middle in (nn.Tanh(), ExpertShare(16, 8, 4, 2, experts_held=2)):
            gauge = default_registry().get("moe_product_row_share")
            if gauge is not None:
                gauge.set(-1.0, layer="1")
            model = nn.Sequential(nn.Linear(2, 16), middle,
                                  nn.Linear(16, 2), nn.LogSoftMax())
            o = optim.Optimizer(
                model=model, criterion=nn.ClassNLLCriterion(), mesh=mesh,
                dataset=array(_samples()) >> SampleToBatch(BATCH))
            assert not o.expert_parallel
            o.set_optim_method(optim.SGD(learning_rate=0.5))
            o.set_end_when(optim.max_iteration(2))
            caplog.clear()
            with caplog.at_level("INFO", logger="bigdl_tpu.optim"):
                o.optimize()
            said = [r.getMessage() for r in caplog.records
                    if r.getMessage().startswith("moe[")]
            if isinstance(middle, nn.Tanh):
                assert not said
                continue
            assert len(said) == 1 and said[0].startswith("moe[1]: ")
            for key in SHARE_STATE_KEYS:
                assert key.removeprefix("moe_") in said[0], key
            assert "dropped" not in said[0]        # ``MoE``'s keys: not here
            share = default_registry().get("moe_product_row_share") \
                .value(layer="1")
            assert 0.0 <= share <= 1.0
    finally:
        Engine.reset()


def test_disabled_span_is_the_bare_annotation():
    """With no tap and export-tracing off a span is the profiler
    annotation and nothing else (the fast path the hot loops pay)."""
    t = tracing.Tracer()
    with t.span("device step"):
        pass
    assert type(t.span("x")).__name__ == "TraceAnnotation"
    assert t.to_dict()["traceEvents"] == []
    assert not jax.profiler.TraceAnnotation.is_enabled()


# ---------------------------------------------------------------------------
# scopes inside the step program
# ---------------------------------------------------------------------------

def _lm_step(num_microbatches):
    """``(jitted step, its arguments)`` of a toy LM."""
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim.accumulation import make_train_step
    from bigdl_tpu.optim.remat import remat_forward
    model = TransformerLM(64, d_model=32, num_heads=2, num_layers=2,
                          max_len=16, with_log_softmax=False)
    method = optim.AdamW(learning_rate=1e-3)
    step = make_train_step(
        fwd=remat_forward(model, "none"),
        criterion=nn.CrossEntropyCriterion(), update_fn=method.update,
        grad_clip={"l2_norm": 1.0, "min_value": None, "max_value": None},
        num_microbatches=num_microbatches)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.ones((4, 16), jnp.int32)
    return jax.jit(step), (
        params, model.init_state(), method.init_state(params),
        jax.random.PRNGKey(1), tokens, tokens, jnp.asarray(1, jnp.int32))


def _lm_step_lowered(num_microbatches):
    step, args = _lm_step(num_microbatches)
    return step.lower(*args)


@pytest.mark.parametrize("k", [1, 2])
def test_scopes_do_not_change_the_step_program(k, monkeypatch):
    with_scopes = _lm_step_lowered(k)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lm_step_lowered(k)
    assert with_scopes.as_text() == without.as_text()
    named = with_scopes.as_text(debug_info=True)
    assert named != without.as_text(debug_info=True)
    # jax writes the transform around the outermost scope only:
    # jvp(model)/block_0/..., transpose(jvp(model))/lm_head/...
    for scope in ("/optimizer_update/", "/grad_clip/", "jvp(criterion)/",
                  "jvp(model)/block_0/", "jvp(model)/lm_head/",
                  "transpose(jvp(model))/block_1/",
                  "transpose(jvp(model))/embed/"):
        assert scope in named, scope


def test_cross_entropy_states_itself_where_it_is_traced_never_in_a_step():
    """The ``bigdl:nn:cross_entropy`` instant (PERF.md section 3): once
    where the step is traced, with what the criterion read, and not
    again when the compiled step runs."""
    step, args = _lm_step(1)
    seen = []
    trace.get_tracer().add_tap(seen.append)
    try:
        step(*args)
        stated = [e for e in seen if e["name"] == "cross_entropy"]
        step(*args)
    finally:
        trace.get_tracer().remove_tap(seen.append)
    assert len(stated) == 1 and stated[0]["cat"] == "nn"
    assert stated[0]["args"] == {"rows": 64, "classes": 64,
                                 "logits_dtype": "float32",
                                 "materialised_bytes": 0}
    assert [e for e in seen if e["name"] == "cross_entropy"] == stated


def test_sequential_scope_names_hold_no_id():
    seq = nn.Sequential(nn.Linear(2, 3), nn.Tanh(),
                        nn.Linear(3, 2).set_name("head"))
    params = seq.init(jax.random.PRNGKey(0))
    text = jax.jit(lambda p, x: seq.apply(p, seq.init_state(), x)[0]) \
        .lower(params, jnp.ones((1, 2))).as_text(debug_info=True)
    for scope in ("0_Linear", "1_Tanh", "head"):
        assert scope in text, scope
    assert "2_Linear" not in text
    assert "@" not in "".join(
        line for line in text.splitlines() if "Linear" in line)


# ---------------------------------------------------------------------------
# the instruction -> scope table of a compiled program
# ---------------------------------------------------------------------------

def _meta(op_name):
    return f'metadata={{op_name="jit(train_step)/{op_name}"}}'


HLO = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "",
    "%fused_computation.1 (p: f32[8]) -> f32[8] {",
    "  %p = f32[8]{0} parameter(0)",
    "  ROOT %mul.9 = f32[8]{0} multiply(%p, %p), "
    + _meta("jvp(model)/block_0/mul"),
    "}",
    "",
    "%region_0.1 (a: f32[], b: f32[]) -> f32[] {",
    '  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}',
    '  %b = f32[] parameter(1), metadata={op_name="reduce_sum"}',
    '  ROOT %add.3 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}',
    "}",
    "",
    "ENTRY %main.5 (params: f32[8]) -> (f32[8], f32[]) {",
    "  %params = f32[8]{0:T(256)} parameter(0), "
    'metadata={op_name="params"}',
    "  %fusion.1 = f32[8]{0:T(256)} fusion(%params), kind=kLoop, "
    "calls=%fused_computation.1, "
    'metadata={op_name="jit(train_step)/jvp(model)/block_0/mul" '
    'source_file="m.py" source_line=3}',
    "  %copy-start = (f32[8]{0:T(256)S(1)}, f32[8]{0}, u32[]) "
    "copy-start(%fusion.1)",
    "  %reduce.2 = f32[]{:T(128)} reduce(%fusion.1, %c), dimensions={0}, "
    "to_apply=%region_0.1, " + _meta("jvp(criterion)/reduce_sum"),
    "  %add_subtract_fusion = f32[8]{0} fusion(%fusion.1), kind=kLoop, "
    "calls=%fused_computation.2, " + _meta("optimizer_update/sub"),
    "  %all-reduce.1 = f32[8]{0} all-reduce(%fusion.1), "
    "to_apply=%region_0.1, "
    + _meta("transpose(jvp(model))/block_0/mul"),
    "  %fusion.7 = f32[8]{0} fusion(%all-reduce.1), kind=kOutput, "
    "calls=%fused_computation.3, "
    + _meta("transpose(jvp(model))/block_0/dot_general"),
    "  ROOT %tuple.1 = (f32[8], f32[]) tuple(%add_subtract_fusion, "
    '%reduce.2), metadata={op_name="x"}',
    "}", "",
    # a weight-gradient matmul with the weight's update as its epilogue
    "%fused_computation.3 (p: f32[8]) -> f32[8] {",
    "  %p.3 = f32[8]{0} parameter(0)",
    "  %sub.4 = f32[8]{0} subtract(%p.3, %p.3), "
    + _meta("optimizer_update/sub"),
    "  %r.5 = f32[] reduce(%sub.4, %c), to_apply=%region_0.1, "
    + _meta("grad_clip/reduce_sum"),
    "  ROOT %dot.6 = f32[8]{0} convolution(%sub.4, %p.3), "
    + _meta("transpose(jvp(model))/block_0/dot_general"),
    "}", ""])


def test_program_scopes_keeps_what_runs_under_its_own_name():
    table = tracing._program_scopes(HLO)
    assert table == {"program": "jit_train_step", "scopes": {
        "jit(train_step)/jvp(model)/block_0/mul": ["fusion.1"],
        "jit(train_step)/jvp(criterion)/reduce_sum": ["reduce.2"],
        "jit(train_step)/optimizer_update/sub": ["add_subtract_fusion"],
        "jit(train_step)/transpose(jvp(model))/block_0/mul":
            ["all-reduce.1"],
        "jit(train_step)/transpose(jvp(model))/block_0/dot_general":
            ["fusion.7"]},
        # what an operation holds besides its root's scope: the update
        # fused into the matmul; a bare op_name ("reduce_sum") is none
        "inside": {"jit(train_step)/optimizer_update": ["fusion.7"],
                   "jit(train_step)/grad_clip": ["fusion.7"]}}


def test_program_scopes_of_a_compiled_step():
    compiled = _lm_step_lowered(1).compile()
    table = tracing._program_scopes(compiled.as_text())
    assert table["program"] == "jit_train_step"
    kinds = {"forward": r"jvp(model)", "backward": "transpose(jvp(model))",
             "criterion": "jvp(criterion)", "update": "optimizer_update"}
    for what, part in kinds.items():
        assert any(part in k for k in table["scopes"]), what
    names = [n for v in table["scopes"].values() for n in v]
    assert len(names) == len(set(names))
    assert set(n for v in table["inside"].values() for n in v) <= set(names)


def test_a_step_without_text_costs_a_warning_not_the_run(caplog):
    class NoText:
        def as_text(self):
            raise RuntimeError("no text")
    scopes = tracing.ProgramScopes()
    with caplog.at_level("WARNING"):
        scopes.add(NoText())
    assert "no scope table" in caplog.text
    scopes.annotate()                       # nothing kept, nothing written


@pytest.mark.parametrize("analysis", ["raises", "none"])
def test_a_step_without_memory_analysis_states_null_and_warns(caplog,
                                                              analysis):
    """A backend (or a deserialised executable) that gives no memory
    analysis: the table is kept, its ``memory`` is ``null`` — never
    zeros — and that costs a warning, not the run."""
    class NoMemory:
        def as_text(self):
            return HLO

        def cost_analysis(self):
            return {"flops": 1.0}

        def memory_analysis(self):
            if analysis == "raises":
                raise NotImplementedError("no memory analysis here")
            return None

    scopes = tracing.ProgramScopes()
    with caplog.at_level("WARNING"):
        scopes.add(NoMemory())
    assert "no memory analysis for compiled step jit_train_step" \
        in caplog.text
    table, = map(json.loads, scopes._tables)
    assert table["memory"] is None
    assert table["scopes"] == tracing._program_scopes(HLO)["scopes"]


def test_memory_fields_are_the_compilers_and_the_peak_their_sum():
    class Memory:
        argument_size_in_bytes, output_size_in_bytes = 100, 90
        alias_size_in_bytes, temp_size_in_bytes = 80, 40
        generated_code_size_in_bytes = 7

    class Compiled:
        def as_text(self):
            return HLO

        def cost_analysis(self):
            return None

        def memory_analysis(self):
            return Memory()

    scopes = tracing.ProgramScopes()
    scopes.add(Compiled())
    table, = map(json.loads, scopes._tables)
    assert table["memory"] == {
        "arg_bytes": 100.0, "output_bytes": 90.0, "alias_bytes": 80.0,
        "temp_bytes": 40.0, "code_bytes": 7.0, "peak_hbm_bytes": 150.0}


# ---------------------------------------------------------------------------
# the serving span
# ---------------------------------------------------------------------------

def test_replica_lock_wait_spans_submissions_and_retakes_only():
    """A submission's wait for the replica lock is a span with its
    ``rid``; the driver's is one only when it re-takes the lock right
    after a burst, so an idle driver's poll ticks leave nothing."""
    import threading
    import time
    from bigdl_tpu.observability.registry import MetricRegistry
    from bigdl_tpu.serving.replica_pool import Replica

    class Batcher:
        idle, steps, health_name = True, 0, "fake_batcher"

        def submit(self, rid, prompt, **kw):
            self.idle = False

        def step(self, burst):
            self.steps += 1
            self.idle = self.steps >= 2
            return 1

    events, seen = [], threading.Event()

    def tap(ev):
        if ev["name"] == "replica lock wait":
            events.append(ev["args"]["rid"])
            if events.count("driver") >= 2:
                seen.set()

    tracer = trace.get_tracer()
    tracer.add_tap(tap)
    rep = Replica("r0", Batcher(), registry=MetricRegistry(),
                  poll_interval=0.001).start()
    try:
        time.sleep(0.05)                    # idle poll ticks: no span
        assert events == []
        rep.submit("q1", [1, 2, 3])
        assert seen.wait(5.0)
    finally:
        rep.stop()
        tracer.remove_tap(tap)
    assert events[0] == "q1" and events.count("driver") == 2
