"""The stage and idle readers (``scopes.py``) on synthetic inputs: a fixture
of compiled HLO text, a hand-built reduced trace and program spans with a
known clock offset."""
import importlib.util
import statistics
import types
from pathlib import Path

import pytest

import cells
import scopes
import trace
from trace import Op, Reduced

BENCH = Path(__file__).resolve().parents[1]
PROGRAM = scopes.PROGRAM

HLO = """\
HloModule jit__ipkmeans_core, is_scheduled=true

%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %mul.4 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(_ipkmeans_core)/pack/mul" stack_frame_id=2}
}

%region_0.1 (sort.2: f32[], sort.3: f32[]) -> pred[] {
  %sort.2 = f32[] parameter(0), metadata={op_name="sort"}
  ROOT %lt.1 = pred[] compare(%sort.2, %sort.2), direction=LT, metadata={op_name="lt_to"}
}

ENTRY %main.4 (points.1: f32[8], key.2: u32[2]) -> f32[8] {
  %points.1 = f32[8]{0} parameter(0), metadata={op_name="points"}
  %sort.0 = f32[8]{0} sort(%points.1), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(_ipkmeans_core)/s1_tree/jit(build_kdtree)/sort" stack_frame_id=3}
  %fusion.12 = f32[8]{0} fusion(%sort.0), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_ipkmeans_core)/pack/mul" stack_frame_id=2}
  %wrapped.2 = f32[8]{0} fusion(%sort.0), kind=kLoop, calls=%fused_computation.3
  %copy.1 = f32[8]{0} copy(%fusion.12)
  %gather.7 = f32[8]{0} gather(%copy.1), metadata={op_name="jit(_ipkmeans_core)/s1_label/jit(label_regions)/gather"}
  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%gather.7)
  %copy-done.4 = f32[8]{0} copy-done(%copy-start.4)
  %while.2 = f32[8]{0} while(%copy-done.4), condition=%cond.1, body=%body.1, metadata={op_name="jit(_ipkmeans_core)/s2/while"}
  %reduce.5 = f32[] reduce(%while.2), metadata={op_name="jit(_ipkmeans_core)/s3/jit(min_asse_merge)/argmin"}
  %add.9 = f32[] add(%reduce.5, %reduce.5), metadata={op_name="jit(_ipkmeans_core)/add"}
  ROOT %tuple.3 = (f32[8]{0}) tuple(%while.2)
}
"""


def test_scope_table_reads_each_instructions_stage():
    table = scopes.scope_table(HLO)
    marked = {"mul.4": "pack", "sort.0": "s1_tree", "fusion.12": "pack",
              "gather.7": "s1_label", "while.2": "s2", "reduce.5": "s3"}
    assert {k: table[k] for k in marked} == marked
    # under the program but outside every scope
    assert table["add.9"] is None


def test_instructions_the_compiler_added_take_a_neighbours_scope():
    table = scopes.scope_table(HLO)
    # a fusion with no op_name: the scope of the computation it calls, not
    # its operand's (s1_tree)
    assert table["wrapped.2"] == "pack"
    # a copy and an async pair: their operand's
    assert table["copy.1"] == "pack"
    assert table["copy-start.4"] == table["copy-done.4"] == "s1_label"
    # a root tuple: its operand's; a parameter: its first scoped user's
    assert table["tuple.3"] == "s2"
    assert table["points.1"] == "s1_tree"
    assert table["param_0.1"] == "pack"
    # a comparator's compare (op_name outside a program) has no scoped
    # neighbour
    assert table["lt.1"] is None


def test_scope_table_takes_the_innermost_scope_and_exact_components():
    text = ('  %a.1 = f32[] add(), metadata={op_name="jit(p)/s2/sse/add"}\n'
            '  %b.2 = f32[] add(), metadata={op_name="jit(p)/jit(sse)/add"}\n'
            '  ROOT %c.3 = f32[] add(), metadata={op_name="jit(p)/lloyd/'
            'while/body/add"}\n')
    assert scopes.scope_table(text) == {"a.1": "sse", "b.2": None,
                                        "c.3": "lloyd"}


def test_instruction_name_from_the_trace_text():
    assert scopes.instruction(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %sort.0), kind=kLoop") \
        == "fusion.12"
    assert scopes.instruction("ROOT %tuple.3 = (f32[8]) tuple()") \
        == "tuple.3"


def _op(text, start, end, module=PROGRAM):
    return Op(trace._base(text), module, start, end, text)


def _reduced(ops, host=(), window=(0, 1000)):
    return Reduced(list(ops), list(host), window, chips=1)


def test_stage_sums_follow_the_table_and_leave_out_the_kernel():
    table = scopes.scope_table(HLO)
    red = _reduced([
        _op("%sort.0 = f32[8] sort()", 0, 100),
        _op("%fusion.12 = f32[8] fusion()", 100, 130),
        _op("%gather.7 = f32[8] gather()", 130, 150),
        _op("%while.2 = f32[8] while()", 150, 400),           # control op
        _op("%_lloyd_solve_batched.4 = f32[8] custom-call()", 160, 390),
        _op("%reduce.5 = f32[] reduce()", 400, 405),
        _op("%add.9 = f32[] add()", 405, 406),
        _op("%copy.1 = f32[8] copy()", 406, 410),
        _op("%fusion.12 = f32[8] fusion()", 410, 420, module="jit_other"),
        _op("%sort.0 = f32[8] sort()", 2000, 2100),            # after
    ])
    got = scopes.stage_seconds(red, table)
    want = {"s1_tree": 100, "pack": 30 + 4, "s1_label": 20, "s3": 5,
            "unscoped": 1}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # the sum is what pipeline_xla_ms sums: the program's ops but the kernel
    pipeline = [o for o in red.module_ops(PROGRAM)
                if o.name != scopes.KERNEL]
    assert sum(got.values()) == pytest.approx(red.seconds(pipeline))


def _span(name, start, end, sid=0):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                 span_id=sid, parent_id=None, root_id=sid)


def test_clock_pairing_recovers_a_known_offset_through_jitter():
    offset = -1_700_000_000_123_456_789
    jitter = [0, 3_000, -2_000, 9_000, 1_000, -4_000, 2_500]
    starts = [1_700_000_010_000_000_000 + j * 535_000_000
              for j in range(len(jitter))]
    # the warm-up job's span comes before the window's, and is not paired
    program = [_span("init", starts[0] - 20_000_000_000, 0)] + [
        _span("init", s, s + 1000) for s in starts] + [
        _span("ipkmeans", s + 2000, s + 9000) for s in starts]
    host = [("bench.init", s + offset + j, s + offset + j + 5000)
            for s, j in zip(starts, jitter)]
    host.append(("bench.window", 0, 10))
    off, spread = scopes.clock_offset(host, program)
    assert off == statistics.median(offset + j for j in jitter)
    assert spread == max(jitter) - min(jitter)
    assert scopes.clock_offset([("bench.window", 0, 1)], program) is None


def test_innermost_pieces_of_nested_spans():
    spans = [(0, 100, "ipkmeans"), (10, 40, "ipkmeans.core"),
             (20, 25, "gc"), (60, 90, "ipkmeans.sse"), (200, 210, "init")]
    assert scopes.innermost(spans) == [
        (0, 10, "ipkmeans"), (10, 20, "ipkmeans.core"), (20, 25, "gc"),
        (25, 40, "ipkmeans.core"), (40, 60, "ipkmeans"),
        (60, 90, "ipkmeans.sse"), (90, 100, "ipkmeans"), (200, 210, "init")]


def test_idle_is_laid_on_the_innermost_span_or_outside():
    # busy [0, 5) [30, 50) [95, 250); idle [5, 30) [50, 95) [250, 300)
    red = _reduced([_op("%a.1 = f32[] add()", 0, 5),
                    _op("%b.2 = f32[] add()", 30, 50),
                    _op("%c.3 = f32[] add()", 95, 250)], window=(0, 300))
    assert scopes.idle_gaps(red) == [(5, 30), (50, 95), (250, 300)]
    pieces = scopes.innermost([
        (0, 100, "ipkmeans"), (10, 40, "ipkmeans.core"), (20, 25, "gc"),
        (60, 90, "ipkmeans.sse"), (260, 270, "compile")])
    got = scopes.attribute(scopes.idle_gaps(red), pieces)
    assert got == [
        {"ipkmeans": 5, "ipkmeans.core": 15, "gc": 5},
        {"ipkmeans": 15, "ipkmeans.sse": 30},
        {"compile": 10, scopes.OUTSIDE: 40}]
    total = sum(sum(g.values()) for g in got)
    assert total == pytest.approx((red.hi - red.lo) - sum(
        e - s for s, e in red.busy_intervals))


class FakeObs:
    def __init__(self, spans):
        self._spans = spans

    def spans(self, since_ns=None):
        return list(self._spans)


def _run(red, jobs):
    return types.SimpleNamespace(trace=red, counters={"jobs": jobs},
                                 config={}, peaks={})


def test_the_idle_and_compile_readers_on_a_synthetic_window(monkeypatch,
                                                             capsys):
    off = 1_000
    # two jobs on the trace's clock: bench.init at 10 and 510
    host = [("bench.init", 10, 20), ("bench.init", 510, 520),
            ("bench.ipkmeans", 30, 480), ("bench.window", 0, 1000)]
    red = _reduced([_op("%a.1 = f32[] add()", 40, 400),
                    _op("%b.2 = f32[] add()", 560, 900)], host=host,
                   window=(0, 1000))
    spans = [_span("init", -5000, -4000),                     # warm-up
             _span("init", 10 - off, 30 - off),
             _span("ipkmeans", 30 - off, 60 - off),
             _span("gc", 450 - off, 470 - off),
             _span("init", 510 - off, 530 - off),
             _span("compile", 530 - off, 550 - off),
             _span("trace", 528 - off, 532 - off),
             _span("compile", 5000, 6000)]                    # after
    monkeypatch.setattr(scopes, "_obs", lambda: FakeObs(spans))
    run = _run(red, jobs=2)
    # idle [0, 40): init 20 (over the harness's bench.init, which opened
    # with it), ipkmeans 10; [400, 560): gc 20, init 18, then the trace
    # that opened inside it 2 and the compile that opened last 20; the
    # rest of [400, 480) under the harness's bench.ipkmeans, and all else
    # outside the program
    ms = scopes.idle_in_program_ms(run)
    want_ns = (20 + 10) + (20 + 18 + 2 + 20)
    assert ms == pytest.approx(want_ns * 1e-6 / 2)
    assert scopes.compiles_in_window(run) == 2
    assert run._scopes["idle"] == {
        "init": 38, "ipkmeans": 10, "gc": 20, "trace": 2, "compile": 20,
        f"{scopes.OUTSIDE}, in bench.ipkmeans": 60,
        scopes.OUTSIDE: 10 + 30 + 10 + 100}
    err = capsys.readouterr().err
    assert err.count("bench: idle gap") == 3


def test_readers_give_none_for_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(scopes, "_obs", lambda: None)
    run = _run(_reduced([], host=[("bench.init", 1, 2)]), jobs=1)
    assert scopes.idle_in_program_ms(run) is None
    assert scopes.compiles_in_window(run) is None
    monkeypatch.setattr(scopes, "_compiled_table", lambda config: None)
    assert scopes.stage_ms(run, "s1_tree") is None


def reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_stage_readers_on_the_small_cells_program():
    """The table comes from compiling the small cell's ``_ipkmeans_core``,
    and each reader finds its stage's operations by instruction name."""
    config = cells.small("sift1m-k256.ipkmeans", "ipkmeans")["config"]
    table = scopes._compiled_table(config)
    assert set(table.values()) - {None} == {"s1_tree", "s1_label", "pack",
                                            "s2", "s3"}
    names = {}
    for name, scope in table.items():
        names.setdefault(scope, name)
    ops = [_op(f"%{names[s]} = f32[] x()", 100 * i, 100 * i + 10 * (i + 1))
           for i, s in enumerate(("s1_tree", "s1_label", "pack", "s3"))]
    run = _run(_reduced(ops, window=(0, 1000)), jobs=2)
    run.config = config
    for i, s in enumerate(("s1_tree", "s1_label", "pack", "s3")):
        assert reader(f"{s}_ms")(run) == pytest.approx(10 * (i + 1) * 1e-6 / 2)


def test_a_cached_executable_without_scopes_is_compiled_afresh(tmp_path,
                                                               monkeypatch):
    """The persistent compile cache keys a program without its metadata, so
    it can hand back an executable compiled from the same program without
    scopes (an older checkout's, sharing the cache); the table then comes
    from a compile of its own."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.core.ipkmeans import _ipkmeans_core
    config = cells.small("sift1m-k256.ipkmeans", "ipkmeans")["config"]
    args, cfg = scopes._core_args(config)
    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    jax.clear_caches()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            with jax.default_matmul_precision(config["precision"]):
                bare = _ipkmeans_core.lower(*args, cfg).compile().as_text()
        assert not any(scopes.scope_table(bare).values())
        jax.clear_caches()
        table = scopes._compiled_table(config)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()
    assert set(table.values()) - {None} == {"s1_tree", "s1_label", "pack",
                                            "s2", "s3"}
    assert set(table) == set(scopes.scope_table(bare))
