"""Per-stage device time and attributed idle, from what the program marks.

The program names its device stages with ``jax.named_scope`` and its host
calls with spans (``repro.obs``).  A trace names an operation by its HLO
instruction (``%fusion.12 = ...``) but keeps no stage: the stage is in the
compiled program's ``op_name`` metadata (``jit(<program>)/.../<scope>/...``).
So, after the window, the program is lowered and compiled again at the
cell's shapes (a compile cache hit) and each window operation finds its
stage by instruction name.  Host spans are on ``time.time_ns()``; the trace's
clock is that less the profile's start, which the reduced trace drops, so
the offset is recovered by pairing each job's ``bench.init`` annotation with
the program's ``init`` span.  Device idle time is then laid on the innermost
program span open over it, or on ``outside the program``.

Where the program marks nothing (an older program), every reading here is
``None``.  What the readers share is kept on the run, computed once.
"""
from __future__ import annotations

import re
import statistics
import sys
from functools import partial

#: the stage scopes the program puts on its device code
SCOPES = ("s1_tree", "s1_label", "pack", "s2", "s3", "lloyd", "sse")
PROGRAM = "jit__ipkmeans_core"
KERNEL = "_lloyd_solve_batched"
INIT = "bench.init"
WINDOW = "bench.window"
OUTSIDE = "outside the program"
UNSCOPED = "unscoped"

_HEAD = re.compile(r'^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$')
_LINE = re.compile(r'^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$')
_OP_NAME = re.compile(r'metadata=\{[^}\n]*?op_name="([^"]*)"')
_METADATA = re.compile(r'metadata=\{[^}\n]*\}')
_CALLS = re.compile(r'(?:calls|to_apply|body|condition|branch_computations|'
                    r'called_computations)=\{?([^,}\s]+'
                    r'(?:,\s*%[^,}\s]+)*)')
_REF = re.compile(r'%([\w.\-]+)')
_MARKED = re.compile(r'/(?:s1_tree|s1_label|pack|s2|s3)[/"]')


def _scope_of(op_name: str, scopes):
    """The innermost of ``scopes`` among the components of an ``op_name``
    under a program (``jit(<program>)/...``), or None."""
    if not op_name.startswith("jit("):
        return None
    hits = [p for p in op_name.split("/") if p in scopes]
    return hits[-1] if hits else None


def scope_table(compiled_text: str, scopes=SCOPES) -> dict:
    """{instruction name: its scope of ``scopes``, or None} for every
    instruction of a compiled program's text.

    An instruction whose ``op_name`` lies under the program takes the
    innermost scope among its components.  One the compiler added with no
    such ``op_name`` (a copy, an async pair, a fusion it formed) takes the
    one scope of the computations it calls, else that of its first scoped
    operand, else that of its first scoped user.  One under the program but
    outside every scope stays None."""
    comps: dict[str, list] = {}
    insts: dict[str, tuple] = {}
    current = None
    for line in compiled_text.splitlines():
        m = _LINE.match(line)
        if m is None:
            head = _HEAD.match(line)
            if head:
                current = comps.setdefault(head.group(1), [])
            continue
        name, rhs = m.groups()
        op = _OP_NAME.search(rhs)
        called = {c.lstrip("%") for g in _CALLS.findall(rhs)
                  for c in re.split(r",\s*", g)}
        rhs = _METADATA.sub("", rhs)
        operands = [r for r in _REF.findall(rhs) if r not in called]
        marked = op is not None and op.group(1).startswith("jit(")
        insts[name] = (_scope_of(op.group(1), scopes) if marked else None,
                       marked, called, operands)
        if current is not None:
            current.append(name)
    table = {name: own for name, (own, _, _, _) in insts.items()}
    users: dict[str, list] = {}
    for name, (_, _, _, operands) in insts.items():
        for o in operands:
            users.setdefault(o, []).append(name)
    added = [name for name, (_, marked, _, _) in insts.items() if not marked]
    for name in added:
        _, _, called, operands = insts[name]
        inner = {table[i] for c in called for i in comps.get(c, ())} - {None}
        if len(inner) == 1:
            table[name] = inner.pop()
            continue
        table[name] = next((table[o] for o in operands if table.get(o)),
                           None)
    for name in [n for n in added if table[n] is None]:
        table[name] = next((table[u] for u in users.get(name, ())
                            if table.get(u)), None)
    return table


def instruction(text: str) -> str:
    """The instruction name of an operation's HLO text."""
    head = text.split(" = ", 1)[0].strip()
    return head.removeprefix("ROOT ").lstrip("%")


def stage_seconds(reduced, table: dict, program: str = PROGRAM,
                  skip=(KERNEL,)) -> dict:
    """Device seconds of ``program``'s window operations by scope, those
    named in ``skip`` left out (the operations ``pipeline_xla_ms`` sums);
    an operation outside every scope counts under ``unscoped``."""
    out: dict[str, float] = {}
    for o in reduced.module_ops(program):
        if o.name in skip:
            continue
        key = table.get(instruction(o.text)) or UNSCOPED
        out[key] = out.get(key, 0.0) + (o.end - o.start) * 1e-9
    return out


def clock_offset(host, program_spans):
    """(offset, spread) in ns: the median, over the paired jobs, of each
    ``bench.init`` start on the trace's clock less its program ``init``
    span's start, and the pairs' max - min.  The pairing is the run of
    consecutive spans that spreads least; None without a pair."""
    marks = sorted(s for name, s, _ in host if name == INIT)
    starts = sorted(s.start_ns for s in program_spans if s.name == "init")
    starts = starts[-(len(marks) + 64):]
    best = None
    for shift in range(len(starts) - len(marks) + 1 if marks else 0):
        diffs = [m - s for m, s in zip(marks, starts[shift:])]
        spread = max(diffs) - min(diffs)
        if best is None or spread < best[1]:
            best = (statistics.median(diffs), spread)
    return best


def innermost(spans, outer=()) -> list:
    """Disjoint (start, end, name) pieces of the time covered by
    ``spans`` (start, end, name), each named by the span open there that
    opened last: the innermost, where spans nest.  A span named in
    ``outer`` lies under every other, whenever it opened."""
    spans = sorted(spans)
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    out, open_, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][0] <= a:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s[1] > a]
        if open_:
            name = max(open_, key=lambda s: (s[2] not in outer, s[0],
                                             -s[1]))[2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_gaps(reduced) -> list:
    """The window's device-idle intervals: the complement of busy time."""
    edges = [reduced.lo] + [x for iv in reduced.busy_intervals
                            for x in iv] + [reduced.hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def attribute(gaps, pieces) -> list:
    """For each gap (sorted, disjoint), {name: ns} of its overlap with the
    sorted disjoint ``pieces`` of ``innermost``; the rest of the gap is
    ``outside the program``."""
    out, j = [], 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        got, k = {}, j
        while k < len(pieces) and pieces[k][0] < g1:
            s0, s1, name = pieces[k]
            ov = min(s1, g1) - max(s0, g0)
            if ov > 0:
                got[name] = got.get(name, 0) + ov
            k += 1
        rest = (g1 - g0) - sum(got.values())
        if rest > 0:
            got[OUTSIDE] = rest
        out.append(got)
    return out


# ------------------------------------------------------- the run's reads ----

def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def _once(run, what, fn):
    """``fn(run)``, computed once for the run, which the readers share."""
    memo = vars(run).setdefault("_scopes", {})
    if what not in memo:
        memo[what] = fn(run)
    return memo[what]


def _core_args(config: dict):
    """(arguments, static configuration) of ``_ipkmeans_core`` as a job of
    the cell calls it: its shapes, and the configuration built as
    ``jobs.py`` builds it."""
    import jax
    import jax.numpy as jnp

    import data
    import jobs
    cfg = jobs.SolveJob(config, {"solver": "ipkmeans"}, None, 0).cfg
    n, d, k = (int(config[x]) for x in ("n_points", "dim", "clusters"))
    return (jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.random.fold_in(data.run_key(0, data.SOLVE), 0)), cfg


def _compiled_table(config: dict):
    """The scope table of ``_ipkmeans_core`` at the cell's shapes, static
    configuration and precision; None where its lowered program has no
    stage scopes."""
    import jax

    from repro.core.ipkmeans import _ipkmeans_core
    args, cfg = _core_args(config)
    with jax.default_matmul_precision(config["precision"]):
        lowered = _ipkmeans_core.lower(*args, cfg)
        if not _MARKED.search(lowered.as_text(debug_info=True)):
            return None
        table = scope_table(lowered.compile().as_text())
        if not any(table.values()):
            # the compile cache, keyed without metadata, handed back an
            # executable compiled from a program without scopes (the same
            # instructions): compile afresh, with metadata in the key
            flag = "jax_compilation_cache_include_metadata_in_key"
            was = getattr(jax.config, flag)
            jax.config.update(flag, True)
            try:
                fresh = jax.jit(partial(_ipkmeans_core.__wrapped__, cfg=cfg))
                table = scope_table(fresh.lower(*args).compile().as_text())
            finally:
                jax.config.update(flag, was)
    return table


def _ms_list(ms: dict) -> str:
    return ", ".join(f"{k} {v!r}" for k, v in sorted(
        ms.items(), key=lambda kv: -kv[1]))


def _stages(run):
    table = _compiled_table(run.config)
    if table is None:
        return None
    jobs = run.counters["jobs"]
    per = {k: 1e3 * v / jobs for k, v in stage_seconds(run.trace,
                                                       table).items()}
    loose: dict[str, float] = {}
    for o in run.trace.module_ops(PROGRAM):
        if o.name != KERNEL and not table.get(instruction(o.text)):
            loose[o.name] = loose.get(o.name, 0.0) + (o.end - o.start) * 1e-6
    print(f"bench: device ms a job by stage of {PROGRAM} ({KERNEL} left "
          f"out): {_ms_list(per)}; sum {sum(per.values())!r}; unscoped by "
          f"operation: {_ms_list({k: v / jobs for k, v in loose.items()})}",
          file=sys.stderr, flush=True)
    return per


def stage_ms(run, scope: str):
    """Device ms a job of ``_ipkmeans_core``'s operations under ``scope``;
    None where the program has no scopes or nothing ran under ``scope``."""
    per = _once(run, "stages", _stages)
    return per.get(scope) if per else None


def _window_spans(run):
    """The program spans that overlap the window, as (start, end, name) on
    the trace's clock; None where the program records no spans or no job
    can be paired."""
    obs = _obs()
    if obs is None:
        return None
    spans = obs.spans()
    paired = clock_offset(run.trace.host, spans)
    if paired is None:
        return None
    off, spread = paired
    lo, hi = run.trace.lo, run.trace.hi
    inside = [(s.start_ns + off, s.end_ns + off, s.name) for s in spans
              if s.end_ns + off > lo and s.start_ns + off < hi]
    counts: dict[str, int] = {}
    for _, _, name in inside:
        counts[name] = counts.get(name, 0) + 1
    print(f"bench: span clock offset {off!r} ns, spread over the jobs "
          f"{spread!r} ns; spans in the window: " + ", ".join(
              f"{k} {v}" for k, v in sorted(counts.items())),
          file=sys.stderr, flush=True)
    return inside


def window_spans(run):
    return _once(run, "spans", _window_spans)


def _idle(run):
    """Idle ns of the window by the innermost program span open over it;
    the rest by the harness's annotation open there (``outside the
    program, in bench.<x>``), else ``outside the program``."""
    got = window_spans(run)
    if got is None:
        return None
    gaps = idle_gaps(run.trace)
    harness = [(s, e, f"{OUTSIDE}, in {name}") for name, s, e in
               run.trace.host if name != WINDOW]
    parts = attribute(gaps, innermost(
        got + harness, outer={h[2] for h in harness}))
    total: dict[str, float] = {}
    for p in parts:
        for name, ns in p.items():
            total[name] = total.get(name, 0) + ns
    longest = sorted(zip(gaps, parts), key=lambda gp: gp[0][0] - gp[0][1])
    for (g0, g1), p in longest[:10]:
        print(f"bench: idle gap {(g1 - g0) * 1e-6!r} ms at "
              f"{(g0 - run.trace.lo) * 1e-9:.6f} s: "
              + _ms_list({k: v * 1e-6 for k, v in p.items()}),
              file=sys.stderr, flush=True)
    print("bench: idle ms in the window by span: "
          + _ms_list({k: v * 1e-6 for k, v in total.items()}),
          file=sys.stderr, flush=True)
    return total


def idle_in_program_ms(run):
    """Device-idle ms a job while a program span was open."""
    total = _once(run, "idle", _idle)
    if total is None:
        return None
    inside = sum(v for k, v in total.items() if not k.startswith(OUTSIDE))
    return inside * 1e-6 / run.counters["jobs"]


def compiles_in_window(run):
    """Traces and compiles the program recorded inside the window."""
    got = window_spans(run)
    if got is None:
        return None
    return sum(1 for _, _, name in got if name in ("compile", "trace"))
