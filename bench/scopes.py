"""Device time per program scope, from a profiler trace and the compiled
step's HLO.

The program names every device op of its train step by a name scope,
``<layer>/<part>`` with layer ``model``, ``opt`` or ``step``
(``model/attn``, ``opt/select_project``, ``step/clip``; the table is in
docs/observability.md). Each scope reaches the ``op_name`` metadata of
the compiled instructions it produced, forward, recompute and backward
alike (``.../transpose(jvp(model/blocks))/while/body/.../model/attn/...``).
The innermost scope of an instruction's ``op_name`` assigns it; an
instruction with none is unscoped.

A device event is an instruction of some module run on the chip; only
those inside the train step's own module runs (the ``XLA Modules`` line
of the device plane) count, since the data pipeline's jitted programs
run on the same chip and their instruction names can collide with the
step's. The step module is the one whose text the harness writes beside
the trace (``step.hlo.txt``).

The loop's host spans (``train/data_wait``, ``train/dispatch``) are
profiler annotations on the ``/host:CPU`` plane, on the same clock as the
device ops: ``loop_gap_ms`` is the device's idle time that they cover.

    python3 -m bench.scopes [<trace dir>] [--ops N]

prints the per-scope table of a traced run (default: this checkout's
``.bench_out/trace``).
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import os
import re
import sys
from typing import NamedTuple

from bench import xtrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
HLO_FILE = "step.hlo.txt"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE = re.compile(r"(?<!\w)(?:model|opt|step)/\w+")
LOOP_SPANS = ("train/data_wait", "train/dispatch")
# host annotations the helper keeps: the window and the program's spans
_HOST = re.compile(r"^(bench/window|(train|ckpt|serve)/\w+)$")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_EPS = 1e-9                 # seconds: the trace's own resolution


@dataclasses.dataclass
class Profile:
    """The parts of a trace the scope table needs."""
    ops: dict              # device id -> [Event] on the XLA Ops line
    modules: dict          # device id -> [Event] on the XLA Modules line
    host: list             # [Event] program spans and the window
    window: tuple          # (start, end) seconds


# ---------------------------------------------------------------------------
# HLO: the scope of every instruction that runs
# ---------------------------------------------------------------------------
def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``name = ``: the shape
    (a tuple in balanced parentheses, or one token) comes first."""
    end = 0
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ")
    m = re.match(r"\s*([\w\-]+)\(", rest[end:])
    return m.group(1) if m else ""


class Instr(NamedTuple):
    opcode: str
    op_name: str           # "" where the metadata has none
    scope: str | None      # its own innermost scope, else an inherited one


_CALLEE = re.compile(r"\b(body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_GROUPS = re.compile(r"\b(branch|called)_computations=\{([^}]*)\}")
_INHERIT_DEPTH = 32


def hlo_ops(hlo_text: str) -> dict:
    """``{instruction: Instr}`` for every instruction of the module's
    computations that run as a sequence of device ops: the entry, loop
    bodies and conditions, branches and calls, not the bodies of fusions
    or of reducers.

    An op the program traced has a name-stack path for its ``op_name``
    and keeps the scope that path gives, or none. An instruction the
    compiler made (a copy with no ``op_name``, or one named after an
    argument) takes the scope of the loop, branch or call that runs its
    computation, else that of its first scoped user (a loop whose carry
    it sets up), else that of its first scoped operand."""
    comps, caller, inner = {}, {}, set()
    cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split("(", 1)[0].replace("ENTRY", "").strip()
            cur = head.lstrip("%")
            comps.setdefault(cur, {})
            continue
        if cur is None or " = " not in line:
            continue
        name, rest = line.strip().split(" = ", 1)
        name = name.replace("ROOT", "").strip().lstrip("%")
        op = _opcode(rest)
        m = re.search(r'op_name="([^"]*)"', rest)
        comps[cur][name] = (op, m.group(1) if m else "",
                            re.findall(r"%([\w.\-]+)", rest))
        for key, callee in _CALLEE.findall(rest):
            if op == "fusion" or (key == "to_apply" and op != "call"):
                inner.add(callee)
            else:
                caller[callee] = (cur, name)
        for key, group in _GROUPS.findall(rest):
            for callee in re.findall(r"%?([\w.\-]+)", group):
                if key == "branch":
                    caller[callee] = (cur, name)
                else:                       # a custom call's comparator
                    inner.add(callee)
    users: dict = {}
    for comp, instrs in comps.items():
        for name, (_, _, operands) in instrs.items():
            for o in operands:
                if o != name and o in instrs:
                    users.setdefault((comp, o), []).append(name)
    memo: dict = {}

    def scope(comp, name, depth):
        key = (comp, name)
        if key in memo or depth > _INHERIT_DEPTH:
            return memo.get(key)
        memo[key] = None                       # breaks cycles
        op_name = comps[comp][name][1]
        found = scope_of(op_name)
        if found is not None or "/" in op_name:
            memo[key] = found                  # the program's own op
            return found
        if comp in caller:
            found = scope(*caller[comp], depth + 1)
        near = users.get(key, []) + [o for o in comps[comp][name][2]
                                     if o != name and o in comps[comp]]
        for other in near if found is None else ():
            found = scope(comp, other, depth + 1)
            if found is not None:
                break
        memo[key] = found
        return found

    return {name: Instr(v[0], v[1], scope(comp, name, 0))
            for comp, instrs in comps.items() if comp not in inner
            for name, v in instrs.items()}


def scope_of(op_name: str):
    """The innermost program scope of an ``op_name``, or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip_metadata(hlo_text: str) -> str:
    """The module's text without its metadata (``op_name``, source lines,
    stack-frame tables): two compiles of one program that differ in their
    name scopes alone give the same text."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line.strip() in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out) + "\n"


def module_name(hlo_text: str) -> str:
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------
def load(directory: str) -> Profile | None:
    """Reads the newest ``*.xplane.pb`` under ``directory``; None if
    there is none or it has no window span."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(dev, []).extend(
                        xtrace.Event(e.name, e.start_ns * 1e-9,
                                     e.end_ns * 1e-9) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [xtrace.Event(e.name, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9)
                         for e in line.events if _HOST.match(e.name)]
    return from_events(ops, modules, host)


def from_events(ops: dict, modules: dict, host: list) -> Profile | None:
    wins = [e for e in host if e.name == xtrace.WINDOW]
    if not wins:
        return None
    w = max(wins, key=lambda e: e.end - e.start)

    def by_start(evs):
        return sorted(evs, key=lambda e: e.start)

    return Profile(ops={d: by_start(v) for d, v in ops.items()},
                   modules={d: by_start(v) for d, v in modules.items()},
                   host=by_start(host), window=(w.start, w.end))


def step_runs(profile: Profile, device: int, module: str) -> list:
    """The step module's runs on ``device`` that start in the window."""
    # a module's event is named after it, then its program id: name(7)
    named = re.compile(rf"(?<![\w.\-]){re.escape(module)}(?![\w.\-])")
    w0, w1 = profile.window
    return [e for e in profile.modules.get(device, [])
            if named.search(e.name) and w0 <= e.start < w1]


def step_events(profile: Profile, device: int, module: str) -> list:
    """The leaf ops on ``device`` that lie inside one of the step
    module's runs in the window."""
    runs = step_runs(profile, device, module)
    out, i = [], 0
    for e in xtrace.leaf_ops(profile.ops.get(device, [])):
        while i < len(runs) and runs[i].end < e.start - _EPS:
            i += 1
        if i == len(runs):
            break
        if runs[i].start - _EPS <= e.start and e.end <= runs[i].end + _EPS:
            out.append(e)
    return out


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Table:
    """Device time of the step module, per window step and chip."""
    ms: dict               # scope (None: unscoped) -> ms per step
    ops: dict              # scope -> {op kind: ms per step}
    busy_ms: float         # union of the step's op intervals, per step
    steps: int


def table(profile: Profile, hlo_text: str, devices=None,
          steps: int | None = None) -> Table | None:
    """The per-scope table over ``devices`` (default: every device of
    the trace); ``steps`` defaults to the step module's runs in the
    window. None when the step module does not run in the trace, or when
    no op of it carries a scope (a program without scopes)."""
    module = module_name(hlo_text)
    devices = sorted(profile.ops) if devices is None else list(devices)
    if not module or not devices:
        return None
    if steps is None:
        steps = max(len(step_runs(profile, d, module)) for d in devices)
    if steps <= 0:
        return None
    instrs = hlo_ops(hlo_text)
    scale = 1e3 / steps / len(devices)
    ms, kinds, busy, scoped = {}, {}, 0.0, False
    for d in devices:
        events = step_events(profile, d, module)
        for e, s, t in xtrace.clipped(events, *profile.window):
            instr = xtrace.op_name(e.name)
            info = instrs.get(instr)
            scope = info.scope if info is not None else None
            scoped |= scope is not None
            ms[scope] = ms.get(scope, 0.0) + (t - s) * scale
            per = kinds.setdefault(scope, {})
            kind = re.sub(r"\.\d+$", "", instr)
            per[kind] = per.get(kind, 0.0) + (t - s) * scale
        busy += sum(t - s for s, t in
                    xtrace.busy_intervals(events, *profile.window)) * scale
    if not scoped:
        return None
    return Table(ms=ms, ops=kinds, busy_ms=busy, steps=steps)


def loop_gap_ms(profile: Profile, devices=None, steps: int | None = None,
                spans=LOOP_SPANS) -> float | None:
    """Device idle time per step, mean over ``devices``, that the host
    spans named in ``spans`` cover; None when the trace has no such span
    in the window (a program whose spans are not profiler annotations)."""
    w0, w1 = profile.window
    host = [e for e in profile.host if e.name in spans
            and e.end > w0 and e.start < w1]
    devices = sorted(profile.ops) if devices is None else list(devices)
    if not host or not devices:
        return None
    if steps is None:
        steps = len([e for e in host if e.name == spans[0]])
    if steps <= 0:
        return None
    cover = xtrace.busy_intervals(host, w0, w1)
    trace = xtrace.Trace(devices=profile.ops, host=[], window=(w0, w1))
    idle = 0.0
    for d in devices:
        for s, t in xtrace.idle_gaps(trace, d):
            for a, b in cover:
                idle += max(0.0, min(t, b) - max(s, a))
    return idle / len(devices) / steps * 1e3


# ---------------------------------------------------------------------------
# the harness's traced run, for the metric readers
# ---------------------------------------------------------------------------
_CACHE: dict = {}


def _stamp(path: str):
    try:
        return path, os.path.getmtime(path)
    except OSError:
        return path, None


def current(directory: str | None = None):
    """``(profile, hlo text)`` of the traced run under ``directory``
    (default: this checkout's), read once per trace and HLO file; None
    where either is missing."""
    directory = directory or TRACE_DIR
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    hlo_path = os.path.join(directory, HLO_FILE)
    if not files or not os.path.exists(hlo_path):
        return None
    key = (tuple(sorted(_stamp(f) for f in files)), _stamp(hlo_path))
    if key not in _CACHE:
        profile = load(directory)
        with open(hlo_path) as f:
            hlo = f.read()
        _CACHE.clear()
        _CACHE[key] = (profile, hlo) if profile is not None else None
    return _CACHE[key]


def for_ctx(ctx, directory: str | None = None):
    """The run the reader's ``ctx`` describes: the same window on the
    same clock, else None (a trace left from another run)."""
    found = current(directory)
    if found is None:
        return None
    profile, hlo = found
    if any(abs(a - b) > 1e-6 for a, b in zip(profile.window,
                                            ctx.trace.window)):
        return None
    return profile, hlo


def scope_ms(ctx, select, directory: str | None = None) -> float | None:
    """Device time per step, averaged over the cell's chips, of the step
    module's ops whose scope ``select(scope)`` accepts (None: unscoped)."""
    found = for_ctx(ctx, directory)
    if found is None or ctx.steps <= 0:
        return None
    t = table(found[0], found[1], ctx.devices, ctx.steps)
    if t is None:
        return None
    return sum(v for s, v in t.ms.items() if select(s))


def ctx_loop_gap_ms(ctx, directory: str | None = None) -> float | None:
    found = for_ctx(ctx, directory)
    if found is None or ctx.steps <= 0:
        return None
    return loop_gap_ms(found[0], ctx.devices, ctx.steps)


# ---------------------------------------------------------------------------
# python3 -m bench.scopes
# ---------------------------------------------------------------------------
def report(profile: Profile, hlo_text: str, top: int = 5) -> str:
    t = table(profile, hlo_text)
    if t is None:
        return "no scoped step module in the trace\n"
    lines = [f"steps {t.steps}, chips {len(profile.ops)}, ms per step "
             f"and chip"]
    total = sum(t.ms.values())
    for scope in sorted(t.ms, key=lambda s: -t.ms[s]):
        name = scope or "unscoped"
        lines.append(f"{name:24s} {t.ms[scope]:10.3f} "
                     f"{100 * t.ms[scope] / total:6.2f}%")
        for kind, v in sorted(t.ops[scope].items(),
                              key=lambda kv: -kv[1])[:top]:
            lines.append(f"    {kind:40s} {v:10.3f}")
    lines.append(f"{'sum of ops':24s} {total:10.3f}")
    lines.append(f"{'busy (union)':24s} {t.busy_ms:10.3f}")
    gap = loop_gap_ms(profile)
    lines.append(f"{'loop_gap_ms':24s} "
                 + ("none" if gap is None else f"{gap:10.4f}"))
    w0, w1 = profile.window
    for name in LOOP_SPANS + ("train/host_sync",):
        durs = [e.end - e.start for e in profile.host
                if e.name == name and e.start >= w0 and e.end <= w1]
        if durs:
            lines.append(f"{'mean ' + name:24s} "
                         f"{1e3 * sum(durs) / len(durs):10.4f} ms "
                         f"({len(durs)} spans)")
    trace = xtrace.Trace(devices=profile.ops, host=profile.host,
                         window=profile.window)
    gaps = sorted(((t - s, s, d) for d in sorted(profile.ops)
                   for s, t in xtrace.idle_gaps(trace, d)), reverse=True)
    for dur, s, d in gaps[:top]:
        lines.append(f"idle gap {1e3 * dur:8.3f} ms on device {d}: "
                     f"{xtrace.host_activity(trace, s, s + dur)}")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m bench.scopes")
    ap.add_argument("directory", nargs="?")
    ap.add_argument("--ops", type=int, default=5,
                    help="op kinds listed under each scope")
    args = ap.parse_args(argv)
    found = current(args.directory)
    if found is None:
        print(f"no trace and {HLO_FILE} under "
              f"{args.directory or TRACE_DIR}",
              file=sys.stderr)
        return 1
    sys.stdout.write(report(found[0], found[1], args.ops))
    return 0


if __name__ == "__main__":
    sys.exit(main())
