"""Per-phase attribution of a profiler trace (``.xplane.pb``).

The serving engine marks each phase of a tick with a host span
(``runtime/trace.py`` ``ENGINE_SPANS``) and each phase of its two step
programs with a named scope (``STEP_SCOPES``), which the compiler keeps in
every instruction's ``op_name`` metadata
(``jit(engine_decode)/while/body/kv.read/gather``).  ``reduce`` turns a
trace into:

* self time per (program, scope): an op event's duration less the op events
  nested in it (a ``while`` that holds the layer loop counts almost nothing
  itself), summed by the program the op ran in and the innermost scope of
  its instruction (``None``: no scope);
* program runs inside the window, to put that time per run of a program;
* idle gaps labelled by the innermost host span covering most of them, so
  that a gap inside the engine's tick reads ``engine.readback``, not
  ``tick``.

An op event names its instruction but not its ``op_name`` (a TPU v5e
event carries only its device offset and duration), so an op's scope comes
from the program's optimised HLO text (``hlo_scopes``): the text of
``jit(...).lower(...).compile().as_text()``, or an ``--xla_dump_to``
``after_optimizations`` file.
"""
from __future__ import annotations

import bisect
import collections
import re

from bench import xplane
from repro.runtime.trace import ENGINE_SPANS, STEP_SCOPES

SPANS = xplane.HOST_SPANS + ENGINE_SPANS

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_ATTR = re.compile(r"\b(calls|body|index)=%?([\w.\-]+)")
# Instructions that only move or re-view data; a fusion of nothing else
# is one too.
MOVERS = {"bitcast", "copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice", "get-tuple-element", "parameter", "reshape",
          "slice", "transpose", "tuple"}


def scope_of(path: str | None, scopes=STEP_SCOPES) -> str | None:
    """The innermost scope named in an ``op_name`` path."""
    for part in reversed((path or "").split("/")):
        if part in scopes:
            return part
    return None


def hlo_scopes(text: str, scopes=STEP_SCOPES) -> dict[str, str | None]:
    """Instruction name -> innermost scope, from an HLO module's text.

    A fusion whose ``op_name`` the compiler dropped (a dot it rewrote as a
    multiply and a reduce) takes the scope of the instructions fused into
    it, its root's first.  An instruction that only moves data (``MOVERS``:
    layout copies, a loop's slicing of stacked buffers) and carries no scope
    takes the scope of the nearest scoped instruction along its data flow,
    through other such movers: its operands first, then its users.  Element
    i of a ``while`` result comes from element i of its body's root tuple.
    A fusion with no ``op_name`` whose fused scatter writes an operand in
    place, and that fuses nothing scoped, takes the scope along that pool
    operand's data flow in the same way: the pool's producers first, then
    the fusion's users (an in-place row write whose ``op_name`` the compiler
    left on the bitcast of its result)."""
    ins, comps, comp = {}, {}, None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head is not None:
            comp = comps.setdefault(head.group(1), {"root": None, "names": []})
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        root, name, rest = m.groups()
        rest = rest.split(" backend_config=")[0]
        op = _OPCODE.search(rest)
        args = rest[op.end():] if op else ""
        depth, cut = 1, len(args)
        for i, ch in enumerate(args):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                cut = i
                break
        meta = _OP_NAME.search(rest)
        ins[name] = {"op": op.group(1) if op else "", "path": meta and meta.group(1),
                     "args": _REF.findall(args[:cut]), "text": args[:cut],
                     **dict(_ATTR.findall(args))}
        comp["names"].append(name)
        if root:
            comp["root"] = name
    users = collections.defaultdict(list)
    for name, d in ins.items():
        d["args"] = [a for a in d["args"] if a in ins]
        for a in d["args"]:
            users[a].append(name)

    def mover(name):
        d = ins[name]
        if d["op"] != "fusion":
            return d["op"] in MOVERS
        body = comps.get(d.get("calls"))
        return body is not None and all(
            ins[n]["op"] in MOVERS or ins[n]["op"] == "constant"
            for n in body["names"])

    def upstream(name):
        d = ins[name]
        if d["op"] == "get-tuple-element" and d["args"] \
                and ins[d["args"][0]]["op"] == "while":
            body = comps.get(ins[d["args"][0]].get("body"), {}).get("root")
            return ins[body]["args"][int(d["index"]):int(d["index"]) + 1] \
                if body else []
        return d["args"]

    def nearest(start, edges):
        seen, todo = {start}, collections.deque(edges(start))
        while todo:
            n = todo.popleft()
            if n in seen:
                continue
            seen.add(n)
            sc = scope_of(ins[n]["path"], scopes)
            if sc is not None:
                return sc
            if mover(n):
                todo.extend(edges(n))
        return None

    def fused(name):
        comp = comps.get(ins[name].get("calls"))
        if comp is None:
            return None
        order = [comp["root"]] + comp["names"][::-1]
        return next((sc for n in order if n is not None
                     for sc in [scope_of(ins[n]["path"], scopes)] if sc), None)

    def pool(name):
        """The argument of a fusion that its fused scatter updates in place
        (the scatter's first operand, through movers, is a parameter of the
        fused computation), or None."""
        comp = comps.get(ins[name].get("calls"))
        sc = comp and next((n for n in comp["names"] if ins[n]["op"] == "scatter"), None)
        if sc is None or not ins[sc]["args"]:
            return None
        n = ins[sc]["args"][0]
        while ins[n]["op"] in MOVERS - {"parameter"} and ins[n]["args"]:
            n = ins[n]["args"][0]
        if ins[n]["op"] != "parameter":
            return None
        k = int(ins[n]["text"])
        return ins[name]["args"][k] if k < len(ins[name]["args"]) else None

    out = {}
    for name, d in ins.items():
        out[name] = scope_of(d["path"], scopes)
        if out[name] is None and d["path"] is None:
            out[name] = fused(name)
        if out[name] is None and d["path"] is None and d["op"] == "fusion" \
                and (p := pool(name)) is not None:
            out[name] = (nearest(name, lambda n: [p] if n == name else upstream(n))
                         or nearest(name, lambda n: users[n]))
        if out[name] is None and mover(name):
            out[name] = (nearest(name, upstream)
                         or nearest(name, lambda n: users[n]))
    return out


def load(path: str) -> dict:
    """The window span, the host spans (``SPANS``) and per-device op and
    program events: ops as (name, start_ns, end_ns, program or None),
    programs as (name, start_ns, end_ns).  A CPU trace has no device plane; there the
    op events are the host-thread events that name their HLO instruction,
    and each thread that runs them stands for a device."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            dev_name = plane.name if on_device else f"{plane.name} {line.name}"
            kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if on_device and kind is None:
                continue
            for ev in line.events:
                if not on_device and (ev.name == xplane.WINDOW_SPAN
                                      or ev.name in SPANS):
                    host.append((ev.name, ev.start_ns, ev.end_ns))
                    continue
                stats = dict(ev.stats)
                if not on_device and "hlo_op" not in stats:
                    continue
                dev = devices.setdefault(dev_name, {"ops": [], "modules": []})
                if kind == "modules":
                    dev["modules"].append((program_name(ev.name), ev.start_ns, ev.end_ns))
                    continue
                name = stats.get("hlo_op") or xplane.op_name(ev.name)
                dev["ops"].append((name, ev.start_ns, ev.end_ns,
                                   program_name(stats.get("hlo_module"))))
    return {"host": host, "devices": devices}


def self_times(ops: list) -> list[float]:
    """Each op's duration less the ops nested directly in it (events on one
    device line nest properly: a ``while`` holds its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [float(o[2] - o[1]) for o in ops]
    stack: list[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def label_innermost(gap: tuple[float, float], spans: list) -> str:
    """The host span that covers most of a gap, each instant counted for
    the innermost span covering it (the latest to start); 'none' if none."""
    lo, hi = gap
    inside = [(n, s, e) for n, s, e in spans if s < hi and e > lo]
    edges = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)
                               if lo < t < hi})
    cover = collections.Counter()
    for a, b in zip(edges, edges[1:]):
        over = [(s, -e, n) for n, s, e in inside if s <= a and e >= b]
        if over:
            cover[max(over)[2]] += b - a
    return cover.most_common(1)[0][0] if cover else "none"


def reduce(trace: dict, hlo: dict) -> dict:
    """Self seconds per (program, scope) and program runs inside the trace's
    window, and idle seconds per innermost host span, averaged over devices.
    ``hlo``: program name -> its ``hlo_scopes`` map; an op of a program not
    in it counts under scope ``None``."""
    lo, hi = xplane.window(trace["host"])
    spans = [(n, s, e) for n, s, e in trace["host"]
             if n != xplane.WINDOW_SPAN and e > lo and s < hi]
    devs = {k: v for k, v in trace["devices"].items() if v["ops"]}
    if not devs:
        raise ValueError("trace has no device plane with ops")
    scoped = collections.defaultdict(collections.Counter)
    runs = collections.Counter()
    idle = collections.Counter()
    top_gaps = []
    for dev in devs.values():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in mods:
            if s >= lo and e <= hi:
                runs[name] += 1
        for op, own in zip(dev["ops"], self_times(dev["ops"])):
            name, s, e, prog = op
            if not (s >= lo and e <= hi):
                continue
            if prog is None:
                j = bisect.bisect_right(starts, s) - 1
                prog = mods[j][0] if j >= 0 and mods[j][2] >= e else None
            scoped[prog][hlo.get(prog, {}).get(name)] += own
        merged = xplane.merge([(s, e) for _, s, e, _ in dev["ops"]], lo, hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for i in range(0, len(edges), 2):
            g = (edges[i], edges[i + 1])
            if g[1] > g[0]:
                label = label_innermost(g, spans)
                idle[label] += g[1] - g[0]
                top_gaps.append([label, (g[1] - g[0]) * 1e-9])
    n = len(devs)
    top_gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "scoped": {p: {sc: t * 1e-9 / n for sc, t in c.items()}
                   for p, c in scoped.items()},
        "runs": {p: c / n for p, c in runs.items()},
        "idle_by_span": {k: v * 1e-9 / n for k, v in idle.items()},
        "top_gaps": top_gaps[:10],
    }


def program_name(program: str | None) -> str | None:
    """A program event's name without the id some traces append
    (``jit_engine_decode(2728503979350226886)``)."""
    return None if program is None else program.split("(", 1)[0]


def per_run_ms(red: dict, program: str) -> dict:
    """Self milliseconds per run of one program, by scope."""
    runs = red["runs"].get(program)
    if not runs:
        return {}
    return {sc: 1e3 * t / runs for sc, t in red["scoped"].get(program, {}).items()}
