"""Device time of a traced window per program scope.

The program names its layers with ``jax.named_scope``; each scope is a
component of the ``op_name`` in the metadata of every HLO instruction it
emits. The profiler's ``XLA Ops`` events name the instruction
(``%fusion.12 = ...``) but carry no ``op_name``, so the names come from the
optimized HLO of the program the window ran:

1. ``programs/<kind>.py`` (found by the traffic's ``kind``) rebuilds the
   kind's timed entry and returns ``compiled.as_text()`` at the window's
   shapes: with the persistent compilation cache that ``run.py``
   configures, the executable the window ran. ``_program`` also compiles
   it with its metadata in the cache key, for this program's own
   ``op_name``s (see there). It runs only in per-layer reads, after the
   window, at most once per run.
2. ``parse`` maps each instruction name to its opcode, output shapes and
   ``op_name``. A fusion takes the ``op_name`` of the dot or convolution it
   holds, where it holds one, else its own (its root's): a projection
   fusion whose root is a ``convert`` counts for the projection. A dot or
   convolution XLA made without metadata takes that of its first user.
3. ``attribute`` matches the window's ``XLA Ops`` events inside that
   program's ``XLA Modules`` events by instruction name, checking opcode and
   output shapes. A loop's time not covered by its children's events goes
   to the loop's own ``op_name``. If the matched events cover less than
   ``MIN_MATCHED`` of device busy time it returns ``None``: a wrong
   attribution is worse than none.

A scope path is the program's scope components of an ``op_name``, in
order: JAX's own (``jit(...)``, ``while``, ``body``, ``closed_call``, einsum
specs, ...) and the primitive are left out, and so is everything under a
nested ``jit(...)``. Metrics select paths by whole components, so ``block``
does not match ``shared_block``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys
import time

from bench import load, tracing

MIN_MATCHED = 0.99
# Components JAX adds to a name stack around the program's own scopes.
JAX_COMPONENTS = {"while", "body", "cond", "closed_call", "checkpoint"}
# The printed table cuts paths this many components below ``layers``.
CUT_BELOW_LAYERS = 2
_SCOPE = re.compile(r"^[A-Za-z_][\w.]*$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
GEMM_OPCODES = ("dot", "convolution")
UNSCOPED = "(no program scope)"


@dataclasses.dataclass(frozen=True)
class Instr:
    opcode: str
    outputs: tuple      # ((dtype, dims), ...)
    op_name: str        # "" where the instruction has no metadata


def _note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def module_name(text: str) -> str:
    m = _MODULE.search(text)
    if not m:
        raise ValueError("no HloModule line in the compiled text")
    return m.group(1)


def _outputs(line: str) -> tuple:
    return tuple((dt, dims) for dt, dims, _ in tracing.typed_shapes(line)[0])


def parse(text: str) -> dict[str, Instr]:
    """Instruction name -> ``Instr`` for every instruction of an optimized
    HLO module's text, with the fusion and no-metadata rules above."""
    raw: dict[str, str] = {}            # name -> line
    comps: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            raw[m.group(2)] = line
            comps[comp].append(m.group(2))
            continue
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
        elif line.strip() == "}":
            comp = None

    def own(name):
        m = _OP_NAME.search(raw[name])
        return m.group(1) if m else ""

    users: dict[str, list[str]] = collections.defaultdict(list)
    for name, line in raw.items():
        for arg in _OPERAND.findall(line.split(" = ", 1)[1].split(", metadata=")[0]):
            if arg in raw:
                users[arg].append(name)

    def gemm_in(name, seen=()):
        """(found, op_name) of the first dot or convolution under a fusion."""
        called = _CALLS.search(raw[name])
        if not called or called.group(1) in seen:
            return False, ""
        found = False
        for inner in comps.get(called.group(1), []):
            op = tracing.opcode(raw[inner])
            if op in GEMM_OPCODES:
                found = True
                if own(inner):
                    return True, own(inner)
            elif op == "fusion":
                f, n = gemm_in(inner, seen + (called.group(1),))
                found |= f
                if n:
                    return True, n
        return found, ""

    def from_user(name):
        for u in users.get(name, []):
            if own(u):
                return own(u)
        return ""

    out = {}
    for name, line in raw.items():
        op = tracing.opcode(line)
        op_name = own(name)
        gemm = op in GEMM_OPCODES
        if op == "fusion":
            gemm, inner = gemm_in(name)
            op_name = inner or op_name
        if gemm and not op_name:
            op_name = from_user(name)
        out[name] = Instr(op, _outputs(line), op_name)
    return out


def scope_path(op_name: str) -> tuple[str, ...]:
    """The program's scopes in an ``op_name``, outermost first. XLA joins
    the names of merged instructions with ``;``: the first is read."""
    parts = op_name.split(";")[0].split("/")[:-1]
    if parts and "(" in parts[0]:        # the outer jit
        parts = parts[1:]
    out = []
    for c in parts:
        if "(" in c:            # a nested jit: JAX's own function
            break
        if c not in JAX_COMPONENTS and _SCOPE.match(c):
            out.append(c)
    return tuple(out)


@dataclasses.dataclass
class Attribution:
    by_op: dict[tuple, float]   # (scope path, op family) -> device ns, over chips
    busy_ns: float              # device busy time, summed over chips
    chips: int

    @property
    def ns(self) -> dict[tuple, float]:
        """Scope path -> device ns."""
        out: dict[tuple, float] = collections.defaultdict(float)
        for (path, _), v in self.by_op.items():
            out[path] += v
        return dict(out)

    def matched_share(self) -> float:
        return sum(self.by_op.values()) / self.busy_ns if self.busy_ns else 0.0

    def select_ns(self, rule) -> float:
        return sum(v for p, v in self.ns.items() if p != (UNSCOPED,) and rule(p))

    def per_round_ms(self, ns: float, rounds: int) -> float:
        return ns / self.chips / rounds * 1e-6


def relabel(ran: dict[str, Instr], own: dict[str, Instr]) -> dict[str, Instr] | None:
    """``ran``'s instructions under ``own``'s ``op_name``s, paired in order;
    ``None`` unless both hold the same opcodes and output shapes in the same
    order. Two programs that differ only in metadata compile to the same
    instructions, but the uniquifier may number a few names differently."""
    if len(ran) != len(own):
        return None
    out = {}
    for (name, r), o in zip(ran.items(), own.values()):
        if (r.opcode, r.outputs) != (o.opcode, o.outputs):
            return None
        out[name] = Instr(r.opcode, r.outputs, o.op_name)
    return out


def attribute(trace: tracing.Trace, instrs: dict[str, Instr], program: str,
              chips: int = 1) -> Attribution | None:
    """Device time in the traced window per scope path, from the events of
    ``program`` whose instructions ``instrs`` holds (see the module
    docstring). ``None`` if the matched events cover less than
    ``MIN_MATCHED`` of busy time."""
    by_op: dict[tuple, float] = collections.defaultdict(float)
    mismatched: dict[str, float] = collections.defaultdict(float)
    seen: dict[str, tuple] = {}
    busy = 0.0
    lo, hi = trace.window()

    def info(name):
        """(is a loop, op family, scope path) of an event; the path is None
        if its name, opcode or output shapes differ from the program's."""
        if name not in seen:
            op = tracing.opcode(name)
            ins = instrs.get(tracing.op_name(name))
            ok = ins is not None and ins.opcode == op and ins.outputs == _outputs(name)
            seen[name] = (op in tracing.CONTAINERS, tracing.op_family(name),
                          (scope_path(ins.op_name) or (UNSCOPED,)) if ok else None)
        return seen[name]

    for dev in trace.devices[:chips]:
        mods = tracing.merge((m.start_ns + trace.shift_ns, m.end_ns + trace.shift_ns)
                             for m in trace.modules.get(dev, [])
                             if m.name.split("(")[0] == program)
        starts = [s for s, _ in mods]

        def add(name, t0, dur):
            _, family, path = info(name)
            i = bisect.bisect_right(starts, t0 + 0.5 * dur) - 1
            if path is None or i < 0 or t0 + 0.5 * dur > mods[i][1]:
                mismatched[family] += dur
            else:
                by_op[path, family] += dur

        ops = trace.ops_in_window(dev)
        busy += tracing.busy_ns(ops)
        leaves, loops = [], []
        for e in ops:
            (loops if info(e.name)[0] else leaves).append(e)
        for e in leaves:
            add(e.name, e.start_ns, e.dur_ns)
        # Loop time no child covers goes to the innermost open loop; loops
        # nest or are disjoint, so a stack swept in time order finds it.
        loops.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        open_loops, k = [], 0
        for s, t in tracing.gaps(leaves, lo, hi):
            mid = 0.5 * (s + t)
            while k < len(loops) and loops[k].start_ns <= mid:
                open_loops.append(loops[k])
                k += 1
            while open_loops and open_loops[-1].end_ns < mid:
                open_loops.pop()
            inner = next((e for e in reversed(open_loops) if e.end_ns >= mid), None)
            if inner is not None:
                s, t = max(s, inner.start_ns), min(t, inner.end_ns)
                add(inner.name, s, t - s)
    att = Attribution(by_op=dict(by_op), busy_ns=busy, chips=chips)
    if att.matched_share() < MIN_MATCHED:
        worst = sorted(mismatched.items(), key=lambda kv: -kv[1])[:5]
        _note(f"scopes: events of {program} matched by name and shape cover "
              f"{100 * att.matched_share():.3f}% of device busy time, under "
              f"{100 * MIN_MATCHED:g}%; no attribution. Unmatched ns by op: {worst}")
        return None
    return att


def cut(path: tuple) -> tuple:
    """A scope path cut at ``CUT_BELOW_LAYERS`` components below ``layers``."""
    if "layers" in path:
        return path[:path.index("layers") + 1 + CUT_BELOW_LAYERS]
    return path


def report(att: Attribution, rounds: int, program: str) -> str:
    """The per-round table: each cut scope path with the three op families
    that took most of its time."""
    tot: dict[tuple, float] = collections.defaultdict(float)
    fam: dict[tuple, dict] = collections.defaultdict(lambda: collections.defaultdict(float))
    for (p, f), v in att.by_op.items():
        tot[cut(p)] += v
        fam[cut(p)][f] += v
    unscoped = att.ns.get((UNSCOPED,), 0.0)
    lines = [f"scopes: {program}, device ms per round over {rounds} rounds; "
             f"{100 * att.matched_share():.3f}% of busy time matched; "
             f"{att.per_round_ms(unscoped, rounds):.6f} ms per round in ops with "
             f"no program scope; busy {att.per_round_ms(att.busy_ns, rounds):.6f} "
             "ms per round"]
    for p, v in sorted(tot.items(), key=lambda kv: -kv[1]):
        top = sorted(fam[p].items(), key=lambda kv: -kv[1])[:3]
        lines.append(f"  {att.per_round_ms(v, rounds):12.6f}  {'/'.join(p)}  ["
                     + ", ".join(f"{f} {att.per_round_ms(n, rounds):.6f}" for f, n in top)
                     + "]")
    return "\n".join(lines)


def _program(run) -> tuple[str, dict[str, Instr]] | None:
    """(module name, instructions) of the program the window ran, with this
    program's ``op_name``s.

    The persistent cache's key leaves metadata out, so the kind's
    ``compiled_text`` loads the executable the window ran, which may have
    been compiled from another program that differs only in metadata (a
    commit without the scopes, sharing the cache directory). With the
    metadata in the key it compiles this program's own, or loads them where
    an earlier traced run stored them; ``relabel`` joins the two."""
    import jax

    path = load.BENCH / "programs" / f"{run.traffic['kind']}.py"
    if not path.is_file():
        _note(f"scopes: no {path.relative_to(load.REPO)}")
        return None
    compiled_text = load._module(path).compiled_text
    ran_text = compiled_text(run)
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        own_text = compiled_text(run)
    finally:
        jax.config.update(key, before)
    own = parse(own_text)
    if ran_text in (None, own_text):
        return module_name(own_text), own
    ran = parse(ran_text)
    instrs = relabel(ran, own)
    if instrs is None:
        _note("scopes: the executable the window ran holds other instructions "
              "than this program compiles to; its names are not joined")
        return module_name(own_text), own
    _note(f"scopes: {sum(a != b for a, b in zip(ran, own))} of {len(own)} names "
          "of the executable the window ran differ from this program's; joined")
    return module_name(own_text), instrs


def of_run(run) -> Attribution | None:
    """The traced window's attribution, computed once per run and printed
    to stderr; ``None`` without a trace, without rounds, below
    ``MIN_MATCHED``, or where the program names no scopes."""
    if "_scopes" not in run.__dict__:
        run._scopes = _of_run(run)
    return run._scopes


def _of_run(run) -> Attribution | None:
    if run.trace is None or not run.record.rounds:
        return None
    t0 = time.perf_counter()
    program = _program(run)
    if program is None:
        return None
    t1 = time.perf_counter()
    att = attribute(run.trace, program[1], program[0], run.chips)
    _note(f"scopes: compiled texts {t1 - t0:.3f} s, attribution "
          f"{time.perf_counter() - t1:.3f} s")
    if att is None:
        return None
    if set(att.ns) <= {(UNSCOPED,)}:
        _note("scopes: the program names no scopes")
        return None
    _note(report(att, run.record.rounds, program[0]))
    return att


def ms_per_round(run, rule):
    """Device ms per round of the traced window in the scope paths that
    satisfy ``rule(path)``; ``None`` where ``of_run`` is."""
    att = of_run(run)
    if att is None:
        return None
    return att.per_round_ms(att.select_ns(rule), run.record.rounds)


# Rules of the metrics in ``metrics/`` (whole components).
def projection(path) -> bool:
    return "dense" in path or "unembed" in path


def sequence_mixer(path) -> bool:
    return "wkv" in path or "ssd" in path or "attn_core" in path


def layer_scan(path) -> bool:
    return "layers" in path and "block" not in path and "shared_block" not in path
