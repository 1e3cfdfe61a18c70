"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time, and idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData``. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per HLO
instruction run, named by its HLO text
(``%tsm2r_pallas.1 = bf16[4096,64]{...} custom-call(bf16[4096,2048]{...} %x, ...)``),
and whose line ``XLA Modules`` holds one event per program run. Host
threads are lines of ``/host:CPU``; the benchmark's own spans there are
``jax.profiler.TraceAnnotation`` events named ``bench.*``.

Device timestamps count from the device's own origin. They are put on the
host clock by the shift that makes every program start no earlier than
the host's ``DoEnqueueProgram`` that launched it, the tightest such one
exactly at it (programs and launches are paired in order).
"""

from __future__ import annotations

import collections
import dataclasses
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
LAUNCH_EVENT = "DoEnqueueProgram"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

# TSM2X kernels as named in the HLO: the launchers in src/repro/kernels/.
TSM2X_FAMILIES = re.compile(
    r"^(tsm2r|tsm2l|tsmt)(_q8)?_pallas(_split)?$|^sum_partials_pallas$")

_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\](\{[^}]*\})?")
# Ops that enclose other ops' events (a scan's loop): their time is
# listed by their children.
CONTAINERS = ("while", "conditional", "call")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "f8e4m3fn": 1, "f8e5m2": 1}


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]        # device plane -> XLA Ops events
    modules: dict[str, list[Event]]    # device plane -> XLA Modules events
    host: list[Event]                  # every host-thread event
    shift_ns: float                    # device clock + shift = host clock

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def window(self) -> tuple[float, float]:
        """(start, end) on the host clock of the ``bench.window`` span, else
        of all device ops."""
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if spans:
            return spans[0].start_ns, spans[-1].end_ns
        evs = [e for ops in self.ops.values() for e in ops]
        return (min(e.start_ns for e in evs) + self.shift_ns,
                max(e.end_ns for e in evs) + self.shift_ns)

    def ops_in_window(self, device: str, whole: bool = False) -> list[Event]:
        """Device ops on the host clock, clipped to the window; with
        ``whole``, only the ops that lie entirely inside it, unclipped."""
        lo, hi = self.window()
        out = []
        for e in self.ops[device]:
            s, t = e.start_ns + self.shift_ns, e.end_ns + self.shift_ns
            if whole:
                if lo <= s and t <= hi:
                    out.append(Event(e.name, s, t - s))
                continue
            s, t = max(s, lo), min(t, hi)
            if t > s:
                out.append(Event(e.name, s, t - s))
        return out


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def launch_shift(modules: list[Event], launches: list[Event]) -> float:
    """Shift that puts device program starts on the host clock (see the
    module docstring): the last programs are paired with the last
    launches, in order. 0 when there is nothing to pair."""
    n = min(len(modules), len(launches))
    if not n:
        return 0.0
    mods = sorted(modules, key=lambda e: e.start_ns)[-n:]
    lchs = sorted(launches, key=lambda e: e.start_ns)[-n:]
    return max(l.start_ns - m.start_ns for m, l in zip(mods, lchs))


def read(path) -> Trace:
    from jax.profiler import ProfileData

    ops, modules, host = {}, {}, []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    if not ops:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane with an "
                         f"{OPS_LINE!r} line")
    launches = [e for e in host if e.name == LAUNCH_EVENT]
    shift = launch_shift(modules.get(sorted(ops)[0], []), launches)
    return Trace(ops=ops, modules=modules, host=host, shift_ns=shift)


# ---------------------------------------------------------------------------
# Intervals
# ---------------------------------------------------------------------------

def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: list[Event]) -> float:
    return sum(e - s for s, e in merge((ev.start_ns, ev.end_ns) for ev in events))


def gaps(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals inside [lo, hi] between the union of ``events``."""
    out, t = [], lo
    for s, e in merge((ev.start_ns, ev.end_ns) for ev in events):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


# ---------------------------------------------------------------------------
# HLO op names and shapes
# ---------------------------------------------------------------------------

def op_name(hlo: str) -> str:
    """``%tsm2r_pallas.1 = ...`` -> ``tsm2r_pallas.1``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_family(hlo: str) -> str:
    """Instruction name without its numeric suffix; fusions keep their kind
    (``fusion:kOutput``), since XLA names most fusions alike."""
    stem = re.sub(r"(\.\d+)+$", "", op_name(hlo))
    kind = re.search(r"\bkind=(k\w+)", hlo)
    if kind and "fusion" in stem:
        return f"{stem}:{kind.group(1)}"
    return stem


def _split(hlo: str):
    """(output text, opcode, operand text) of one HLO instruction."""
    rhs = hlo.split(" = ", 1)[1] if " = " in hlo else hlo
    m = re.search(r"\s([a-z][\w\-]*)\(", rhs)
    if not m:
        return rhs, "", ""
    depth, i = 0, m.end() - 1
    for j in range(i, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[j], 0)
        if depth == 0:
            break
    return rhs[:m.start()], m.group(1), rhs[i + 1:j]


def opcode(hlo: str) -> str:
    return _split(hlo)[1]


def typed_shapes(hlo: str):
    """(outputs, operands) of one HLO instruction as lists of
    (dtype, dims, memory space); space 0 is HBM, 1 the on-chip VMEM that
    XLA may assign a buffer to (``S(1)`` in its layout)."""
    out_txt, _, args_txt = _split(hlo)

    def parse(txt):
        out = []
        for dt, dims, layout in _SHAPE.findall(txt):
            space = re.search(r"S\((\d+)\)", layout or "")
            out.append((dt, tuple(int(x) for x in dims.split(",") if x),
                        int(space.group(1)) if space else 0))
        return out
    return parse(out_txt), parse(args_txt)


def nbytes(shapes, space: int | None = None) -> int:
    """Bytes of (dtype, dims, space) shapes, of one memory space if given."""
    total = 0
    for dt, dims, sp in shapes:
        if space is not None and sp != space:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n * _BYTES[dt]
    return total


def gemm_cost(hlo: str) -> tuple[float, float, str]:
    """(flops, HBM bytes, operand dtype) of a TSM2X GEMM launch from its HLO
    shapes: A[m,k] @ B[k,n] (tsm2r/tsm2l) or X[m,a]^T Y[m,b] (tsmt). Bytes
    are the operands and outputs at their dtypes, without tile padding,
    that live in HBM; an operand XLA placed in VMEM moves no HBM bytes.
    Quantized launches carry scale sidecars as further operands."""
    outs, args = typed_shapes(hlo)
    if len(args) < 2 or len(args[0][1]) != 2 or len(args[1][1]) != 2:
        raise ValueError(f"not a 2-D GEMM launch: {hlo[:120]}")
    (da, (m, k), _), (_, (_, n), _) = args[0], args[1]
    # tsmt's X[m,a]^T Y[m,b] reads as m=m, k=a, n=b: the same 2mkn.
    return 2.0 * m * k * n, float(nbytes(args, 0) + nbytes(outs, 0)), da


# ---------------------------------------------------------------------------
# Breakdown
# ---------------------------------------------------------------------------

def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """The op families that took most device time; loop containers are
    left out, since their children's events are listed."""
    fam: dict[str, float] = collections.defaultdict(float)
    for e in events:
        if opcode(e.name) not in CONTAINERS:
            fam[op_family(e.name)] += e.dur_ns * 1e-9
    return [[k, v] for k, v in sorted(fam.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(trace: Trace, device: str, n: int = 10) -> list[list]:
    """Idle device seconds in the window, summed by the innermost
    ``bench.*`` host span open at each gap's midpoint."""
    lo, hi = trace.window()
    spans = sorted((e for e in trace.host
                    if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN),
                   key=lambda e: e.dur_ns)
    tot: dict[str, float] = collections.defaultdict(float)
    for s, e in gaps(trace.ops_in_window(device), lo, hi):
        mid = 0.5 * (s + e)
        name = next((sp.name for sp in spans
                     if sp.start_ns <= mid <= sp.end_ns), "outside bench spans")
        tot[name] += (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
