"""Device time per program scope (``bench/scopes.py``): on a synthetic trace
of a hand-written compiled module, on a scoped trace recorded on a TPU v5e
(``data/scoped.xplane.pb`` and ``data/scoped.hlo.txt``, made by
``record_scoped_trace.py``), and each kind's ``programs/<kind>.py`` against
the entry its generator runs, at a small size on the CPU."""

from __future__ import annotations

import pathlib
import re
import types

import numpy as np
import pytest

from bench_testlib import TRAFFIC, small_sizes
from bench import load, scopes, serving, tracing

DATA = pathlib.Path(__file__).parent / "data"
DEV = "/device:TPU:0"
P = "jit(step)/serve.prefill/layers/while/body/closed_call"

# A module with a projection fusion whose root is a convert, a fusion
# without a dot, a dot XLA made without metadata, a loop, and ops under
# ``block`` and ``shared_block``.
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_proj (p0: bf16[8,16], p1: bf16[16,32]) -> bf16[8,32] {{
  %p0 = bf16[8,16]{{1,0}} parameter(0)
  %p1 = bf16[16,32]{{1,0}} parameter(1)
  %convolution.1 = f32[8,32]{{1,0}} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={{op_name="{P}/block/time_mix/dense/tsmm.dense/dot_general"}}
  ROOT %convert.1 = bf16[8,32]{{1,0}} convert(%convolution.1), metadata={{op_name="{P}/block/time_mix/convert_element_type"}}
}}

%fused_mul (p0.1: f32[8,32]) -> f32[8,32] {{
  %p0.1 = f32[8,32]{{1,0}} parameter(0)
  ROOT %multiply.1 = f32[8,32]{{1,0}} multiply(%p0.1, %p0.1), metadata={{op_name="{P}/block/time_mix/wkv/mul"}}
}}

%body (arg: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {{
  %arg = (s32[], bf16[8,16]{{1,0}}) parameter(0)
  %fusion.1 = bf16[8,32]{{1,0}} fusion(%x, %w), kind=kOutput, calls=%fused_proj, metadata={{op_name="{P}/block/time_mix/convert_element_type"}}
  %dot.7 = f32[8,32]{{1,0}} dot(%a, %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  %fusion.2 = f32[8,32]{{1,0}} fusion(%dot.7), kind=kLoop, calls=%fused_mul, metadata={{op_name="{P}/block/time_mix/wkv/mul"}}
  %dynamic-slice.3 = bf16[16,32]{{1,0}} dynamic-slice(%ws, %i), dynamic_slice_sizes={{1,16,32}}, metadata={{op_name="{P}/dynamic_slice"}}
  %add.4 = f32[8,32]{{1,0}} add(%c, %c), metadata={{op_name="{P}/shared_block/add"}}
  ROOT %copy.5 = bf16[8,16]{{1,0}} copy(%y)
}}

ENTRY %main (x: bf16[8,16]) -> bf16[8,16] {{
  %x = bf16[8,16]{{1,0}} parameter(0)
  %while.1 = (s32[], bf16[8,16]{{1,0}}) while(%t), condition=%cond, body=%body, metadata={{op_name="jit(step)/serve.prefill/layers/while"}}
  ROOT %gather.9 = bf16[8,16]{{1,0}} gather(%x, %i), metadata={{op_name="jit(step)/serve.prefill/embed/jit(_take)/gather"}}
}}
"""


def _event(hlo_line: str, start: float, dur: float) -> tracing.Event:
    """The ``XLA Ops`` event of one instruction: its line, with operand shapes
    as the profiler prints them and no metadata."""
    head, rest = hlo_line.strip().split(" = ", 1)
    rest = rest.split(", metadata=")[0]
    op = tracing.opcode(hlo_line)
    rest = rest.replace(f"{op}(", f"{op}(f32[1]{{0}} ", 1)
    return tracing.Event(f"{head} = {rest}", start, dur)


def _instructions(text: str) -> list[str]:
    return [re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in text.splitlines()
            if re.match(r"^\s*(ROOT\s+)?%[\w.\-]+ = ", ln)]


def _line(name: str) -> str:
    lines = (ln.strip().removeprefix("ROOT ") for ln in HLO.splitlines())
    return next(ln for ln in lines if ln.startswith(f"%{name} ="))


# One round: the loop spans 0-100 ns; its children leave 10 ns uncovered.
ROUND = [("while.1", 0, 100), ("fusion.1", 0, 40), ("dot.7", 40, 10),
         ("fusion.2", 50, 20), ("dynamic-slice.3", 70, 5), ("add.4", 75, 10),
         ("copy.5", 95, 5), ("gather.9", 100, 10)]


def _attribute(trace, text):
    return scopes.attribute(trace, scopes.parse(text), scopes.module_name(text))


def _trace(rounds=2, rename=None, period=1000.0):
    ops, mods = [], []
    for r in range(rounds):
        t0 = r * period
        mods.append(tracing.Event("jit_step(123)", t0, 110))
        for name, s, d in ROUND:
            e = _event(_line(name), t0 + s, d)
            if rename and name == rename[0]:
                e = tracing.Event(e.name.replace(rename[1], rename[2], 1), e.start_ns,
                                  e.dur_ns)
            ops.append(e)
    return tracing.Trace(ops={DEV: ops}, modules={DEV: mods}, host=[], shift_ns=0.0)


def test_parse_applies_the_fusion_and_no_metadata_rules():
    ins = scopes.parse(HLO)
    # The projection fusion counts for its convolution, not its convert root.
    assert scopes.scope_path(ins["fusion.1"].op_name)[-2:] == ("dense", "tsmm.dense")
    # A fusion without a dot keeps its own op_name.
    assert scopes.scope_path(ins["fusion.2"].op_name)[-1] == "wkv"
    # A dot without metadata takes its first user's.
    assert scopes.scope_path(ins["dot.7"].op_name)[-1] == "wkv"
    assert ins["copy.5"].op_name == ""
    assert ins["fusion.1"].outputs == (("bf16", (8, 32)),)
    assert scopes.module_name(HLO) == "jit_step"


def test_scope_path_whole_components():
    path = scopes.scope_path(f"{P}/shared_block/attn/attn_core/while/body/dot_general")
    assert path == ("serve.prefill", "layers", "shared_block", "attn", "attn_core")
    assert "block" not in path and scopes.sequence_mixer(path)
    assert not scopes.layer_scan(path)
    assert scopes.layer_scan(("serve.prefill", "layers"))
    assert not scopes.layer_scan(("serve.prefill", "layers", "block", "mamba"))
    # Einsum specs, nested jits and merged names are JAX's or XLA's, not scopes.
    assert scopes.scope_path(f"{P}/block/mamba/ssd/jit(cumsum)/mamba2_fwd/x") == \
        ("serve.prefill", "layers", "block", "mamba", "ssd")
    assert scopes.scope_path(f"{P}/block/wkv/bcthd,bcshd->bctsh/dot_general") == \
        ("serve.prefill", "layers", "block", "wkv")
    assert scopes.scope_path(f"{P}/block/wkv/reshape;block/wkv/reshape")[-1] == "wkv"
    assert scopes.scope_path("jit(step)/while/body/dot_general") == ()


def test_attribution_and_ms_per_round():
    att = _attribute(_trace(rounds=2), HLO)
    assert att is not None and att.matched_share() == pytest.approx(1.0)
    assert att.busy_ns == 2 * 110
    # Per round: projection 40, mixer 10 + 20, scan's own 5 + the loop's 10
    # uncovered, shared_block 10, embed 10, copy 5 with no program scope.
    run = types.SimpleNamespace(trace=None, record=types.SimpleNamespace(rounds=2))
    run._scopes = att
    assert scopes.ms_per_round(run, scopes.projection) == pytest.approx(40e-6)
    assert scopes.ms_per_round(run, scopes.sequence_mixer) == pytest.approx(30e-6)
    assert scopes.ms_per_round(run, scopes.layer_scan) == pytest.approx(15e-6)
    assert att.ns[(scopes.UNSCOPED,)] == 2 * 5
    assert sum(att.ns.values()) == att.busy_ns
    table = scopes.report(att, 2, "jit_step")
    assert "serve.prefill/layers/block/time_mix" in table
    assert "serve.prefill/layers/block/time_mix/dense" not in table      # cut


@pytest.mark.parametrize("rename", [
    ("fusion.1", "%fusion.1 =", "%fusion.99 ="),        # a name the module lacks
    ("fusion.1", "bf16[8,32]", "f32[8,32]"),            # another output shape
])
def test_mismatch_over_one_percent_gives_none(rename, capsys):
    assert _attribute(_trace(rename=rename), HLO) is None
    assert "no attribution" in capsys.readouterr().err


def test_small_mismatch_is_tolerated_and_other_programs_ignored():
    tr = _trace(rounds=1)
    # A round of 11000 ns beside 80 ns of an unknown op and 10 ns of another
    # program's op of the same name: 90 of 11090 ns unmatched, under 1%.
    ops = [tracing.Event(e.name, e.start_ns * 100, e.dur_ns * 100) for e in tr.ops[DEV]]
    ops.append(_event(_line("add.4").replace("%add.4", "%add.77"), 11000, 80))
    mods = [tracing.Event("jit_step(1)", 0, 11000), tracing.Event("jit_other(2)", 20000, 10)]
    ops.append(_event(_line("add.4"), 20000, 10))       # same name, other program
    tr = tracing.Trace(ops={DEV: ops}, modules={DEV: mods}, host=[], shift_ns=0.0)
    att = _attribute(tr, HLO)
    assert att is not None
    assert att.matched_share() == pytest.approx(11000 / 11090)


def test_names_of_the_executable_that_ran_are_joined():
    """An executable compiled from a program without scopes (a shared cache
    entry) numbers a name differently: its events take the scoped
    program's op_names by place."""
    ran_text = HLO.replace("%add.4 ", "%add.40 ")
    for scope in ("/block", "/time_mix", "/wkv", "/shared_block"):
        ran_text = ran_text.replace(scope + "/", "/")
    ran, own = scopes.parse(ran_text), scopes.parse(HLO)
    joined = scopes.relabel(ran, own)
    assert joined["add.40"].op_name == own["add.4"].op_name
    tr = _trace(rename=("add.4", "%add.4 =", "%add.40 ="))
    att = scopes.attribute(tr, joined, "jit_step")
    assert att.matched_share() == pytest.approx(1.0)
    assert att.select_ns(lambda p: "shared_block" in p) == 2 * 10
    # Other instructions, or another order, are not joined.
    assert scopes.relabel(scopes.parse(HLO.replace("bf16[16,32]{1,0} dynamic-slice",
                                                   "bf16[16,64]{1,0} dynamic-slice")),
                          own) is None
    assert scopes.relabel(dict(reversed(list(ran.items()))), own) is None


def test_program_without_scopes_reads_nothing(monkeypatch, capsys):
    bare = HLO
    for scope in ("/serve.prefill", "/layers", "/block", "/time_mix", "/dense",
                  "/tsmm.dense", "/wkv", "/shared_block", "/embed"):
        bare = bare.replace(scope + "/", "/")
    att = _attribute(_trace(), bare)
    assert att is not None and set(att.ns) == {(scopes.UNSCOPED,)}
    monkeypatch.setattr(scopes, "_program", lambda run: ("jit_step", scopes.parse(bare)))
    run = types.SimpleNamespace(trace=_trace(), chips=1,
                                record=types.SimpleNamespace(rounds=2))
    assert scopes.ms_per_round(run, scopes.projection) is None
    assert "names no scopes" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# The recorded scoped trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return tracing.read(DATA / "scoped.xplane.pb"), (DATA / "scoped.hlo.txt").read_text()


def test_recorded_trace_is_attributed(recorded):
    trace, text = recorded
    att = _attribute(trace, text)
    assert att is not None and att.matched_share() >= scopes.MIN_MATCHED
    ins = scopes.parse(text)
    kern = [e for e in trace.ops_in_window(DEV)
            if tracing.op_family(e.name) == "tsm2r_pallas"]
    assert kern
    for e in kern:
        path = scopes.scope_path(ins[tracing.op_name(e.name)].op_name)
        assert path[-2:] == ("dense", "tsmm.tsm2r") and "layers" in path
    kern_ns = sum(e.dur_ns for e in kern)
    assert att.select_ns(lambda p: "tsmm.tsm2r" in p) == pytest.approx(kern_ns)
    assert att.select_ns(scopes.projection) >= kern_ns


# ---------------------------------------------------------------------------
# Each kind's compiled text is the program its generator runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_compiled_text_is_the_entry_the_generator_ran(traffic):
    sizes = small_sizes("rwkv6-1.6b")
    _, cfg_mod = load.config("rwkv6-1.6b")
    mix = TRAFFIC[traffic]
    seed = 2**33 + 5
    gen = load.kind(mix["kind"]).Generator(mix, cfg_mod, sizes, seed)
    cfg = serving.program_config(cfg_mod, sizes)
    gen.build(cfg)
    params = serving.build_params(cfg_mod, sizes, seed)
    gen.setup(params)
    if mix["kind"] == "prefill":
        ran = gen._prefill.lower(params, gen.prompts(0))
    else:
        ran = gen._decode.lower(params, gen._tok, np.int32(gen._pos), gen._cache)
    run = types.SimpleNamespace(traffic=mix, cfg_mod=cfg_mod, sizes=sizes, generator=gen)
    prog = load._module(load.BENCH / "programs" / f"{mix['kind']}.py")
    text = prog.compiled_text(run)
    ran_text = ran.compile().as_text()
    # The same instructions with the same op_names; only the stack-frame
    # tables of the metadata differ, with the Python stack each was traced from.
    assert _instructions(text) == _instructions(ran_text)
    assert scopes.parse(text) == scopes.parse(ran_text)
    assert scopes.module_name(text) == f"jit_{mix['kind']}"
