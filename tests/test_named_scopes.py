"""The profile scopes of the serving path (``jax.named_scope``): present in
the compiled ``op_name``s of the rwkv6 and zamba2 smoke configs' prefill and
decode steps, covering every matrix product, and changing no instruction."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.models import model
from repro.serve import engine

EXPECTED = {
    "rwkv6-1.6b": {"serve.prefill", "serve.decode", "embed", "layers", "block",
                   "time_mix", "wkv", "intra", "state", "channel_mix", "dense",
                   "lora", "unembed"},
    "zamba2-1.2b": {"serve.prefill", "serve.decode", "embed", "layers", "block",
                    "shared_block", "mamba", "ssd", "attn", "attn_core", "mlp",
                    "dense", "lora", "unembed"},
}
# Every matrix product lies under one of these: a projection or a sequence mixer.
GEMM_SCOPES = {"dense", "unembed", "wkv", "ssd", "attn_core"}
STEPS = [(arch, step) for arch in EXPECTED for step in ("prefill", "decode")]

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_META = re.compile(r", metadata=\{[^}]*\}")


def _compiled(arch: str, step: str) -> str:
    cfg = registry.get_config(arch, smoke=True)
    params = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(cfg, 2, 24))
    prefill_step, decode_step = engine.make_serve_fns(cfg)
    if step == "prefill":
        toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        lowered = jax.jit(prefill_step).lower(params, {"tokens": toks}, cache)
    else:
        toks = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jax.jit(decode_step).lower(params, toks, pos, cache)
    return lowered.compile().as_text()


@pytest.fixture(scope="module")
def compiled():
    texts: dict = {}

    def get(arch, step):
        if (arch, step) not in texts:
            texts[arch, step] = _compiled(arch, step)
        return texts[arch, step]
    return get


def _instructions(text: str) -> dict[str, str]:
    """Instruction name -> its line, for every computation of the module."""
    return {m.group(1): ln for ln in text.splitlines() if (m := _INSTR.match(ln))}


def _components(line: str) -> set[str]:
    m = re.search(r'op_name="([^"]*)"', line)
    return set(m.group(1).split(";")[0].split("/")[:-1]) if m else set()


def _opcode(line: str) -> str:
    m = re.search(r"\s([a-z][\w\-]*)\(", line.split(" = ", 1)[1])
    return m.group(1) if m else ""


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_each_scope_appears(compiled, arch):
    seen = set()
    for step in ("prefill", "decode"):
        for line in _instructions(compiled(arch, step)).values():
            seen |= _components(line)
    assert EXPECTED[arch] <= seen, EXPECTED[arch] - seen
    assert any(c.startswith("tsmm.") for c in seen)


@pytest.mark.parametrize("arch,step", STEPS)
def test_every_matrix_product_is_scoped(compiled, arch, step):
    """Every dot or convolution, and every fusion holding one, lies under a
    projection or sequence-mixer scope: a guard against a refactor that
    drops a scope."""
    text = compiled(arch, step)
    ins = _instructions(text)
    users: dict[str, list[str]] = {}
    for name, line in ins.items():
        for arg in re.findall(r"%([\w.\-]+)", _META.sub("", line.split(" = ", 1)[1])):
            users.setdefault(arg, []).append(name)
    body: dict[str, list[str]] = {}
    comp = None
    for ln in text.splitlines():
        if c := re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", ln):
            comp = c.group(1)
        elif (m := _INSTR.match(ln)) and comp:
            body.setdefault(comp, []).append(m.group(1))

    def scoped(name):
        own = _components(ins[name])
        if own:
            return bool(own & GEMM_SCOPES)
        # A product XLA rewrote without metadata (a degenerate dimension
        # dropped) is judged by the instructions that consume it.
        return bool(users.get(name)) and all(scoped(u) for u in users[name])

    gemms = {n for n, ln in ins.items() if _opcode(ln) in ("dot", "convolution")}
    assert gemms
    fusions = {n for n, ln in ins.items()
               if (c := re.search(r"\bcalls=%([\w.\-]+)", ln))
               and gemms & set(body.get(c.group(1), []))}
    bad = [n for n in sorted(gemms | fusions) if not scoped(n)]
    assert not bad, [ins[n][:300] for n in bad]


@pytest.mark.parametrize("arch,step", STEPS)
def test_scopes_change_no_instruction(compiled, monkeypatch, arch, step):
    """Compiled with ``jax.named_scope`` made a no-op, the module holds the
    same instructions, under the same names wherever the device runs them."""
    scoped = compiled(arch, step)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled(arch, step)
    names = lambda text: set().union(*map(_components, _instructions(text).values()))
    assert {"layers", "block", "dense"} <= names(scoped)
    assert not {"layers", "block", "dense"} & names(bare)
    assert _computations(scoped) == _computations(bare)


def _computations(text: str) -> list[tuple[str, str]]:
    """(computation, instruction line) with metadata stripped, in order.

    Inside a fused computation, instruction names are renumbered by their
    place: the uniquifier that numbers them there (``broadcast_in_dim.39``)
    counts ops of the lowered module, which a scope can split or merge,
    while the fusions the device runs keep their names. Operands always lie
    in their own computation, so the renumbering keeps every reference."""
    called = set(re.findall(r"\bcalls=%([\w.\-]+)", text))
    out, comp, local = [], None, {}
    for ln in text.splitlines():
        if c := re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", ln):
            comp, local = c.group(1), {}
        elif (m := _INSTR.match(ln)) and comp:
            ln = _META.sub("", ln)
            if comp in called:
                local[m.group(1)] = f"i{len(local)}"
                ln = re.sub(r"%([\w.\-]+)", lambda r: "%" + local.get(r.group(1), r.group(1)), ln)
            out.append((comp, ln))
    return out
