"""The profile scopes of the serving path (``jax.named_scope``): present in
the compiled ``op_name``s of the rwkv6, zamba2 and deepseek-v3 smoke
configs' prefill and decode steps, covering every matrix product, and
changing no instruction."""

import contextlib
import hashlib
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
    "deepseek-v3-671b": {"serve.prefill", "serve.decode", "embed", "layers",
                         "block", "attn", "attn_core", "mlp", "moe", "router",
                         "dispatch", "experts", "dense", "unembed"},
}
# Every matrix product lies under one of these: a projection, a sequence
# mixer, the held experts' grouped matmuls or the expert layer's combine.
GEMM_SCOPES = {"dense", "unembed", "wkv", "ssd", "attn_core", "experts", "dispatch"}
STEPS = [(arch, step) for arch in EXPECTED for step in ("prefill", "decode")]
# sha256 of ``_computations`` of the rwkv6 and zamba2 smoke entries, recorded
# on the tree before MLA gained YaRN and the expert layer its held share and
# dropless dispatch: neither change may reach these models. A change meant
# to alter their programs records the new digests.
BEFORE = {
    ("rwkv6-1.6b", "prefill"): "5ad617666453c990805c0c5f14384fda911001ef73c3f3f391fb7bf351c6ea46",
    ("rwkv6-1.6b", "decode"): "00b1eaaa512c86fbd58e0da17bed9b8b95d137342e3139560a907427e9ccb752",
    ("zamba2-1.2b", "prefill"): "9f04f46dea9e2945d334f4d3ab31551e4e655abf1e1578aaf21bfe5db5816646",
    ("zamba2-1.2b", "decode"): "be182e0d57c50de86e574350cc42ef9a72a1e6bcb261d8b3cbedc9ffe77cf349",
}

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


def test_mla_and_expert_scopes_in_prefill(compiled):
    """MLA's ``attn``/``attn_core`` and the expert layer's ``moe`` with
    ``router``, ``dispatch`` and ``experts`` beneath it name the compiled
    prefill's ops, in that nesting."""
    paths = {"/".join(c for c in m.group(1).split("/")[:-1] if "(" not in c)
             for ln in _instructions(compiled("deepseek-v3-671b", "prefill")).values()
             if (m := re.search(r'op_name="([^";]*)', ln))}
    for want in ("block/attn/", "attn/attn_core", "block/moe/router", "moe/dispatch",
                 "moe/experts", "moe/dense"):
        assert any(want in p + "/" for p in paths), want


@pytest.mark.parametrize("arch,step", sorted(BEFORE))
def test_entries_compile_as_before(compiled, arch, step):
    """The rwkv6 and zamba2 entries hold the instructions recorded before
    (metadata stripped, fused instructions named by place)."""
    lines = [f"{c}\t{ln}" for c, ln in _computations(compiled(arch, step))]
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest() == BEFORE[arch, step]


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
