"""Every device instruction of a train step says which part of the model
it belongs to: the compiled ``tiny`` step carries the program's scopes in
its instructions' ``op_name``, and the rule the benchmark's reader uses
(``chipbench/scopes.classify``) tells the forward pass, the recompute and
the backward pass apart. Settled here on CPU programs, not on the chip:
``op_name`` is made by jax, before any backend."""

from __future__ import annotations

import contextlib
import functools
import importlib
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import optax
import pytest

from chipbench import scopes
from ray_tpu import models
from ray_tpu.models import transformer

# ``ray_tpu.ops.attention`` the attribute is the dispatch function.
attention_ops = importlib.import_module("ray_tpu.ops.attention")


def _traced_step(cfg, *, rows: int = 4, seq_len: int = 32,
                 accum_steps: int = 1):
    opt = optax.adamw(1e-3)
    state = jax.eval_shape(
        lambda k: models.init_train_state(k, cfg, opt), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq_len + 1), jnp.int32)}
    step = jax.jit(models.make_train_step(cfg, opt, accum_steps=accum_steps))
    return step.trace(state, batch)


def _step_text(cfg, **shape) -> str:
    text = _traced_step(cfg, **shape).lower().compile().as_text()
    assert text.startswith("HloModule jit_train_step")
    return text


def _parts_and_passes(cfg, accum_steps: int = 1) -> set[tuple[str, str]]:
    return {scopes.classify(n) for n in re.findall(
        r'op_name="([^"]*)"', _step_text(cfg, accum_steps=accum_steps))}


BLOCK = ("attn_norm", "attn", "mlp_norm", "mlp")


@pytest.mark.parametrize("arch", ["gpt2", "llama"])
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("scan_layers,ce_impl", [(True, "fused"),
                                                 (False, "checkpoint")])
def test_compiled_step_carries_every_scope_and_its_pass(
        arch, remat, scan_layers, ce_impl):
    cfg = models.tiny(arch=arch, remat=remat, scan_layers=scan_layers,
                      loss_chunk=64, ce_impl=ce_impl)
    found = _parts_and_passes(cfg)
    for part in ("embed", "layers", *BLOCK, "final_norm", "head_loss"):
        assert (part, "forward") in found, (part, sorted(found))
        assert (part, "backward") in found, (part, sorted(found))
    assert ("optimizer", "forward") in found
    assert not any(part == "optimizer" and ps != "forward"
                   for part, ps in found)
    # Full remat re-runs every part of a block inside the backward pass;
    # without it nothing of a block is recomputed.
    for part in BLOCK:
        assert ((part, "recompute") in found) == remat, (part, sorted(found))
    # jax.checkpoint around the loss chunk recomputes its logits; the
    # fused custom_vjp computes its gradients in its forward scan.
    assert (("head_loss", "recompute") in found) == (ce_impl == "checkpoint")
    assert {part for part, _ in found} <= set(scopes.PARTS) | {scopes.UNSCOPED}


def test_unchunked_loss_accumulation_and_experts_have_their_scopes():
    found = _parts_and_passes(models.tiny(remat=True), accum_steps=2)
    assert ("grad_accum", "forward") in found
    assert ("head_loss", "backward") in found       # plain cross entropy
    assert ("attn", "recompute") in found           # inside the micro scan
    moe = _parts_and_passes(models.tiny_moe(n_layers=1, remat=True))
    assert {("moe", "forward"), ("moe", "recompute"),
            ("moe", "backward")} <= moe
    assert not any(part == "mlp" for part, _ in moe)


def test_default_head_loss_backward_rule_is_in_head_loss():
    """At the default ``loss_chunk`` 0 head and loss are one op with its
    own gradient rule, whose gradients are made in its forward: the rule's
    backward is the scaling by the loss's cotangent alone, and jax runs a
    rule under the scopes its op was called in, so with a cotangent that
    is not 1 (which XLA folds away) every instruction of it reads
    ``head_loss`` ``backward`` and the scaling adds no other name."""
    cfg = models.tiny(remat=True)
    assert cfg.loss_chunk == 0
    params = jax.eval_shape(lambda k: models.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 33), jnp.int32)}

    def names(scaled: bool) -> set[str]:
        def grad(p, b, s):
            return jax.grad(lambda p: transformer.lm_loss(p, b, cfg)[0]
                            * (s if scaled else 1.0))(p)
        text = jax.jit(grad).lower(
            params, batch, jax.ShapeDtypeStruct((), jnp.float32)
        ).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    plain, scaled = names(False), names(True)
    rule = {n for n in scaled - plain if "head_loss" in n}
    assert any(n.endswith("/mul") for n in rule), sorted(scaled - plain)
    assert {scopes.classify(n) for n in rule} == {("head_loss", "backward")}
    assert scaled - plain - rule == {"s"}      # the cotangent's parameter


def test_dropless_experts_carry_their_sub_scopes_in_every_pass():
    """The four sub-scopes the dropless block of ``ops/moe.py`` opens
    inside ``moe`` (``moe_shared`` is a shared expert's, which this model
    has none of: ``tests/test_kanana2.py``) reach the
    compiled step's ``op_name``s in every pass that has work of theirs,
    and the benchmark's rule still gives every one of those instructions
    to ``moe`` (the innermost name IT knows), so ``step_mlp_ms`` stays
    whole. That is every (sub-scope, pass) pair but ONE: (``moe_combine``,
    ``recompute``) went when the block took its own backward
    (``_down_and_combine``), which reads ``h`` and never the experts'
    output, so the gather back to token order is not run a second time.
    For the same reason the recompute holds TWO grouped matmuls (gate and
    up; a ``dot`` each on the CPU), not the forward's three; the backward
    rule's own instructions (opened under ``moe_experts`` /
    ``moe_combine`` inside the rule) read ``backward``."""
    from ray_tpu.ops import moe

    cfg = models.olmoe_1b_7b(
        n_layers=1, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        n_experts=8, expert_top_k=3, vocab_size=256, max_seq_len=64)
    dropless = tuple(s for s in moe.SCOPES if s != "moe_shared")
    found, matmuls = set(), {ps: 0 for ps in scopes.PASSES}
    for line in _step_text(cfg).splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        for sub in dropless:
            if name and f"/{sub}/" in name.group(1):
                part, ps = scopes.classify(name.group(1))
                assert part == "moe", name.group(1)
                found.add((sub, ps))
                matmuls[ps] += sub == "moe_experts" and " dot(" in line
    assert found == {(sub, ps) for sub in dropless for ps in scopes.PASSES
                     } - {("moe_combine", "recompute")}, sorted(found)
    assert matmuls == {"forward": 3, "recompute": 2, "backward": 6}
    assert not set(moe.SCOPES) & set(scopes.PARTS)


def test_scopes_id_covers_every_file_that_opens_a_scope(tmp_path):
    """``SCOPES_ID`` is over the bytes of ``models/transformer.py``,
    ``ops/moe.py``, ``ops/attention.py``, ``ops/linear_attention.py``,
    ``ops/state_space.py`` AND ``models/mixers.py``: an edit of any gives
    another id, so a step
    cached by a tree with other sub-scope names is never loaded."""
    from ray_tpu.models import mixers
    from ray_tpu.ops import linear_attention, moe, state_space

    assert transformer.SCOPE_FILES == (
        transformer.__file__, moe.__file__, attention_ops.__file__,
        linear_attention.__file__, state_space.__file__, mixers.__file__)
    assert transformer.SCOPES_ID == transformer._scopes_id()
    for i, path in enumerate(transformer.SCOPE_FILES):
        edited = tmp_path / f"edited{i}.py"
        with open(path, "rb") as f:
            edited.write_bytes(f.read() + b"\n# an edit\n")
        files = list(transformer.SCOPE_FILES)
        files[i] = str(edited)
        assert transformer._scopes_id(files) != transformer.SCOPES_ID


ATTN_NAMES = transformer.ATTN_PART_SCOPES + attention_ops.SCOPES
_F, _R, _B = scopes.PASSES
_EVERY = {_F, _R, _B}
# One tiny model per attention path: (the config, row length, the passes
# each name must be on in the COMPILED step). The kernel's output and
# logsumexp outlive the layer's remat, so nothing of ``attn_core`` is
# recomputed on the kernel path; its forward layout moves are layouts,
# not instructions, on the CPU (the lowered text is asked for them).
ATTENTION_PATHS = {
    # GQA 4 / 2, RoPE, QK-norm, the Pallas kernels (interpreted): all seven
    "kernel": (lambda: models.TransformerConfig(
        arch="llama", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=256, max_seq_len=256, qk_norm=True,
        attn_impl="flash", remat=True), 256,
        {"attn_qkv": _EVERY, "attn_pos": _EVERY, "attn_gqa": _EVERY,
         "attn_core": {_F, _B}, "attn_out": _EVERY, "attn_layout": {_B},
         "attn_delta": {_B}}),
    # latent attention: no repeat, positions inside ``mla_latent``
    "latent": (lambda: models.kanana_2_30b_a3b(
        n_layers=3, d_model=64, n_heads=4, d_ff=32, kv_latent=32,
        d_head_nope=16, d_head_rope=8, d_head_v=16, d_ff_dense=96,
        d_ff_shared=48, n_experts=8, expert_top_k=3, vocab_size=256,
        max_seq_len=256, experts_held=(1, 4), attn_impl="flash"), 256,
        {"attn_qkv": _EVERY, "attn_pos": _EVERY, "attn_core": {_F, _B},
         "attn_out": _EVERY, "attn_layout": {_B}, "attn_delta": {_B}}),
    # gpt2: learned positions, every head its own key, materialised scores
    "materialised": (lambda: models.tiny(remat=True), 32,
                     {"attn_qkv": _EVERY, "attn_core": _EVERY,
                      "attn_out": _EVERY}),
}


@functools.cache
def _attention_step(path: str):
    """(lowered, optimised text) of ``ATTENTION_PATHS[path]``'s step: the
    two tests below read the same text, compiled once."""
    make, seq_len, _ = ATTENTION_PATHS[path]
    lowered = _traced_step(make(), rows=2, seq_len=seq_len).lower()
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_train_step")
    return lowered, text


@pytest.mark.parametrize("path", ATTENTION_PATHS)
def test_attention_names_its_parts_in_every_pass(path):
    """The seven names of ``transformer.ATTN_PART_SCOPES`` and
    ``ops.attention.SCOPES`` reach the compiled step's ``op_name``s in
    every pass that has work of theirs, a model has only the names whose
    work it has, and the benchmark's rule still gives every one of those
    instructions to ``attn``, so ``step_attn_ms`` stays whole. The two
    names ``ops/attention.py`` opens inside the custom gradient's
    backward rule read ``backward`` (their path holds ``transpose(``)."""
    _, _, want = ATTENTION_PATHS[path]
    lowered, text = _attention_step(path)
    found: dict[str, set] = {}
    for name in re.findall(r'op_name="([^"]*)"', text):
        pieces = scopes._CUT.split(name)
        for part in set(pieces).intersection(ATTN_NAMES):
            model_part, ps = scopes.classify(name)
            assert model_part == "attn", name
            found.setdefault(part, set()).add(ps)
            if part in attention_ops.SCOPES and name.startswith("jit("):
                assert "transpose(" in name, name
            if part == "attn_pos":
                assert (transformer.MLA_SCOPE in pieces) == (path == "latent")
                assert "attn_qkv" not in pieces, name
    assert set(found) == set(want), sorted(found)
    for part, passes in want.items():
        assert found[part] >= passes, (part, sorted(found[part]))
    # the forward rule's own moves, before any compiler has had them
    moves = re.findall(r'loc\("([^"]*attn_core/attn_layout/transpose)"',
                       lowered.as_text(debug_info=True))
    assert any("transpose(" not in name and not name.startswith("checkpoint/")
               for name in moves) == ("attn_layout" in want), moves
    assert not set(ATTN_NAMES) & set(scopes.PARTS)


@pytest.mark.parametrize("path", ATTENTION_PATHS)
def test_the_attention_scopes_cost_nothing(path, monkeypatch):
    """A named scope is metadata: the optimised step compiled with the
    seven names patched away is, ``metadata={...}``, the tables of
    Python frames it points at and the ``scopes=`` frontend attribute
    stripped, the same text, instruction for instruction."""
    make, seq_len, _ = ATTENTION_PATHS[path]

    def stripped(text: str) -> tuple[str, bool]:
        named = any(f"/{name}/" in text for name in ATTN_NAMES)
        # the module's tables of the Python frames its metadata points at
        text = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n(?:\d+ .*\n)+", "\n", text)
        text = re.sub(r',? ?metadata=\{[^}]*\}', "", text)
        return re.sub(r'scopes="[^"]*"', "", text), named

    with_names, named = stripped(_attention_step(path)[1])
    assert named
    opened = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        contextlib.nullcontext() if name in ATTN_NAMES else opened(name)))
    jax.clear_caches()      # nothing traced with the names open
    without, named = stripped(_step_text(make(), rows=2, seq_len=seq_len))
    assert not named
    assert with_names == without


def test_causal_blocks_leave_no_whole_score_tensor_and_stay_in_attn():
    """At T = 512 ``attention(impl="auto")`` computes four query blocks
    of 128 rows against key prefixes of 128 to 512: no instruction of the
    compiled step has the whole ``[B,H,512,512]`` score shape any more
    (``impl="reference"`` shows the pattern finds one), and the
    instructions that produce a block carry ``attn`` in the forward pass,
    the recompute and the backward pass, so ``step_attn_ms`` still reads
    them."""
    whole = re.compile(r"\[\d+,\d+,512,512\]")
    block = re.compile(
        r" = \w+\[\d+,\d+,128,(?:128|256|384|512)\].*op_name=\"([^\"]*)\"")
    cfg = models.tiny(max_seq_len=512, remat=True)
    assert whole.search(_step_text(replace(cfg, attn_impl="reference"),
                                   rows=2, seq_len=512))
    text = _step_text(cfg, rows=2, seq_len=512)
    assert not whole.search(text)
    found = {scopes.classify(m.group(1))
             for m in map(block.search, text.splitlines()) if m}
    assert found == {("attn", "forward"), ("attn", "recompute"),
                     ("attn", "backward")}, sorted(found)


def test_a_step_at_1024_has_no_kernel_and_one_at_2048_has(monkeypatch):
    """The dense cells' bypass: on a TPU ``attention(impl="auto")`` at
    T = 1024 takes the materialised blocks, so the train step lowered
    for a TPU has no ``tpu_custom_call``; at T = 2048 it has two (the
    kernel's forward and, since PR 38, its one backward kernel: the
    layer's checkpoint keeps the kernel's output and logsumexp, so its
    recompute launches no third; ``tests/test_flash_remat.py``)."""
    import types

    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")])

    def kernels(t):
        traced = _traced_step(models.tiny(max_seq_len=t, remat=True),
                              rows=2, seq_len=t)
        return traced.lower(lowering_platforms=("tpu",)).as_text().count(
            "tpu_custom_call")

    assert kernels(1024) == 0
    assert kernels(2048) == 2


def test_the_reader_knows_exactly_the_programs_scopes():
    assert set(scopes.PARTS) == set(transformer.SCOPES)


_CACHE_SCRIPT = r"""
import json, jax, jax.numpy as jnp, optax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.util import tracing

assert transformer.SCOPES_ID.startswith("scopes.")
seen = []
tracing._emit = lambda ev: seen.append(ev)
cfg, opt = models.tiny(n_layers=1), optax.sgd(1e-3)
state = jax.eval_shape(lambda k: models.init_train_state(k, cfg, opt),
                       jax.random.PRNGKey(0))
batch = {"tokens": jax.ShapeDtypeStruct((2, 17), jnp.int32)}

def compile_step():
    jax.clear_caches()
    del seen[:]
    jax.jit(models.make_train_step(cfg, opt)).lower(state, batch).compile()
    return [e["attributes"]["cache"] for e in seen
            if e["name"] == "jax.compile"
            and "train_step" in e["attributes"]["fun"]]

out = [compile_step(), compile_step()]
transformer.SCOPES_ID = "scopes.00000000"     # the model file was edited
out += [compile_step(), compile_step()]
print(json.dumps(out))
"""


def test_a_tree_with_other_scopes_never_loads_this_trees_step(tmp_path):
    """jax's compile-cache key leaves scope names out; SCOPES_ID is in it
    (a frontend attribute of one instruction), so a step found in the
    cache always carries the names of the tree that asks for it. Also the
    compile listener on a real persistent cache: miss, then hit."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    done = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.strip().splitlines()[-1]) == [
        ["miss"], ["hit"], ["miss"], ["hit"]]


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(layers)/while/body/closed_call/attn/dot_general",
     ("attn", "forward")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/attn/dot_general:",
     ("attn", "recompute")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/mlp/mul", ("mlp", "backward")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/"
     "dynamic_update_slice", ("layers", "backward")),
    ("jit(train_step)/transpose(jvp(head_loss))/mul", ("head_loss",
                                                       "backward")),
    ("jit(train_step)/optimizer/jit(_where)/select_n", ("optimizer",
                                                        "forward")),
    ("jit(train_step)/transpose(jvp())/reshape;jit(train_step)/"
     "transpose(jvp(final_norm))/mul", ("final_norm", "backward")),
    ("jit(convert_element_type)/convert_element_type", ("unscoped",
                                                        "forward")),
    ("", ("unscoped", "forward")),
])
def test_rule_on_names_as_jax_writes_them(op_name, want):
    assert scopes.classify(op_name) == want
