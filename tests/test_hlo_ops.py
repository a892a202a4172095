"""The operation table of a compiled training program
(``utils/hlo_ops.py``) and the two named scopes it reads
(``core/step.py``): on the repo's transformer at a toy size, its blocks
under ``nn.remat``, with the zoo's loss and Adam, compiled on the CPU as
the worker's 4-step task program."""

import contextlib
import re

import jax
import numpy as np
import pytest

from elasticdl_tpu.core import step as step_module
from elasticdl_tpu.core.step import build_multi_step, build_train_step
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.utils import hlo_ops

LAYERS = 2


def _tiny(remat: bool):
    """The repo's transformer at a toy size, its blocks under
    ``nn.remat`` (with the policy the cells run) or plain, with the
    zoo's loss and optimizer."""
    from elasticdl_tpu.core.model_spec import load_module
    from elasticdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    zoo = load_module("model_zoo/transformer/transformer_lm.py")
    model = TransformerLM(TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=LAYERS, d_ff=64,
        max_len=16, compute_dtype=np.float32, remat=remat,
    ))
    return model, zoo.loss, zoo.optimizer()


def _compiled_text(remat: bool, steps: int = 4) -> str:
    model, loss, tx = _tiny(remat)
    tokens = np.zeros((4, 16), np.int32)
    one = {"features": tokens, "labels": tokens,
           "mask": np.ones((4,), np.float32)}
    state = jax.eval_shape(lambda: init_train_state(model, tx, one))
    if steps == 1:
        return build_train_step(loss).lower(state, one).compile().as_text()
    task = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((steps,) + x.shape, x.dtype), one)
    return build_multi_step(loss).lower(state, task).compile().as_text()


@pytest.fixture(scope="module")
def texts():
    return {remat: _compiled_text(remat) for remat in (True, False)}


@pytest.fixture(scope="module")
def tables(texts):
    return {remat: hlo_ops.table_of(text) for remat, text in texts.items()}


def _phases(table):
    return {row["phase"] for row in table["ops"]}


def test_table_is_named_as_the_trace_names_the_program(tables):
    assert tables[True]["module"] == "jit_multi_step"
    assert hlo_ops.module_name(_compiled_text(True, steps=1)) == (
        "jit_train_step")


@pytest.mark.parametrize("phase", hlo_ops.SCOPED)
def test_under_remat_the_table_has_rows_of_every_phase(tables, phase):
    rows = [r for r in tables[True]["ops"] if r["phase"] == phase]
    assert rows, _phases(tables[True])
    for row in rows:
        assert "mixed" not in row


def test_without_remat_no_row_is_recompute(tables):
    phases = _phases(tables[False])
    assert "recompute" not in phases
    assert {"forward", "backward", "optimizer"} <= phases
    for row in tables[False]["ops"]:
        assert "recompute" not in row.get("mixed", ())


def test_every_instruction_of_the_while_body_has_a_row(texts, tables):
    """Counted apart from the module's own parse: the computation the
    task's ``while`` names as its body, line by line."""
    text = texts[True]
    line = [ln for ln in text.splitlines()
            if " while(" in ln and 'op_name="jit(multi_step)/while"' in ln]
    (name,) = {re.search(r"body=%?([\w.\-]+)", ln).group(1) for ln in line}
    inside, names = False, []
    for ln in text.splitlines():
        if ln.startswith(f"%{name} (") or ln.startswith(f"{name} ("):
            inside = True
        elif inside and ln.startswith("}"):
            break
        elif inside and " = " in ln:
            names.append(ln.split(" = ")[0].split()[-1].lstrip("%"))
    assert len(names) > 50
    rows = {row["name"] for row in tables[True]["ops"]}
    assert set(names) <= rows
    # and nothing from inside a fused computation has one
    fused = re.findall(r"^%?(fused_computation[\w.\-]*) \(", text, re.M)
    assert fused
    first = text.index(fused[0] + " (")
    inner = re.search(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text[first:], re.M)
    assert inner.group(1) not in rows


def test_mixed_rows_list_their_phases(tables):
    mixed = [r for r in tables[True]["ops"] if r["phase"] == "mixed"]
    for row in mixed:
        assert len(row["mixed"]) > 1
        assert set(row["mixed"]) <= set(hlo_ops.SCOPED) | {"other"}


def test_folded_module_paths_merge_the_layers(tables):
    modules = {r["module"] for r in tables[True]["ops"]}
    assert {"block_*/mlp/wi", "block_*/attn/query", "token_embed",
            "lm_head"} <= modules
    assert not any(re.search(r"block_\d", m) for m in modules)
    # each layer's first MLP product: forward, recomputed, backward
    wi = [r for r in tables[True]["ops"] if r["module"] == "block_*/mlp/wi"
          and r["opcode"] in ("dot", "fusion")]
    assert {r["phase"] for r in wi} >= {"forward", "recompute", "backward"}
    raw = {r["op_name"] for r in wi}
    for layer in range(LAYERS):
        assert any(f"block_{layer}/mlp/wi" in name for name in raw)


def test_the_loss_is_a_module_and_no_phase(tables):
    rows = [r for r in tables[True]["ops"] if r["module"] == "loss"]
    assert {r["phase"] for r in rows} >= {"forward", "backward"}
    assert "loss" not in _phases(tables[True])


def test_the_optimizer_is_its_scope(tables):
    rows = [r for r in tables[True]["ops"] if r["phase"] == "optimizer"]
    assert len(rows) >= 16 * LAYERS  # a block's kernels, biases, scales
    assert {r["module"] for r in rows} == {"optimizer"}
    for row in rows:
        assert tracing.OPTIMIZER_SCOPE in row["op_name"].split("/")


# The issue's table: what ran -> the op_name JAX 0.9.0 writes -> phase.
ORIGINS = [
    ("jit(multi_step)/while/body/closed_call/jvp(M)/blk1/up/dot_general",
     "forward", "blk1/up"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(M))/jvp(M)/"
     "checkpoint/rematted_computation/blocks_3/up/dot_general",
     "recompute", "blocks_*/up"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(M))/jvp(M)/"
     "checkpoint/blocks_7/down/dot_general", "backward", "blocks_*/down"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(M))/head/"
     "dot_general", "backward", "head"),
    # a model inside a wrapper module, as the benchmark's: one path
    # in every pass
    ("jit(multi_step)/while/body/closed_call/jvp(W.apply)/W/block_2/moe/"
     "jit(take_along_axis)/gather", "forward", "W/block_*/moe"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(W.apply))/W/"
     "jvp(W.apply)/W/checkpoint/block_0/moe/gather", "backward",
     "W/block_*/moe"),
    # a conditional under a custom rule (the expert layer's two paths):
    # the branches are their layer's, and the rule's own ``jax.vjp`` of
    # a branch wraps a function inside the module and restates nothing
    ("jit(multi_step)/while/body/closed_call/jvp(W.apply)/W/block_1/moe/"
     "cond/branch_1_fun/ragged_dot_general", "forward", "W/block_*/moe"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(W.apply))/W/"
     "jvp(W.apply)/W/checkpoint/block_0/moe/cond/branch_0_fun/"
     "transpose(jvp())/mul", "backward", "W/block_*/moe"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(W.apply))/W/"
     "jvp(W.apply)/W/checkpoint/block_0/moe/cond/branch_0_fun/jvp()/"
     "reduce_sum", "backward", "W/block_*/moe"),
    ("jit(multi_step)/while/body/closed_call/jvp(edl_loss)/integer_pow",
     "forward", "loss"),
    ("jit(multi_step)/while/body/closed_call/transpose(jvp(edl_loss))/mul",
     "backward", "loss"),
    ("jit(multi_step)/while/body/closed_call/edl_optimizer/mul",
     "optimizer", "optimizer"),
    ("jit(multi_step)/while/body/closed_call/mul", "other", ""),
    ("jit(multi_step)/while/body/dynamic_slice", "other", ""),
    ("jit(multi_step)/while/body/closed_call/jit(_threefry_split)/"
     "TrainState.next_rng/while/body/closed_call/xor", "other",
     "TrainState.next_rng"),
]


@pytest.mark.parametrize("op_name,phase,module", ORIGINS)
def test_phase_and_module_of_an_origin(op_name, phase, module):
    assert hlo_ops.phases_of(op_name) == {phase}
    assert hlo_ops.module_of(op_name) == module


@pytest.mark.parametrize("op_name", [
    "jit(multi_step)/while/body/closed_call",  # a closed-over constant
    "gather", "sort", "reduce_sum", "ragged-dot-none",  # XLA's expansions
    "state.params['blocks_1']['down']['bias']",  # an entry parameter
    "",
])
def test_an_origin_that_names_no_operation_says_nothing(op_name):
    assert hlo_ops.phases_of(op_name) == set()


def test_scope_names_are_spelled_once():
    assert tracing.OPTIMIZER_SCOPE == "edl_optimizer"
    assert tracing.LOSS_SCOPE == "edl_loss"


_TEXT = '''HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  %c = f32[] constant(2), metadata={op_name="jit(train_step)"}
  %b = f32[4]{0} broadcast(%c), dimensions={}, metadata={op_name="jit(train_step)"}
  %add.1 = f32[4]{0} add(%p0, %p1), metadata={op_name="jit(train_step)/transpose(jvp(M))/head/add_any" stack_frame_id=3}
  ROOT %mul.1 = f32[4]{0} multiply(%add.1, %b), metadata={op_name="jit(train_step)/edl_optimizer/mul"}
}

%fused_computation.2 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p0), metadata={op_name="jit(train_step)/jvp(M)/head/neg;jit(train_step)/transpose(jvp(M))/head/neg"}
}

%fused_computation.3 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %c.3 = s32[1]{0} constant({0}), metadata={op_name="gather"}
  ROOT %gather.1 = f32[4]{0} gather(%p0, %c.3), offset_dims={}, metadata={op_name="gather"}
}

%branch_a (p: (f32[4], /*index=1*/f32[4])) -> f32[4] {
  %p = (f32[4]{0:T(128)S(1)}, /*index=1*/f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%p), index=0
  ROOT %kernel.7 = f32[4]{0} custom-call(%g), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(M)/blocks_2/attn/pallas_call"}
}

%branch_b (p: (f32[4], /*index=1*/f32[4])) -> f32[4] {
  %p = (f32[4]{0}, f32[4]{0}) parameter(0)
  ROOT %g2 = f32[4]{0} get-tuple-element(%p), index=1
}

ENTRY %main.9 (a: f32[4], b: f32[4], i: s32[]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %b.1 = f32[4]{0} parameter(1)
  %i = s32[] parameter(2)
  %fusion.5 = f32[4]{0} fusion(%a, %b.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/edl_optimizer/mul"}
  %negate_fusion = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2
  %fusion.9 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/blocks_1/moe/jit(take_along_axis)/gather"}
  %ragged-dot-none.4 = f32[4]{0} custom-call(%a), custom_call_target="ragged_dot", metadata={op_name="ragged-dot-none"}
  %t = (f32[4]{0:T(128)S(1)}, /*index=1*/f32[4]{0}) tuple(%fusion.5, %negate_fusion)
  %copy-start.3 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%a)
  %copy-done.3 = f32[4]{0} copy-done(%copy-start.3)
  ROOT %conditional.2 = f32[4]{0} conditional(%i, %t, %t), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(train_step)/cond"}
}
'''


def test_a_fusion_over_two_phases_is_mixed_with_both_listed():
    table = hlo_ops.table_of(_TEXT)
    assert table["module"] == "jit_train_step"
    rows = {row["name"]: row for row in table["ops"]}
    # Adam's update fused into a gradient's last add: counted apart.
    # The constant and its broadcast, which name no operation, say
    # nothing.
    assert rows["fusion.5"]["phase"] == "mixed"
    assert rows["fusion.5"]["mixed"] == ["backward", "optimizer"]
    assert rows["fusion.5"]["module"] == "optimizer"
    # One instruction of two origins that disagree, and a fusion with
    # no metadata of its own.
    assert rows["negate_fusion"]["phase"] == "mixed"
    assert rows["negate_fusion"]["mixed"] == ["backward", "forward"]
    # Both branches of the conditional have rows, a kernel among them,
    # with the tuple types' parentheses skipped.
    assert rows["kernel.7"]["opcode"] == "custom-call"
    assert rows["kernel.7"]["phase"] == "forward"
    assert rows["kernel.7"]["module"] == "blocks_*/attn"
    assert rows["g2"]["opcode"] == "get-tuple-element"
    assert rows["t"]["opcode"] == "tuple"
    # What XLA made with no metadata is ``other``.
    assert rows["copy-done.3"]["phase"] == "other"
    assert rows["copy-done.3"]["op_name"] == ""
    # XLA's expansion of a gather left bare ``gather`` on everything
    # inside: the fusion's own op_name speaks. Where the bare name is
    # all there is, the operation is ``other``.
    assert rows["fusion.9"]["phase"] == "recompute"
    assert rows["fusion.9"]["module"] == "blocks_*/moe"
    assert rows["ragged-dot-none.4"]["phase"] == "other"
    # Nothing from inside a fused computation has a row.
    assert "add.1" not in rows and "mul.1" not in rows
    assert len(rows) == 15


def _stripped(text: str) -> str:
    """The compiled text without what names alone can move: every
    ``metadata={...}`` and the frame table at its head."""
    chunks = [c for c in text.split("\n\n") if not c.lstrip().startswith(
        ("FileNames", "FunctionNames", "FileLocations", "StackFrames"))]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(chunks))


@pytest.mark.parametrize("remat", [True, False])
def test_the_scopes_change_nothing_but_names(texts, monkeypatch, remat):
    """With ``core/step.py``'s two scopes patched away the task
    program's compiled text differs in metadata alone."""
    scoped = texts[remat]
    assert tracing.OPTIMIZER_SCOPE in scoped
    assert tracing.LOSS_SCOPE in scoped
    real = jax.named_scope

    def unless_ours(name):
        if name in (tracing.OPTIMIZER_SCOPE, tracing.LOSS_SCOPE):
            return contextlib.nullcontext()
        return real(name)

    monkeypatch.setattr(step_module.jax, "named_scope", unless_ours)
    bare = _compiled_text(remat)
    assert tracing.OPTIMIZER_SCOPE not in bare
    assert tracing.LOSS_SCOPE not in bare
    assert _stripped(bare) == _stripped(scoped)
    assert bare != scoped
