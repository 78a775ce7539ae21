"""Op -> scope tables (ISSUE 38): ``xla_stats.op_scopes`` on executables
compiled here, the registry ``capture`` fills at the engine's and the train
step's dispatch, and what the registry may hold."""
import dataclasses
import gc
import sys
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[3]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from apex_tpu import train_step  # noqa: E402
from apex_tpu.inference import InferenceEngine  # noqa: E402
from apex_tpu.observability import compile_count, xla_stats  # noqa: E402
from apex_tpu.optimizers import functional  # noqa: E402

MOE = {"apex_moe_route", "apex_moe_sort", "apex_moe_experts",
       "apex_moe_combine", "apex_moe_shared"}
ENGINE = {"apex_prefill_forward", "apex_prefill_cache_insert",
          "apex_prefill_sample", "apex_decode_forward", "apex_decode_sample",
          "apex_decode_advance"}
#: every scope each kind's code sets, over its prefill and decode steps
KIND_SCOPES = {
    "gpt": ENGINE,
    "laguna": ENGINE | MOE,
    # the held-experts loop scatters into the tokens' rows inside its
    # experts stage: no combine of its own
    "axk1": ENGINE | (MOE - {"apex_moe_combine"})
    | {"apex_mla_down", "apex_mla_expand", "apex_mla_absorb",
       "apex_mla_up"},
    "keye": ENGINE | (MOE - {"apex_moe_shared"})
    | {"apex_dsa_index", "apex_dsa_select", "apex_dsa_attend"},
}


@pytest.fixture
def registry(monkeypatch):
    """A fresh process registry for the test."""
    monkeypatch.setattr(xla_stats, "_TABLES", {})
    monkeypatch.setattr(xla_stats, "_PENDING", [])
    return xla_stats


def _text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# -- op_scopes on executables compiled here ----------------------------------

def _nested(x):
    with jax.named_scope("apex_outer"):
        y = jnp.sin(x) @ x.T                   # a dot: no fusion takes it
        with jax.named_scope("apex_inner"):
            y = jnp.tanh(y) @ x
    return y, jnp.cos(x)                       # the cosine: no scope


def test_the_innermost_scope_names_an_instruction_and_none_no_scope():
    got = xla_stats.op_scopes(_text(_nested, jnp.ones((16, 8))))
    scopes = {s for s, _ in got.values()}
    assert {"apex_outer", "apex_inner", None} <= scopes
    assert not any(b for _, b in got.values())


def test_a_fusion_takes_its_roots_metadata():
    text = _text(_nested, jnp.ones((16, 8)))
    got = xla_stats.op_scopes(text)
    fusions = [line.split(" = ")[0].strip().removeprefix("ROOT ")[1:]
               for line in text.splitlines()
               if " fusion(" in line and "op_name=" in line]
    assert fusions and all(f in got for f in fusions)
    for f in fusions:
        line = next(ln for ln in text.splitlines()
                    if ln.strip().startswith((f"%{f} ", f"ROOT %{f} ")))
        want = "apex_inner" if "/apex_inner/" in line else (
            "apex_outer" if "/apex_outer/" in line else None)
        assert got[f][0] == want


def _loop(x):
    def body(c, _):
        with jax.named_scope("apex_body"):
            return jnp.sin(c) * 1.5 + 0.1, None
    with jax.named_scope("apex_around"):
        y, _ = jax.lax.scan(body, x, None, length=4)
    return y


def test_a_while_bodys_instructions_carry_the_bodys_scope():
    text = _text(_loop, jnp.ones((8, 8)))
    assert " while(" in text
    got = xla_stats.op_scopes(text)
    whiles = [n for n in got if n.startswith("while")]
    assert whiles and all(got[n][0] == "apex_around" for n in whiles)
    assert any(s == "apex_body" for s, _ in got.values())


def _grad_step(w, x):
    def loss(w):
        with jax.named_scope("apex_train_forward"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)
    value, g = jax.value_and_grad(loss)(w)
    with jax.named_scope("apex_train_optimizer"):
        return w - 0.1 * g, value


def test_grad_splits_forward_from_backward_by_the_transpose():
    got = xla_stats.op_scopes(_text(_grad_step, jnp.ones((8, 4)),
                                    jnp.ones((16, 8))))
    fwd = {(s, b) for s, b in got.values()}
    assert ("apex_train_forward", False) in fwd
    assert ("apex_train_forward", True) in fwd
    assert ("apex_train_optimizer", False) in fwd
    assert ("apex_train_optimizer", True) not in fwd


def test_components_strip_transforms_round_each_scope():
    chain, back = xla_stats._chain_of(
        "jit(step)/transpose(jvp(apex_train_forward))/while/body/"
        "closed_call/apex_layer_norm_bwd/pallas_call")
    assert chain == ("apex_train_forward", "apex_layer_norm_bwd") and back
    assert xla_stats._chain_of("jit(f)/vmap(jvp(apex_a/apex_b))/mul") == (
        ("apex_a", "apex_b"), False)
    assert xla_stats._chain_of("x") == ((), False)


def test_an_instruction_without_metadata_takes_its_consumers_scope():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        '  %p = f32[8]{0} parameter(0), metadata={op_name="p"}',
        "  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%p)",
        "  %copy-done = f32[8]{0} copy-done(%copy-start)",
        "  ROOT %fusion.1 = f32[8]{0} fusion(%copy-done), kind=kLoop, "
        'calls=%fc, metadata={op_name="jit(f)/apex_stage/mul"}',
        "}"])
    module, chains, types, inferred = xla_stats._parse(text)
    assert module == "jit_f"
    assert chains["copy-start"] == chains["copy-done"] == (
        ("apex_stage",), False)
    assert inferred == {"copy-start", "copy-done"}
    assert chains["p"] == ((), False) and types["fusion.1"] == "f32[8]{0}"


def test_an_op_a_compiler_pass_renamed_takes_its_primitives_chain():
    """XLA's v5e pipeline rewrites ``ragged_dot`` into custom calls whose
    ``op_name`` is their own name (a v5e compile shows it); they take the
    chain of the traced program's ``ragged_dot_general`` equations."""
    def experts(x, w, sizes):
        with jax.named_scope("apex_decode_forward"):
            with jax.named_scope("apex_moe_experts"):
                y = jax.lax.ragged_dot(x, w, sizes)
            with jax.named_scope("apex_moe_combine"):
                return y * 2.0
    traced = jax.jit(experts).trace(jnp.ones((8, 4)), jnp.ones((2, 4, 4)),
                                    jnp.array([4, 4], jnp.int32))
    text = "\n".join([
        "HloModule jit_experts, is_scheduled=true",
        "ENTRY %main (x: f32[8,4]) -> f32[8,4] {",
        '  %x = f32[8,4]{1,0} parameter(0), metadata={op_name="x"}',
        "  %ragged-dot-none.3 = f32[8,4]{1,0} custom-call(%x), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        "  ROOT %fusion.1 = f32[8,4]{1,0} fusion(%ragged-dot-none.3), "
        'kind=kLoop, calls=%fc, metadata={op_name="jit(experts)/'
        'apex_decode_forward/apex_moe_combine/mul"}',
        "}"])
    chains = xla_stats._parse(text, traced.jaxpr)[1]
    assert chains["ragged-dot-none.3"] == (
        ("apex_decode_forward", "apex_moe_experts"), False)
    # without the program, its consumer's chain is all there is
    assert xla_stats._parse(text)[1]["ragged-dot-none.3"] == (
        ("apex_decode_forward", "apex_moe_combine"), False)


# -- the registry --------------------------------------------------------------

def test_capture_shares_the_calls_compile_and_keeps_strings_only(registry):
    def fresh(x):                               # compiled nowhere else
        return _nested(x)
    f = jax.jit(fresh)
    x = jnp.ones((16, 8))
    c0 = compile_count()
    table = registry.capture(f, x)
    jax.block_until_ready(f(x))
    assert compile_count() - c0 == 1
    assert table.module == "jit_fresh" and table.scopes
    assert registry.scope_tables() == (table,)
    _holds_plain_data_only(table)
    # a second shape is a second executable and a second table
    registry.capture(f, jnp.ones((32, 8)))
    assert len(registry.scope_tables()) == 2


def test_capture_never_raises(registry):
    assert registry.capture(jax.jit(lambda x: x.foo), jnp.ones(3)) is None
    assert registry.scope_tables() == ()


def _holds_plain_data_only(table):
    for f in dataclasses.fields(table):
        v = getattr(table, f.name)
        items = ([v] if isinstance(v, (str, float)) else list(v)
                 if isinstance(v, frozenset) else
                 [x for kv in v.items() for x in kv])
        for x in items:
            flat = x if isinstance(x, tuple) else (x,)
            for y in flat:
                for z in (y if isinstance(y, tuple) else (y,)):
                    assert isinstance(z, (str, bool, float)), type(z)


# -- the engine's dispatches ---------------------------------------------------

def _toy(kind):
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import (GPTConfig, gpt_model_provider,
                                              standalone_axk1 as SA,
                                              standalone_keye as SK,
                                              standalone_laguna as SL)
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg, provider = {
        "gpt": (GPTConfig(num_layers=2, hidden_size=32,
                          num_attention_heads=2, vocab_size=96,
                          max_seq_length=64), gpt_model_provider),
        "laguna": (SL.LagunaConfig(), SL.laguna_model_provider),
        "axk1": (SA.AXK1Config(), SA.axk1_model_provider),
        "keye": (SK.KeyeConfig(), SK.keye_model_provider)}[kind]
    params = provider(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))
    return cfg, params


PROMPTS = [list(range(1, 30)), [5, 6, 7]]


@pytest.fixture(scope="module", params=sorted(KIND_SCOPES))
def served(request):
    """kind -> the tables its engine's dispatches left, the compiles the
    second wave of the same shapes cost, and weak references to the
    engine's arrays after the engine is gone."""
    kind = request.param
    saved = dict(xla_stats._TABLES)
    xla_stats._TABLES.clear()
    try:
        cfg, params = _toy(kind)
        eng = InferenceEngine(kind, cfg, params, slots=2, max_seq=64,
                              page_size=8, num_pages=24)
        eng.generate(PROMPTS, max_new_tokens=3)
        first = xla_stats.scope_tables()
        c0 = compile_count()
        eng.generate(PROMPTS, max_new_tokens=3)
        again = compile_count() - c0
        refs = [weakref.ref(x) for x in jax.tree_util.tree_leaves(
            eng.params)]
        del eng, params
        gc.collect()
        return kind, first, again, refs, xla_stats.scope_tables()
    finally:
        xla_stats._TABLES.clear()
        xla_stats._TABLES.update(saved)


def test_every_scope_the_kind_sets_reaches_its_tables(served):
    kind, tables, *_ = served
    held = {c for t in tables for chain, _ in t.scopes.values()
            for c in chain}
    assert KIND_SCOPES[kind] <= held


def test_most_instructions_of_prefill_and_decode_carry_a_scope(served):
    _, tables, *_ = served
    steps = [t for t in tables
             if t.module.startswith(("jit_prefill", "jit_decode"))]
    assert {t.module for t in steps} == {"jit_prefill_paged_fn",
                                         "jit_decode_fn"}
    for t in steps:
        scoped = sum(1 for chain, _ in t.scopes.values() if chain)
        assert scoped / len(t.scopes) > 0.7, (t.module, scoped)


def test_capture_runs_once_a_shape_and_adds_no_compile(served):
    _, tables, again, _, after = served
    # prefill (one bucket: both prompts pad to 64), decode, evict
    assert sorted(t.module for t in tables) == [
        "jit_decode_fn", "jit_evict", "jit_prefill_paged_fn"]
    assert again == 0 and after == tables


def test_the_registry_outlives_no_array_of_the_engine(served):
    _, tables, _, refs, _ = served
    assert refs and all(r() is None for r in refs)
    for t in tables:
        _holds_plain_data_only(t)


def test_capture_adds_no_backend_compile_to_a_first_dispatch(registry,
                                                             monkeypatch):
    cfg, params = _toy("gpt")
    counts = {}
    for on in (False, True):
        if not on:
            monkeypatch.setattr(registry, "capture", lambda *a: None)
        else:
            monkeypatch.undo()
            monkeypatch.setattr(xla_stats, "_TABLES", {})
        eng = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                              page_size=8, num_pages=24)
        c0 = compile_count()
        eng.generate(PROMPTS, max_new_tokens=3)
        counts[on] = compile_count() - c0
    assert counts[True] == counts[False]
    assert len(xla_stats.scope_tables()) == 3


# -- the train step, jitted by its caller --------------------------------------

def _params():
    rng = np.random.RandomState(0)
    return {f"w{i}": jnp.asarray(rng.randn(8, 8) * 0.3, jnp.float32)
            for i in range(3)}


def _loss(params, batch):
    h = batch["x"]
    for i in range(3):
        h = jnp.tanh(h @ params[f"w{i}"])
    return jnp.mean((h - batch["y"]) ** 2)


def test_the_train_step_is_captured_when_the_tables_are_read(registry):
    tx = functional.fused_lamb(lr=1e-3)
    state = train_step.init_train_state(tx, _params(), loss_scale="dynamic")
    step = jax.jit(train_step.make_train_step(_loss, tx),
                   donate_argnums=(0,))
    batch = {"x": jnp.ones((16, 8)), "y": jnp.zeros((16, 8))}
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    assert len(registry._PENDING) == 1          # one trace, no table yet
    c0 = compile_count()
    (table,) = registry.scope_tables()
    assert compile_count() == c0                # the call's executable
    assert registry._PENDING == [] and table.module == "jit_step"
    held = {(chain[0], back) for chain, back in table.scopes.values()
            if chain}
    assert held == {("apex_train_forward", False),
                    ("apex_train_forward", True),
                    ("apex_train_unscale", False),
                    ("apex_train_optimizer", False)}
    # the fused kernels sit inside the stage that calls them
    kernels = {chain for chain, _ in table.scopes.values() if len(chain) > 1}
    assert (("apex_train_optimizer", "apex_lamb_stage1") in kernels
            or not any("apex_lamb_stage1" in c for c in kernels))
    # read again: nothing left to capture
    assert registry.scope_tables() == (table,)
