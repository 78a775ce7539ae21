"""Every Pallas kernel has a stable name of its own: the ``pallas_call``
equation carries it (so does the profiler's trace, where the benchmark's
``lamb_kernel_ms``/``unscale_kernel_ms`` find the kernel by it), and the
v5e's compiler puts a ``tpu_custom_call`` under it."""
import importlib
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis.pallas_audit import kernel_specs

OPS = Path(__file__).resolve().parents[3] / "apex_tpu" / "ops"
fu = importlib.import_module("apex_tpu.ops.fused_update")
ln = importlib.import_module("apex_tpu.ops.layer_norm")
attn = importlib.import_module("apex_tpu.ops.attention")

F32, BF16 = jnp.float32, jnp.bfloat16


def _s(shape, dtype, **kw):
    return jax.ShapeDtypeStruct(shape, dtype, **kw)


def _flat(n=2048, **kw):
    return _s((n,), F32, **kw)


def _registered(op):
    """The fixture the kernel auditor traces ``op`` with."""
    return next(s for s in kernel_specs() if s.name == op).build()


def _unscale(g):
    return fu.fused_scale(g, 1.0 / 65536.0)


def _lamb(p, g, m, v):
    return fu.fused_lamb_phase1_flat(
        p, g, m, v, beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01,
        step=1)


def _ln_fwd(x, w, b):
    return ln.layer_norm(x, w, b)


def _ln_bwd(x, w, b):
    y, vjp = jax.vjp(ln.layer_norm, x, w, b)
    return vjp(y)


def _flash(q, k, v):
    return attn.flash_attention(q, k, v, causal=True, xla_max_seq=0)


def _flash_bwd(q, k, v):
    y, vjp = jax.vjp(_flash, q, k, v)
    return vjp(y)


_LN = (_s((128, 256), BF16), _s((256,), F32), _s((256,), F32))
_QKV = (_s((1, 2, 256, 64), BF16),) * 3
_QKV_LONG = (_s((1, 1, 8192, 128), BF16),) * 3     # past the fused backward

#: name -> () -> (function, abstract arguments): one way to each of the 21
#: ``pallas_call`` sites under ``apex_tpu/ops``
KERNELS = {
    "apex_amp_unscale": lambda: (_unscale, (_flat(),)),
    "apex_axpby": lambda: (
        lambda x, y: fu.fused_axpby(1.0, x, 2.0, y), (_flat(),) * 2),
    "apex_l2norm": lambda: (fu.fused_l2norm, (_flat(),)),
    "apex_l2norm_scale": lambda: (
        lambda x: fu.fused_l2norm_scale(x, 0.5), (_flat(),)),
    "apex_adam_update": lambda: (
        lambda p, g, m, v: fu.fused_adam_flat(
            p, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.0, step=1), (_flat(),) * 4),
    "apex_adagrad_update": lambda: (
        lambda p, g, h: fu.fused_adagrad_flat(
            p, g, h, lr=1e-2, eps=1e-10, weight_decay=0.0), (_flat(),) * 3),
    "apex_sgd_update": lambda: (
        lambda p, g, b: fu.fused_sgd_flat(
            p, g, b, lr=1e-2, momentum=0.9, dampening=0.0,
            weight_decay=0.0, nesterov=False), (_flat(),) * 3),
    "apex_lamb_stage1": lambda: (_lamb, (_flat(),) * 4),
    "apex_layer_norm_fwd": lambda: (_ln_fwd, _LN),
    "apex_layer_norm_bwd": lambda: (_ln_bwd, _LN),
    "apex_flash_fwd": lambda: (_flash, _QKV),
    "apex_flash_bwd": lambda: (_flash_bwd, _QKV),
    "apex_flash_bwd_dq": lambda: (_flash_bwd, _QKV_LONG),
    "apex_flash_bwd_dkv": lambda: (_flash_bwd, _QKV_LONG),
    "apex_paged_decode": lambda: _registered("paged_decode_attention"),
    "apex_paged_decode_latent": lambda: _registered("paged_decode_latent"),
    "apex_fused_block_decode": lambda: _registered("fused_block_decode"),
    "apex_dsa_index_fwd": lambda: _registered("dsa_index_scores"),
    "apex_dsa_index": lambda: _registered("paged_index_scores"),
    "apex_dsa_attend": lambda: _registered("paged_select_attention"),
    "apex_dsa_attend_latent": lambda: _registered(
        "paged_select_attention_latent"),
}


def _kernel_names(jaxpr) -> set:
    """``name`` of every ``pallas_call`` equation reachable from ``jaxpr``."""
    out = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.add(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= _kernel_names(sub)
    return out


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_the_pallas_call_equation_carries_its_stable_name(name):
    fn, args = KERNELS[name]()
    assert name in _kernel_names(jax.make_jaxpr(fn)(*args).jaxpr)


def test_every_pallas_call_site_is_named_and_no_name_is_used_twice():
    # a site's ``name=`` is a literal, or a variable assigned from literals
    # (the paged walk and the latent walk each run under two names: with
    # and without picked positions): 19 sites, 21 names
    calls, named, names = 0, 0, []
    for f in sorted(OPS.glob("*.py")):
        src = f.read_text()
        calls += len(re.findall(r"\bpl\.pallas_call\(", src))
        named += len(re.findall(r"^\s+name=[\w\"]+,$", src, re.M))
        for line in re.findall(r"^\s+name ?= ?.*$", src, re.M):
            names += re.findall(r'"(apex_\w+)"', line)
    assert calls == named == 19
    assert len(names) == 21 and sorted(names) == sorted(KERNELS)


# -- compiled for the described chip (no chip attached, nothing runs) --------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


BERT_LARGE_PARAMS = 334820352        # the flat length of the benchmark's cell
_ROWS, _HIDDEN = 32 * 128, 1024      # batch 32 x seq 128, BERT-large width


def _bert_large(name, sharding):
    flat = _flat(BERT_LARGE_PARAMS, sharding=sharding)
    rows = (_s((_ROWS, _HIDDEN), BF16, sharding=sharding),
            _s((_HIDDEN,), F32, sharding=sharding),
            _s((_HIDDEN,), F32, sharding=sharding))
    return {"apex_amp_unscale": (_unscale, (flat,)),
            "apex_lamb_stage1": (_lamb, (flat,) * 4),
            "apex_layer_norm_fwd": (_ln_fwd, rows),
            "apex_layer_norm_bwd": (_ln_bwd, rows)}[name]


@pytest.mark.parametrize("name", ["apex_amp_unscale", "apex_lamb_stage1",
                                  "apex_layer_norm_fwd",
                                  "apex_layer_norm_bwd"])
def test_v5e_compiles_a_custom_call_under_the_kernels_name(
        name, one_chip, monkeypatch):
    # the process's backend is the CPU, so the wrappers would interpret
    for mod in (fu, ln):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    fn, args = _bert_large(name, one_chip)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, "no Mosaic kernel in the compiled program"
    # the instruction is named after the kernel (autodiff wraps the name:
    # %jvp_apex_layer_norm_fwd_.1), never after the jitted function
    assert any(re.match(rf"\s*(ROOT )?%\w*{name}[\w.]* = ", line)
               for line in calls), [c[:120] for c in calls]


def _toy_gpt(one_chip):
    """A paged ``gpt`` for the described chip at a pool of 0.4 GB (one
    small enough is prefetched to on-chip memory): ``(cfg, params, cache,
    the pool's type)``, shapes only."""
    from apex_tpu.inference import kv_cache
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

    if not parallel_state.model_parallel_is_initialized():
        parallel_state.initialize_model_parallel(1)
    layers, heads, d, ps, slots, pages = 3, 2, 128, 64, 8, 4096
    cfg = GPTConfig(vocab_size=256, hidden_size=heads * d, num_layers=layers,
                    num_attention_heads=heads, max_seq_length=512,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    params_dtype=BF16)
    on_chip = lambda x: _s(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(
        gpt_model_provider(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32)))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: kv_cache.init_paged_cache(
            pages, layers, heads, ps, d, slots=slots,
            max_pages_per_slot=512 // ps)))
    return cfg, params, cache, f"bf16[{pages + 1},{layers},{heads},{ps},{d}]"


def _toy_axk1(one_chip):
    """A paged ``axk1`` for the described chip at the published latent row
    (512 + 64) and page, a pool of 0.45 GB: ``(cfg, params, cache, the
    pool's type)``, shapes only."""
    from apex_tpu.inference import kv_cache
    from apex_tpu.transformer.testing import standalone_axk1 as SA
    from apex_tpu.transformer.testing.standalone_laguna import YarnRope

    layers, slots, ps, pages, mpps = 3, 8, 256, 512, 4
    cfg = SA.AXK1Config(
        vocab_size=256, hidden_size=256, num_layers=layers, num_heads=8,
        q_lora_rank=128, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, ffn_hidden_size=256,
        moe_ffn_hidden_size=128, shared_ffn_hidden_size=128,
        num_experts=32, held=(0, 4), experts_per_token=4, n_group=4,
        topk_group=2, max_seq_length=ps * mpps,
        rope=YarnRope(theta=10000.0, rotary_dim=64, factor=32.0,
                      original_max_position=4096, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.0),
        params_dtype=BF16)
    on_chip = lambda x: _s(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    params = {"params": jax.tree.map(
        lambda shape: _s(shape, BF16, sharding=one_chip),
        SA.axk1_param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))}
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: kv_cache.init_paged_cache(
            pages, layers, 0, ps, 0, slots=slots, max_pages_per_slot=mpps,
            latent=cfg.latent_dim)))
    return cfg, params, cache, (
        f"bf16[{pages + 1},{layers},{cfg.latent_dim},{ps}]")


def test_v5e_gpt_decode_step_reads_the_pool_in_place(one_chip, monkeypatch):
    """A paged ``gpt`` decode step compiled for the chip (ISSUE 31): one
    ``apex_paged_decode`` custom call a layer, each reading the WHOLE pool
    where ``append_layer`` wrote it — no copy, slice or any other op whose
    result is pool-sized besides the appends' in-place updates.  (Interpret
    mode cannot show this: there the kernel is a loop that carries the
    pool.)"""
    import apex_tpu.ops.attention as at
    import apex_tpu.ops.paged_attention as pa
    from apex_tpu.inference.engine import make_decode_fn
    from apex_tpu.inference.sampling import SamplingConfig

    for mod in (at, ln, pa):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    cfg, params, cache, pool = _toy_gpt(one_chip)
    layers, slots = cache.layers, cache.slots
    step = jax.jit(make_decode_fn("gpt", cfg, SamplingConfig()),
                   donate_argnums=(0,))
    args = (cache, params, _s((slots,), jnp.int32, sharding=one_chip),
            _s((slots,), bool, sharding=one_chip),
            _s((2,), jnp.uint32, sharding=one_chip),
            _s((), jnp.int32, sharding=one_chip))
    # the step TRACES the work list's builder once, not once a layer (the
    # compiler would fold equal builds, so the jaxpr is where that shows)
    assert str(jax.make_jaxpr(step)(*args)).count(
        "name=paged_work_list") == 1
    hlo = step.lower(*args).compile().as_text()
    made = {}                       # op -> count, of pool-sized results
    lists = set()                   # the kernels' work-list operands
    kernels = 0
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, op = m.groups()
        if op == "custom-call" and name.startswith("apex_paged_decode"):
            kernels += 1
            assert line.count(pool) == 2, line[:300]     # k and v, whole
            # grid bound, slot, page, start, lengths: then layer, q, k, v
            lists.add(tuple(re.findall(
                r"%[\w.\-]+", line.split("custom-call(", 1)[1])[:5]))
        if result.startswith(pool) and op != "parameter":
            made[op] = made.get(op, 0) + 1
    assert kernels == layers
    # every layer's call walks the SAME list: one build a step, whose one
    # gather reads int32 table entries
    assert len(lists) == 1, lists
    assert len(re.findall(r" gather\(.*paged_work_list", hlo)) == 1
    # the appends: one in-place scatter of k and of v a layer, nothing else
    assert set(made) <= {"fusion", "scatter"}, made
    assert sum(made.values()) <= 4 * layers, made


def _prefill_setup(kind, one_chip):
    """A paged prefill of ``kind`` compiled for the chip with the cache
    donated: ``(compiled HLO text, the pool's type, memory stats)``."""
    from apex_tpu.inference.engine import make_prefill_fn
    from apex_tpu.inference.sampling import SamplingConfig

    cfg, params, cache, pool = {"gpt": _toy_gpt,
                                "axk1": _toy_axk1}[kind](one_chip)
    step = jax.jit(make_prefill_fn(kind, cfg, SamplingConfig(), paged=True),
                   donate_argnums=(0,))
    i32 = lambda *shape: _s(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = step.lower(
        cache, params, i32(2 * cache.page_size), i32(), i32(),
        i32(cache.max_pages_per_slot), i32(),
        _s((2,), jnp.uint32, sharding=one_chip), i32()).compile()
    return compiled.as_text(), pool, compiled.memory_analysis()


def _computation(hlo: str, name: str) -> str:
    """The text of the HLO computation called ``name``."""
    return re.search(rf"^%{re.escape(name)} .*?^}}", hlo, re.M | re.S)[0]


@pytest.mark.parametrize("kind", ["gpt", "axk1"])
def test_v5e_gpt_prefill_writes_the_pool_in_place(kind, one_chip,
                                                  monkeypatch):
    """A paged prefill compiled for the chip writes its slab
    into the donated pool in place: no op besides the insert's scatters
    (one a pool array) has a pool-sized result.  ``gpt`` shares prefixes,
    so its start is traced and a ``cond`` of the insert picks the rows to
    write: the pool goes in read-only and is written after it — the
    aligned branch does not touch the pool at all (no gather, no copy),
    the mid-page one reads the pages it touches.  ``axk1`` never resumes:
    its insert is the aligned write with no ``cond`` at all."""
    import apex_tpu.ops.attention as at
    import apex_tpu.ops.paged_attention as pa

    for mod in (at, ln, pa):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    hlo, pool, stats = _prefill_setup(kind, one_chip)
    made = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if m and m.group(2).startswith(pool) and m.group(3) not in (
                "parameter", "get-tuple-element", "tuple"):
            made[m.group(3)] = made.get(m.group(3), 0) + 1
    # the write, in place and never a copy: a scatter of whole pages a pool
    # array (a fusion round a scatter); ``gpt`` also writes the page after
    # them, which only its mid-page write fills (a fusion round a
    # one-page dynamic-update-slice)
    arrays, writes = (2, 2) if kind == "gpt" else (1, 1)
    assert set(made) <= {"fusion", "scatter", "dynamic-update-slice"}, made
    assert sum(made.values()) <= 2 * arrays * writes, made
    conds = [line for line in hlo.splitlines() if " conditional(" in line
             and "apex_prefill_cache_insert" in line]
    if kind != "gpt":
        assert not conds, conds
    else:
        assert len(conds) == arrays, conds
        for cond in conds:
            # branch 1 is the true branch: the start on a page boundary
            mid, aligned = re.search(r"branch_computations=\{%([\w.]+), "
                                     r"%([\w.]+)\}", cond).groups()
            assert pool not in _computation(hlo, aligned)
            assert " gather(" not in _computation(hlo, aligned)
            assert pool in _computation(hlo, mid)
            # the branches hand back rows, never the pool
            assert pool not in cond.split(" conditional(")[0], cond[:300]
    assert stats.temp_size_in_bytes < 64 << 20, stats


@pytest.mark.parametrize("table", ["laguna", "gpt"])
def test_v5e_paged_decode_takes_the_cells_work_lists(table, one_chip,
                                                      monkeypatch):
    """``apex_paged_decode`` at a cell's table compiles for the chip
    (ISSUE 33): the grid's one dynamic bound, and the work list as
    scalar-prefetch operands — ``laguna-xs.2-serve``'s is 32 slots x 260
    pages = 8,320 entries an array in SMEM, beside 8 KV heads."""
    import apex_tpu.ops.paged_attention as pa
    monkeypatch.setattr(pa, "interpret_mode", lambda: False)
    slots, h, kvh, mpps, pages, layers = {
        "laguna": (32, 48, 8, 260, 4096, 2),
        "gpt": (16, 16, 16, 32, 640, 24)}[table]
    ps, d = 64, 128
    on = lambda shape, dtype: _s(shape, dtype, sharding=one_chip)  # noqa: E731
    pool = on((pages + 1, layers, kvh, ps, d), BF16)
    hlo = jax.jit(
        lambda *a: pa.paged_decode_attention(*a, layer=layers - 1)).lower(
            on((slots, h, d), BF16), pool, pool,
            on((slots, mpps), jnp.int32),
            on((slots,), jnp.int32)).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "%apex_paged_decode" in calls[0], calls
    entries = f"s32[{slots * mpps}]"
    # the bound, then slot and page lists at their whole capacity
    assert f"operand_layout_constraints={{s32[], {entries}{{0}}, " \
           f"{entries}{{0}}, s32[{slots + 1}]{{0}}" in calls[0], calls[0][:600]


def test_v5e_latent_decode_step_reads_the_one_pool_in_place(one_chip,
                                                            monkeypatch):
    """A paged ``axk1`` decode step compiled for the chip (ISSUE 34): one
    ``apex_paged_decode_latent`` custom call a layer, each reading the ONE
    latent pool whole where ``append_layer`` wrote it, all along the same
    work list — no value pool, and no copy, slice or any other op whose
    result is pool-sized besides the appends' in-place updates."""
    import apex_tpu.ops.attention as at
    import apex_tpu.ops.paged_attention as pa
    from apex_tpu.inference.engine import make_decode_fn
    from apex_tpu.inference.sampling import SamplingConfig

    for mod in (at, ln, pa):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    cfg, params, cache, pool = _toy_axk1(one_chip)
    layers, slots = cache.layers, cache.slots
    assert cache.v is None
    step = jax.jit(make_decode_fn("axk1", cfg, SamplingConfig()),
                   donate_argnums=(0,))
    args = (cache, params, _s((slots,), jnp.int32, sharding=one_chip),
            _s((slots,), bool, sharding=one_chip),
            _s((2,), jnp.uint32, sharding=one_chip),
            _s((), jnp.int32, sharding=one_chip))
    assert str(jax.make_jaxpr(step)(*args)).count(
        "name=paged_work_list") == 1
    hlo = step.lower(*args).compile().as_text()
    made, bounds, kernels = {}, set(), 0
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, op = m.groups()
        if op == "custom-call" and name.startswith("apex_paged_decode"):
            assert name.startswith("apex_paged_decode_latent"), name
            kernels += 1
            assert line.count(pool) == 1, line[:300]      # ONE pool, whole
            # the grid's bound: the one work list's count of live items
            # (the lists themselves reach each call through the
            # compiler's own copies of 32 entries into fast memory)
            bounds.add(re.findall(
                r"%[\w.\-]+", line.split("custom-call(", 1)[1])[0])
        if result.startswith(pool) and op != "parameter":
            made[op] = made.get(op, 0) + 1
    assert kernels == layers
    assert len(bounds) == 1, bounds
    # the appends: one in-place scatter of the row a layer, nothing else
    assert set(made) <= {"fusion", "scatter"}, made
    assert sum(made.values()) <= 2 * layers, made


def test_v5e_selecting_decode_step_reads_three_pools_in_place(one_chip,
                                                             monkeypatch):
    """A paged ``keye`` decode step compiled for the chip (ISSUE 36) at the
    published widths of a page (4 KV heads x 128, index keys of 64, page
    128): one ``apex_dsa_index`` and one ``apex_dsa_attend`` custom call a
    layer and NO ``apex_paged_decode`` — the index kernel reads the
    index-key pool whole, the attention kernel the K and V pools whole,
    all along the one work list — and no copy, slice or any other op whose
    result is pool-sized besides the appends' in-place updates, nor one
    that is ``[slots, max_seq, heads, d]``-sized."""
    import apex_tpu.ops.attention as at
    import apex_tpu.ops.paged_attention as pa
    from apex_tpu.inference import kv_cache
    from apex_tpu.inference.engine import make_decode_fn
    from apex_tpu.inference.sampling import SamplingConfig
    from apex_tpu.transformer.testing import standalone_keye as SK

    for mod in (at, ln, pa):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    # pools too large for the compiler to park in fast memory, as the
    # cell's are (a 12 MB index-key pool it prefetches whole)
    layers, slots, ps, pages, mpps = 3, 8, 128, 2048, 32
    cfg = SK.KeyeConfig(
        vocab_size=256, hidden_size=256, num_layers=layers, num_heads=32,
        num_kv_heads=4, head_dim=128, mrope_section=(16, 24, 24),
        index_heads=16, index_head_dim=64, index_topk=2048,
        index_q_chunk=512, moe_ffn_hidden_size=128, num_experts=16,
        experts_per_token=4, max_seq_length=ps * mpps, params_dtype=BF16)
    on_chip = lambda x: _s(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    params = {"params": jax.tree.map(
        lambda shape: _s(shape, BF16, sharding=one_chip),
        SK.keye_param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))}
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: kv_cache.init_paged_cache(
            pages, layers, 4, ps, 128, slots=slots, max_pages_per_slot=mpps,
            index=64)))
    step = jax.jit(make_decode_fn("keye", cfg, SamplingConfig()),
                   donate_argnums=(0,))
    args = (cache, params, _s((slots,), jnp.int32, sharding=one_chip),
            _s((slots,), bool, sharding=one_chip),
            _s((2,), jnp.uint32, sharding=one_chip),
            _s((), jnp.int32, sharding=one_chip))
    assert str(jax.make_jaxpr(step)(*args)).count(
        "name=paged_work_list") == 1
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    kv_pool = f"bf16[{pages + 1},{layers},4,{ps},128]"
    ik_pool = f"bf16[{pages + 1},{layers},64,{ps}]"
    window = slots * ps * mpps * 4 * 128        # [slots, max_seq, kvh, d]
    made, calls = {}, {"apex_dsa_index": 0, "apex_dsa_attend": 0}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, op = m.groups()
        if op == "custom-call":
            assert not name.startswith("apex_paged_decode"), name
            kind = re.sub(r"\.\d+$", "", name)
            if kind in calls:
                calls[kind] += 1
                # each pool it reads, whole, once
                want = {"apex_dsa_index": (0, 1),
                        "apex_dsa_attend": (2, 0)}[kind]
                assert (line.count(kv_pool), line.count(ik_pool)) == want, \
                    line[:400]
        for pool in (kv_pool, ik_pool):
            if result.startswith(pool) and op != "parameter":
                made[op] = made.get(op, 0) + 1
        # no gathered window of the slots' whole contexts
        dims = re.match(r"\w+\[([\d,]+)\]", result)
        if dims and op not in ("parameter", "get-tuple-element", "bitcast"):
            size = np.prod([int(x) for x in dims.group(1).split(",")])
            assert size < window or result.startswith((kv_pool, ik_pool)), \
                line[:300]
    assert calls == {"apex_dsa_index": layers, "apex_dsa_attend": layers}
    # the appends: in-place scatters of the token's rows, nothing else
    assert set(made) <= {"fusion", "scatter"}, made
    assert sum(made.values()) <= 2 * 3 * layers, made
    pool_bytes = 2 * (pages + 1) * layers * ps * (2 * 4 * 128 + 64)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


#: the serving cells' caches: (pool shape, rings' shape or None, slots,
#: pages a slot[, the index-key pool's shape]) — gpt3-1.3b-serve,
#: laguna-xs.2-serve, a.x-k1-serve, keye-vl-2.0-30b-a3b-serve
_CELL_CACHES = {
    "gpt": ((641, 24, 16, 64, 128), None, 16, 32),
    "laguna": ((4097, 2, 8, 64, 128), (3, 32, 8, 576, 128), 32, 260),
    "latent": ((1537, 6, 576, 256), None, 64, 35),
    "select": ((2817, 5, 4, 128, 128), None, 16, 264, (2817, 5, 64, 128)),
}


@pytest.mark.parametrize("pool", sorted(_CELL_CACHES))
def test_v5e_evict_writes_the_metadata_and_copies_no_pool(pool, one_chip):
    """Retirement's device half compiled for the chip (ISSUE 35): the
    program an engine jits in its constructor (``_evict``: the cache
    donated, the slot a traced int32 — the same for every kind, so a toy
    engine's is lowered) at each serving cell's cache: every leaf aliased
    to itself, the pool (``laguna``'s rings, the latent array) handed from
    parameter to result with no op in between, no temporary — only the
    page table, the lengths and the capacity are written."""
    from apex_tpu.inference import InferenceEngine, kv_cache
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

    if not parallel_state.model_parallel_is_initialized():
        parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    evict = InferenceEngine(
        "gpt", cfg, gpt_model_provider(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)),
        slots=2, page_size=8, num_pages=8)._evict
    shape, rings, slots, mpps, *index = _CELL_CACHES[pool]
    on = lambda s, dt: _s(s, dt, sharding=one_chip)  # noqa: E731
    latent = len(shape) == 4
    cache = kv_cache.PagedKVCache(
        k=on(shape, BF16), v=None if latent else on(shape, BF16),
        page_table=on((slots, mpps), jnp.int32),
        lengths=on((slots,), jnp.int32), capacity=on((slots,), jnp.int32),
        last_tokens=on((slots,), jnp.int32),
        wk=rings and on(rings, BF16), wv=rings and on(rings, BF16),
        ik=on(index[0], BF16) if index else None)
    leaves = len(jax.tree_util.tree_leaves(cache))
    compiled = evict.lower(cache, on((), jnp.int32)).compile()
    hlo = compiled.as_text()
    head = hlo.split("\n", 1)[0]
    for i in range(leaves):
        assert f"{{{i}}}: ({i}, {{}}, may-alias)" in head, head[:400]
    big = {"bf16[" + ",".join(map(str, s)) + "]"
           for s in (shape, rings, *index) if s}
    written = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if not m:
            continue
        _, result, op = m.groups()
        if op not in ("parameter", "tuple"):
            assert not any(result.startswith(b) for b in big), line[:200]
            if op == "dynamic-update-slice":
                written.append(result.split("{")[0])
    assert sorted(written) == sorted(
        [f"s32[{slots}]", f"s32[{slots}]", f"s32[{slots},{mpps}]"])
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 1 << 16, stats
    small = 4 * (slots * mpps + 2 * slots)
    assert stats.argument_size_in_bytes - stats.alias_size_in_bytes \
        <= small, stats


def _mlp_loss(params, batch):
    h = batch["x"]
    for i in range(len(params) // 2):
        h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
    return jnp.mean((h - batch["y"]) ** 2)


def _unscaled_copy_step(loss_fn, tx):
    """The train step as it was spelled before the loss scale's 1/scale
    rode LAMB's ``grad_scale``: ``fused_scale`` writes an unscaled copy of
    the flat grads, and the update reads that copy."""
    from apex_tpu.amp.scaler import update_scale

    def step(state, batch):
        opt, scaler = state.opt, state.scaler
        g = jax.grad(lambda flat: loss_fn(opt.unravel(flat), batch)
                     * scaler.loss_scale)(opt.master)
        g, flag = fu.fused_scale(g, 1.0 / scaler.loss_scale)
        return state.replace(
            opt=tx.update(opt, g, noop_flag=flag),
            scaler=update_scale(scaler.replace(found_inf=flag)))
    return step


def _lamb_step_hlo(one_chip, make_step):
    """Optimized HLO of a dense LAMB + dynamic loss-scale train step of a
    16-layer MLP of width 1024 (16.8M parameters, 67 MB a flat buffer:
    at a quarter of that the compiler keeps the buffers in on-chip
    memory and fuses what it would not fuse at BERT-large's size),
    compiled for the described chip, and the flat length."""
    from apex_tpu import train_step
    from apex_tpu.optimizers import functional

    tx = functional.fused_lamb(lr=1e-3)
    d = 1024
    state = jax.eval_shape(lambda: train_step.init_train_state(
        tx, {f"{w}{i}": jnp.zeros((d, d) if w == "w" else (d,))
             for i in range(16) for w in "wb"}, loss_scale="dynamic"))
    on = lambda x: _s(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    batch = {"x": _s((16, d), F32), "y": _s((16, d), F32)}
    step = jax.jit(make_step(_mlp_loss, tx), donate_argnums=(0,))
    hlo = step.lower(jax.tree.map(on, state),
                     jax.tree.map(on, batch)).compile().as_text()
    return hlo, state.opt.master.shape[0]


def _hlo_graph(hlo):
    """``(ENTRY instruction -> (opcode, operands, line), fused
    computation -> its instruction lines)`` of an optimized HLO text."""
    bodies = {m.group(1): m.group(2).splitlines() for m in re.finditer(
        r"\n(%[\w.\-]+) \([^\n]*\{\n(.*?)\n\}", hlo, re.S)}
    entry = {}
    for line in hlo[hlo.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .*? ([\w\-]+)\((.*?)\)",
                     line)
        if m:
            operands = re.findall(r"%[\w.\-]+", m.group(3))
            entry[m.group(1)] = (m.group(2), operands, line)
    return entry, bodies


def _producer(entry, bodies, name):
    """The instruction line that computes ENTRY value ``name``: inside a
    fusion, the root (or the root tuple's element) that yields it."""
    op, operands, line = entry[name]
    index = None
    if op == "get-tuple-element":
        index = int(re.search(r"index=(\d+)", line).group(1))
        op, operands, line = entry[operands[0]]
    calls = re.search(r"calls=(%[\w.\-]+)", line)
    if op != "fusion" or not calls:
        return line
    body = bodies[calls.group(1)]
    root = next(lb for lb in body if lb.lstrip().startswith("ROOT"))
    if index is not None:
        element = re.findall(r"%[\w.\-]+",
                             root.split(" tuple(", 1)[1])[index]
        root = next(lb for lb in body
                    if re.match(rf"\s*{re.escape(element)} = ", lb))
    return root


def _lamb_grad_reads(hlo):
    """What the compiled step does with the flat grads before LAMB's
    kernel: ``(the line that yields the kernel's grad operand, the ENTRY
    fusions that reduce over those grads)``."""
    entry, bodies = _hlo_graph(hlo)
    kernel = next(v for k, v in entry.items()
                  if k.startswith("%apex_lamb_stage1"))
    grad = kernel[1][1]
    source = entry[grad][1][0] if entry[grad][0] == "get-tuple-element" \
        else grad
    readers = []
    for name, (op, operands, line) in entry.items():
        calls = re.search(r"calls=(%[\w.\-]+)", line)
        if op == "fusion" and calls and (name == source
                                         or source in operands):
            reduces = [lb for lb in bodies[calls.group(1)]
                       if " reduce(" in lb]
            if reduces:
                readers.append(reduces)
    return _producer(entry, bodies, grad), readers


def test_v5e_lamb_reads_the_grads_the_backward_wrote(one_chip, monkeypatch):
    """The loss scale's 1/scale rides LAMB's ``grad_scale``: compiled for
    the chip, the train step holds no ``apex_amp_unscale``, the kernel's
    grad operand is what the backward wrote (not a multiply: no scaled
    or unscaled copy of the flat grads), and ONE fusion reduces over
    those grads before the kernel — the overflow flag and LAMB's sum of
    squares in the same pass."""
    from apex_tpu import train_step

    monkeypatch.setattr(fu, "interpret_mode", lambda: False)
    hlo, n = _lamb_step_hlo(one_chip, train_step.make_train_step)
    assert "apex_amp_unscale" not in hlo
    source, readers = _lamb_grad_reads(hlo)
    assert f"f32[{n}]" in source, source[:200]
    assert " multiply(" not in source, source[:200]
    assert "transpose(jvp(apex_train_forward))" in source, source[:200]
    assert len(readers) == 1, [r[:1] for r in readers]
    scopes = " ".join(readers[0])
    assert "apex_train_unscale/reduce_max" in scopes
    assert "apex_train_optimizer/reduce_sum" in scopes


def test_v5e_lamb_grad_reads_tell_the_unscaled_copy(one_chip, monkeypatch):
    """Positive control of the test above: the step that writes an
    unscaled copy first trips it — the copy's kernel is in the program,
    and LAMB reads its output, not the backward's."""
    monkeypatch.setattr(fu, "interpret_mode", lambda: False)
    hlo, _ = _lamb_step_hlo(one_chip, _unscaled_copy_step)
    assert re.search(r"%apex_amp_unscale[\w.]* = ", hlo)
    source, readers = _lamb_grad_reads(hlo)
    assert "transpose(jvp(apex_train_forward))" not in source, source[:200]
    assert not any("apex_train_unscale" in " ".join(r) for r in readers)
