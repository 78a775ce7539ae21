"""The single registry of ``APEX_TPU_*`` environment knobs.

Every environment variable the package reads MUST have an entry here:
the APX108 lint rule flags any ``os.environ``/``os.getenv`` read of an
``APEX_TPU_``-prefixed name that is not registered, and the README
"Environment knobs" table is validated against this dict by
``tests/L0/run_analysis/test_env_registry.py`` — so the docs cannot
drift from the code, and a new knob cannot ship undocumented.

To add a knob: read it in code, add an :class:`EnvKnob` entry here,
and add the matching row to README.md; the lint + the doc test enforce
both halves.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

__all__ = ["EnvKnob", "KNOBS", "is_registered"]


@dataclass(frozen=True)
class EnvKnob:
    name: str
    default: str
    effect: str
    read_by: str          # module that consumes it


KNOBS: Dict[str, EnvKnob] = {k.name: k for k in [
    EnvKnob(
        name="APEX_TPU_CPP_EXT",
        default="0",
        effect="build-time: compile the optional C++ parity extension "
               "(csrc/) during `pip install`; everything degrades "
               "gracefully without it",
        read_by="setup.py"),
    EnvKnob(
        name="APEX_TPU_ATTN_XLA_MAX_SEQ",
        default="256",
        effect="flash_attention auto-dispatches padded sequences at or "
               "below this length to the fused-XLA path (measured "
               "kernel/XLA crossover, bench r5; 0 disables the XLA "
               "path); per-call override: flash_attention("
               "xla_max_seq=...)",
        read_by="apex_tpu/ops/attention.py"),
    EnvKnob(
        name="APEX_TPU_DECODE_XLA_MAX_SEQ",
        default="4096",
        effect="decode_attention uses the grouped-query XLA einsum "
               "chain at or below this cache length and the flash "
               "kernel above it (PROVISIONAL crossover, stamped into "
               "infer bench captures); per-call override: "
               "decode_attention(xla_max_seq=...)",
        read_by="apex_tpu/ops/attention.py"),
    EnvKnob(
        name="APEX_TPU_ZERO_PREFETCH",
        default="0",
        effect="number of layered-prefetch gather spans a ZeRO train "
               "state is built with when prefetch= is not passed: the "
               "flat master's param all-gather splits along leaf "
               "boundaries into this many independent per-span gathers "
               "XLA overlaps with the consuming layers (APX217-"
               "verified; 0/1 = monolithic gather); stamped into ZeRO "
               "bench captures as zero_prefetch",
        read_by="apex_tpu/train_step.py"),
    EnvKnob(
        name="APEX_TPU_TP_OVERLAP_CHUNKS",
        default="1",
        effect="default overlap_chunks for tensor-parallel Column/Row "
               "layers: >1 decomposes the row-parallel matmul+psum "
               "(and the column-parallel backward psum) into an "
               "N-chunk matmul/ppermute ring pipeline at identical "
               "ring bytes (1 = fused psum; must be a multiple of the "
               "tensor axis size); per-layer override: "
               "overlap_chunks=; stamped into TP bench captures as "
               "tp_overlap_chunks",
        read_by="apex_tpu/transformer/tensor_parallel/mappings.py"),
    EnvKnob(
        name="APEX_TPU_PAGE_SIZE",
        default="64",
        effect="default KV page size (tokens per page, power of two) "
               "for paged inference engines that don't pass "
               "page_size= explicitly; stamped into paged infer bench "
               "captures",
        read_by="apex_tpu/inference/kv_cache.py"),
    EnvKnob(
        name="APEX_TPU_XENT_CHUNK",
        default="0",
        effect="token-chunk size of the fused LM-head+cross-entropy "
               "(the [tokens, vocab] logits never materialize; the "
               "backward re-projects per chunk) used by loss heads "
               "when fused_head_xent=/token_chunk= is not passed; 0 "
               "keeps the unfused dense logits; stamped into "
               "xent_fused bench captures as xent_chunk",
        read_by="apex_tpu/ops/fused_lm_xent.py"),
    EnvKnob(
        name="APEX_TPU_XENT_VOCAB_CHUNK",
        default="0",
        effect="vocab-chunk size of the fused LM-head+cross-entropy's "
               "inner online-logsumexp scan (shrinks the per-chunk "
               "logits transient to [token_chunk, vocab_chunk]; must "
               "divide the vocab) when vocab_chunk= is not passed; 0 "
               "projects the whole vocab per token chunk; stamped "
               "into xent_fused bench captures as xent_vocab_chunk",
        read_by="apex_tpu/ops/fused_lm_xent.py"),
    EnvKnob(
        name="APEX_TPU_TELEMETRY",
        default="0",
        effect="runtime telemetry sink directory: a path attaches the "
               "JSONL event log (telemetry.jsonl) and the Prometheus "
               "text-exposition file (metrics.prom) to the global "
               "metrics registry at first use; 0 keeps telemetry "
               "in-process only (instruments still work, nothing is "
               "written); schema pinned by .telemetry_schema.json",
        read_by="apex_tpu/observability/__init__.py"),
    EnvKnob(
        name="APEX_TPU_PROFILE_DIR",
        default="0",
        effect="profiler capture directory: a path arms observability."
               "profile_capture() — bench legs and examples/generate.py "
               "drop jax.profiler (TensorBoard/xprof) traces there, and "
               "the main bench leg re-ingests them (trace_ingest) into "
               "measured attribution stamps; an unwritable or already-"
               "populated dir degrades to a no-op with a "
               "profile_skipped event (never shadows an old trace); 0 "
               "disables capture (the context manager is a no-op)",
        read_by="apex_tpu/observability/tracing.py"),
    EnvKnob(
        name="APEX_TPU_NUMERICS",
        default="0",
        effect="numerics observability mode (grad/param/update-norm "
               "probes + overflow autopsy) for instrumented_train_loop "
               "when numerics= is not passed: 1 computes the in-program "
               "probes as extra outputs of the same ONE donated step "
               "and arms the numerics metric families + JSONL events "
               "(zero added syncs, zero recompiles); 0 (default) off; "
               "stamped into train bench captures as numerics",
        read_by="apex_tpu/observability/numerics.py"),
    EnvKnob(
        name="APEX_TPU_NUMERICS_EVERY",
        default="1",
        effect="numerics NORM-probe sampling interval: observe the "
               "norm probes every Nth step (host-side choice of what "
               "the deferred collector enqueues — the compiled step is "
               "identical at every value, so flipping it can never "
               "recompile); the overflow autopsy's per-leaf nonfinite "
               "vector and loss-scale backoff/growth tracking ride "
               "every step regardless; stamped into train bench "
               "captures as numerics_every",
        read_by="apex_tpu/observability/numerics.py"),
    EnvKnob(
        name="APEX_TPU_PREFIX_CACHE",
        default="1",
        effect="shared-prefix KV page sharing for paged schedulers: 1 "
               "(default) matches each prompt against the host radix "
               "prefix cache and maps cached prefix pages into the "
               "slot's page-table row at one reference each "
               "(refcount + copy-on-write; only the uncached tail "
               "prefills); 0 disables matching and insertion (every "
               "admission prefills cold); per-scheduler override: "
               "SlotScheduler(prefix_cache=); stamped into paged "
               "infer bench captures as infer_prefix_cache",
        read_by="apex_tpu/inference/prefix_cache.py"),
    EnvKnob(
        name="APEX_TPU_PREFILL_CHUNK",
        default="0",
        effect="chunked-prefill chunk size in tokens for paged "
               "schedulers (must be a multiple of the page size): "
               "prompts longer than this prefill in chunks interleaved "
               "with decode steps so a long-prompt burst cannot stall "
               "in-flight decode tokens for a whole monolithic "
               "prefill; 0 (default) keeps monolithic prefill; "
               "per-scheduler override: SlotScheduler(prefill_chunk=); "
               "stamped into paged infer bench captures as "
               "infer_prefill_chunk",
        read_by="apex_tpu/inference/scheduler.py"),
    EnvKnob(
        name="APEX_TPU_TENANT_PRIORITY",
        default="0",
        effect="per-tenant admission-priority overrides for the "
               "SLO-aware scheduler, as 'tenantA=10,tenantB=-1' "
               "(added to each request's own priority when picking "
               "the next admission; ties go to the least recently "
               "admitted tenant, then FIFO); 0/empty (default) = no "
               "overrides; per-scheduler override: "
               "SlotScheduler(tenant_priority=)",
        read_by="apex_tpu/inference/scheduler.py"),
    EnvKnob(
        name="APEX_TPU_TRACE",
        default="0",
        effect="request-trace sampling for serving schedulers: 0 "
               "(default) off, 1 traces every request, N traces one "
               "request in N (uid % N == 0) — each sampled request's "
               "lifecycle lands in the JSONL stream as trace_span "
               "events (queued/admitted/prefill_chunk/cow_copy/"
               "first_token/decode/retired) rendered by `report "
               "--trace <uid>`; host-side only (the tracer never "
               "enters jitted code), so no value can add a sync or "
               "recompile; per-telemetry override: ServeTelemetry("
               "trace=); stamped into infer bench captures as "
               "infer_trace",
        read_by="apex_tpu/observability/spans.py"),
    EnvKnob(
        name="APEX_TPU_SLO_TTFT_US",
        default="0",
        effect="TTFT p99 SLO target in microseconds (0 = off): arms a "
               "ttft_p99 objective over serve_ttft_seconds — per-wave "
               "burn-rate/error-budget gauges, slo_violation events "
               "when a window burns faster than its 1% budget "
               "(bucket-resolution accounting off the pinned "
               "histogram; host-side only, can never recompile); "
               "per-scheduler override: SlotScheduler(slo=); stamped "
               "into infer bench captures as infer_slo_ttft (µs)",
        read_by="apex_tpu/observability/slo.py"),
    EnvKnob(
        name="APEX_TPU_SLO_DECODE_US",
        default="0",
        effect="decode-token p99 SLO target in microseconds (0 = "
               "off): arms a decode_token_p99 objective over "
               "serve_decode_token_seconds — same burn-rate/error-"
               "budget accounting as APEX_TPU_SLO_TTFT_US; stamped "
               "into infer bench captures as infer_slo_decode (µs)",
        read_by="apex_tpu/observability/slo.py"),
    EnvKnob(
        name="APEX_TPU_DECODE_FUSION",
        default="0",
        effect="fused transformer-block decode for paged engines: 1 "
               "lowers every decode-layer as ONE Pallas kernel (norm "
               "+ qkv + RoPE + paged attention incl. the current "
               "token + out-proj + MLP; weights resident in VMEM, "
               "activations never round-trip HBM between sublayers), "
               "0 (default) keeps the per-op XLA path bitwise, auto "
               "fuses when the per-slot window reaches "
               "APEX_TPU_FUSION_MIN_PAGES pages; resolved STATICALLY "
               "at engine construction (one decode executable either "
               "way); per-engine override: InferenceEngine("
               "decode_fusion=); stamped into paged infer bench "
               "captures as infer_decode_fusion",
        read_by="apex_tpu/ops/paged_attention.py"),
    EnvKnob(
        name="APEX_TPU_FUSION_MIN_PAGES",
        default="8",
        effect="auto-mode crossover for APEX_TPU_DECODE_FUSION: fuse "
               "the decode block when max_pages_per_slot is at least "
               "this many pages (PROVISIONAL, stamped into paged "
               "infer bench captures as infer_fusion_min_pages); "
               "per-engine override: InferenceEngine("
               "fusion_min_pages=)",
        read_by="apex_tpu/ops/paged_attention.py"),
    EnvKnob(
        name="APEX_TPU_SPEC_K",
        default="0",
        effect="speculative decoding: drafted tokens per decode round "
               "(0 = off).  Engines built with spec_k > 0 serve "
               "decode through ONE compiled verify executable per k "
               "(slab width k+1 is static) scoring all drafts + the "
               "bonus token in one batched paged-attention step; "
               "accept/reject is an in-program length rollback "
               "(pages already reserved, rejection releases "
               "nothing).  Per-engine override: InferenceEngine("
               "spec_k=); stamped into infer bench captures as "
               "infer_spec_k",
        read_by="apex_tpu/inference/speculative.py"),
    EnvKnob(
        name="APEX_TPU_SERVE_TP",
        default="0",
        effect="tensor-parallel serving width (ISSUE 17): 0/unset = "
               "single chip; N > 1 shards the engine's param mirrors "
               "column/row-wise and the paged kv pool over kv heads "
               "across an N-chip mesh — each step stays ONE donated "
               "executable (a shard_map mesh program), the page "
               "table/allocator/prefix cache stay replicated host-side "
               "logic.  Requires the paged cache; needs tp | heads and "
               "tp | kv_heads or kv_heads | tp (GQA/MQA replicate "
               "below tp).  Per-engine override: InferenceEngine(tp=); "
               "stamped into infer bench captures as infer_serve_tp",
        read_by="apex_tpu/inference/engine.py"),
    EnvKnob(
        name="APEX_TPU_HOST_KV_TIER_BYTES",
        default="0",
        effect="host-DRAM KV page tier byte budget for paged serving "
               "(ISSUE 18): > 0 arms a second cache tier under the "
               "prefix cache — LRU eviction copies full prefix pages "
               "to host RAM (the HBM page frees immediately) instead "
               "of discarding them, and a later hit uploads them back "
               "in fixed-width batches overlapped with chunked prefill "
               "of the uncached tail; 0 (default) keeps discard-on-"
               "evict.  Requires the paged cache.  Per-engine "
               "override: InferenceEngine(host_tier_bytes=); stamped "
               "into paged infer bench captures as "
               "infer_host_tier_bytes",
        read_by="apex_tpu/inference/engine.py"),
    EnvKnob(
        name="APEX_TPU_SWAP_BATCH_PAGES",
        default="8",
        effect="pages per swap copy batch for the host KV tier: both "
               "swap directions run ONE fixed-width executable each "
               "(shorter batches pad with the trash page / an OOB "
               "drop sentinel), so swap traffic can never recompile; "
               "per-engine override: InferenceEngine("
               "swap_batch_pages=); stamped into paged infer bench "
               "captures as infer_swap_batch_pages",
        read_by="apex_tpu/inference/kv_cache.py"),
    EnvKnob(
        name="APEX_TPU_FLEET_REPLICAS",
        default="0",
        effect="replica count for the fleet front door (ISSUE 19): "
               "> 0 makes bench's fleet leg / examples build this "
               "many engine+scheduler replicas behind one FleetRouter "
               "(process-local, equal aggregate HBM); 0 (default) "
               "serves behind one standalone scheduler.  Stamped into "
               "fleet bench captures as fleet_replicas",
        read_by="apex_tpu/fleet/router.py"),
    EnvKnob(
        name="APEX_TPU_FLEET_POLICY",
        default="prefix_affinity",
        effect="routing policy when FleetRouter(policy=None): "
               "round_robin, least_loaded, or prefix_affinity "
               "(read-only radix peek + swap-aware admission cost, "
               "with a load-aware spill threshold); stamped into "
               "fleet bench captures as fleet_policy",
        read_by="apex_tpu/fleet/router.py"),
    EnvKnob(
        name="APEX_TPU_PROTOCOL_SCOPE",
        default="0",
        effect="comma-separated scope names `apex-tpu-analyze "
               "--protocol` restricts the protocol audit to "
               "(core/tiered/fleet; `0`/unset = all committed "
               "scopes); a restricted run refuses --write-protocol "
               "so the shared pin always covers every scope",
        read_by="apex_tpu/analysis/protocol_audit.py"),
]}


def is_registered(name: str) -> bool:
    return name in KNOBS
