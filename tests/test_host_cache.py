"""What a served family's ``stats()`` and ``tick`` records are made of
(ISSUE 45).  `chipbench/layer_metrics/*.json` read both by key, and which
keys there are depends on the configuration's cache kind
(`serving/kvpool/host_cache.py`): the key sets of every family are pinned
here, as they were before the kinds' host halves had a module of their own."""

import dataclasses

import jax
import pytest

from bpe_transformer_tpu.models.config import TS_TEST_CONFIG
from bpe_transformer_tpu.models.transformer import init_params
from bpe_transformer_tpu.serving.kvpool import host_cache
from bpe_transformer_tpu.serving.server import ServingEngine
from bpe_transformer_tpu.telemetry import Telemetry
from tests import test_cohere2moe as cohere
from tests import test_evabyte as evabyte
from tests import test_granitehybrid as granite
from tests import test_longcatflash as longcat
from tests import test_nemotronh as nemotron

DENSE = dataclasses.replace(TS_TEST_CONFIG, vocab_size=64, context_length=32)


def _of(module, c, **engine):
    return module.ref.weights_from_seed(3, c), module.program_cfg(c), engine


#: ``(weights, config, engine arguments)`` of each served family at its test
#: file's small configuration, and the host half it is served through.
FAMILIES = {
    "dense": (host_cache.HostDenseRows, lambda: (
        init_params(jax.random.PRNGKey(0), DENSE), DENSE, dict(block_size=4),
    )),
    "cohere2moe": (host_cache.HostGroupedPages, lambda: _of(
        cohere, cohere.reference_cfg(2, 2, layers=4), block_size=2, prefix_cache=False,
    )),
    "longcatflash": (host_cache.HostLatentRows, lambda: _of(
        longcat, longcat.reference_cfg(4, 4), block_size=4,
    )),
    "granitehybrid": (host_cache.HostRecurrentRows, lambda: _of(
        granite, granite.reference_cfg(6, 6), block_size=4, prefix_cache=False,
    )),
    "evabyte": (host_cache.HostEvaRows, lambda: (
        evabyte.weights(3), evabyte.program_cfg(),
        dict(block_size=evabyte.CHUNK, prefill_chunk=16, prefix_cache=False),
    )),
    "nemotronh": (host_cache.HostRecurrentRows, lambda: _of(
        nemotron, nemotron.reference_cfg(), block_size=4, prefix_cache=False,
    )),
}

#: A ``tick`` record of every paged engine, and what a kind adds to it.
TICK_KEYS = {
    "admit_s", "batch", "carry_flushes", "chunks", "cpu_s", "deliver_s",
    "dispatch_s", "dur_s", "emit_s", "gc_s", "host_offcpu_s", "idle_s", "kind",
    "moe_rows_local", "moe_zero_assignments", "other_s", "overlapped",
    "prefill_s", "prefill_tokens", "queue_depth", "ssm_chunk_rows",
    "ssm_chunk_tokens", "ssm_tick_state_rows", "stale_rows", "t", "wait_s",
}
TICK_KEYS_OF = {
    "longcatflash": {"attn_shared_kv_positions", "attn_shared_slots"},
    "evabyte": {"attn_kv_positions", "attn_summary_kv_positions"},
}

#: ``stats()`` of every paged engine, and what a kind adds to it.
STATS_KEYS = {
    "active_slots", "admit_backlog", "alerts_firing", "attn_kv_positions",
    "attn_pairs", "block_size", "carry_flushes", "chunk_launches",
    "compiled_programs", "decode_roofline", "decode_seconds", "decode_tokens",
    "decode_tokens_per_sec", "engine_kind", "finish_reasons", "fused_sampling",
    "gc_collections", "gc_gen2_collections", "gc_pause_s", "import_backlog",
    "kv_blocks_free", "kv_blocks_shared", "kv_blocks_total",
    "kv_bytes_per_token", "kv_dtype", "kv_full_blocks_free",
    "kv_full_blocks_total", "kv_pool_aliased_bytes", "kv_pool_bytes",
    "kv_window_blocks_free", "kv_window_blocks_recycled",
    "kv_window_blocks_total", "launch_chunk_after_s", "launch_chunk_call_s",
    "launch_chunk_key_s", "launch_chunk_prepare_s", "launch_tick_after_s",
    "launch_tick_call_s", "launch_tick_prepare_s", "migration_bytes_in",
    "migration_bytes_out", "migrations_in", "migrations_out",
    "moe_expert_groups", "moe_relaid_layers", "moe_rows_local",
    "moe_tokens_routed", "moe_zero_assignments", "params_bytes", "phase_p50_s",
    "phase_p95_s", "prefill_bucket_work", "prefill_buckets",
    "prefill_pending_slots", "prefill_pending_tokens", "prefix_cache_hits",
    "prefix_cache_misses", "prefix_cache_nodes", "prefix_hit_rate",
    "queue_depth", "requests_finished", "requests_rejected",
    "requests_submitted", "role", "sample_topk_ticks", "sample_topp_ticks",
    "slots", "ssm_chunk_rows", "ssm_chunk_tokens", "ssm_state_bytes",
    "ssm_state_resets", "ssm_tick_state_rows", "tick_attention_path",
    "tick_live_key_share", "tick_stale_rows", "tick_temp_bytes",
    "tick_weight_bytes", "ticks", "ticks_overlapped", "tokens_emitted",
    "uptime_s", "weight_dtype", "worker_cpu_seconds", "worker_offcpu_seconds",
    "worker_phase_seconds",
}
STATS_KEYS_OF = {
    "longcatflash": {
        "attn_shared_kv_positions", "attn_shared_slots", "chunk_attn_pairs",
        "chunk_attn_kernel_pairs",
    },
    "evabyte": {
        "attn_summary_kv_positions", "eva_summary_rows", "eva_windows_closed",
        "kv_summary_blocks_used",
    },
}


def serve_once(family: str):
    """One request through ``ServingEngine(paged=True)``: the engine's host
    half, its ``stats()`` and its last ``tick`` record."""
    params, config, engine = FAMILIES[family][1]()
    records = []
    with ServingEngine(
        params, config, slots=2, min_bucket=4, paged=True,
        telemetry=Telemetry(sink=records.append), **engine,
    ) as serving:
        serving.generate((1, 2, 3, 4, 5), max_new_tokens=4, temperature=0.0)
        stats = serving.stats()
        cache = serving.engine.cache
    ticks = [r for r in records if r.get("kind") == "tick"]
    return cache, stats, ticks[-1]


@pytest.mark.parametrize("family", FAMILIES)
def test_stats_and_tick_records_keep_their_keys(family):
    cache, stats, tick = serve_once(family)
    assert type(cache) is FAMILIES[family][0]
    assert set(tick) == TICK_KEYS | TICK_KEYS_OF.get(family, set())
    assert set(stats) == STATS_KEYS | STATS_KEYS_OF.get(family, set())
