"""Test harness: force an 8-device virtual CPU mesh.

The reference tests multi-node behavior by spawning many OS processes on one
box (SURVEY.md §4.5); the TPU-native analogue is many virtual XLA CPU devices
in one process. Must run before any jax backend initialization.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent XLA compile cache (utils/metrics.enable_compile_cache): the
# suite is compile-bound — the heavy engine programs (DARTS supernets,
# scanned round blocks) dominate wall clock, and a repeat run (CI re-verify,
# local iteration) should pay them once, not every time
from fedml_tpu.utils.metrics import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "smoke: fast tier — every engine's oracle at minimal shapes, "
        "<5 min total on a 1-core box (scripts/ci.sh default; run the "
        "full suite with scripts/ci.sh full or plain pytest)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection soak tier — many seeded FaultPlans over "
        "full federated runs (scripts/chaos_soak.py). Marked slow too, so "
        "tier-1 ('-m not slow') excludes it; run with -m chaos")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 budget ('-m not slow')")


# The smoke tier, kept as ONE auditable list instead of decorators
# scattered over 30 files. Selection rule: the cheapest test that proves
# each engine/subsystem's ORACLE (usually an ≡ equivalence), not its
# broadest coverage — durations from the round-3 full-suite run
# (236 tests, 25m51s on 1 core); this subset sums to ~3.5 min there.
_SMOKE_TESTS = {
    # core FedAvg engine + data planes
    "test_fedavg.py::test_fedavg_full_participation_equals_centralized",
    "test_fedavg.py::test_standalone_equals_distributed",
    "test_fedavg.py::test_device_data_plane_matches_host_pack",
    "test_fedavg.py::test_run_rounds_working_set_equals_full_park",
    # algorithm engines (each ≡ its reduction oracle)
    "test_algorithms.py::test_fedopt_sgd_lr1_equals_fedavg",
    "test_algorithms.py::test_fedprox_mu0_equals_fedavg",
    "test_algorithms.py::test_fednova_uniform_tau_equals_fedavg",
    "test_algorithms.py::test_robust_clipping_bounds_update",
    "test_algorithms.py::test_hierarchical_one_group_equals_flat",
    "test_algorithms.py::test_dsgd_shard_map_matches_vmap",
    "test_distillation.py::test_feddf_learns",
    "test_distillation.py::test_feddf_hard_variant_runs",
    "test_fedseg.py::test_fedseg_learns_blobs",
    "test_nas_derived.py::test_genotype_extraction",
    "test_nas_darts_search.py::test_fednas_heldout_split_is_disjoint",
    "test_affinity_condense.py::test_fedcon_trains_on_condensed_union",
    "test_affinity_condense.py::test_affinity_matrix_properties",
    "test_augment_poison.py::test_backdoor_attack_and_clipping_defense",
    "test_augment_poison.py::test_edge_case_pickle_reader_southwest_format",
    # cross-process runtimes ≡ in-process engines
    "test_comm.py::test_distributed_loopback_equals_standalone",
    "test_comm.py::test_elastic_partial_aggregation_survives_dead_client",
    "test_distributed_variants.py::test_distributed_fedgkt_equals_inprocess",
    "test_distributed_variants.py::test_distributed_splitnn_equals_inprocess",
    "test_distributed_variants.py::test_distributed_vfl_equals_inprocess",
    "test_distributed_variants.py::test_distributed_turboaggregate_secure_matches_plain",
    "test_collectives.py::test_shamir_encode_decode",
    # parallelism strategies (sp/tp/ep/pp/federated-tp + kernels)
    "test_fedavg_seq.py::test_seq_parallel_fedavg_equals_single_device",
    "test_tensor_parallel.py::test_tp_training_equals_single_device",
    "test_tensor_parallel.py::test_ep_moe_training_equals_single_device",
    "test_tensor_parallel.py::test_federated_tensor_parallel_equals_single_device",
    "test_tensor_parallel.py::test_attention_core_stays_sharded",
    "test_pipeline_parallel.py::test_gpipe_equals_sequential_forward_and_grad",
    "test_ring_attention.py::test_ring_attention_matches_full",
    "test_ring_attention.py::test_ulysses_matches_full",
    "test_flash_attention.py::test_flash_gradients_match_dense",
    "test_flash_attention.py::test_flash_gradients_under_strict_vma_shard_map",
    "test_sync_bn.py::test_sync_bn_equals_global_batch_bn",
    # round-3 additions: wire codec, sparse uplink, async ckpt, DP.
    # (bf16-resnet / CLI-attack knob tests stay full-tier: their oracles —
    # model forward, backdoor flow — are covered above, and the smoke
    # budget is a hard <5 min)
    "test_comm.py::test_wire_codecs_roundtrip_and_shrink",
    "test_comm.py::test_topk_sparse_encode_decode_conservation",
    "test_comm.py::test_sparse_uplink_ratio1_equals_dense_protocol",
    "test_privacy.py::test_q1_reduces_to_gaussian",
    "test_privacy.py::test_dp_forces_uniform_average",
    "test_infra.py::test_async_checkpointer_equals_sync",
    # telemetry: the round-record schema + comm accounting oracle
    "test_obs.py::test_loopback_run_emits_full_round_schema",
    # infra: checkpoint/CLI/tracing/packer/partition/data/params
    "test_infra.py::test_checkpoint_roundtrip",
    "test_infra.py::test_cli_build_api_all_algos",
    "test_tracing.py::test_engine_populates_tracer",
    "test_native_packer.py::test_native_matches_numpy_exactly",
    "test_partition.py::test_dirichlet_partition_properties",
    "test_data_extras.py::test_synthetic_leaf_exact_split_reconstruction",
    "test_param_parity.py::test_cnn_original_fedavg_param_counts",
    # round-6 additions: pipelined round execution (docs/PERFORMANCE.md) —
    # the prefetch-on ≡ prefetch-off identity AND the overlap oracle
    "test_round_pipeline.py::test_prefetch_on_equals_off_per_round",
    "test_round_pipeline.py::test_round_r_plus_1_transfer_before_round_r_drain",
    "test_round_pipeline.py::test_warmup_compiles_all_bucket_variants",
    # round-7 additions: mesh-sharded server state (docs/PERFORMANCE.md
    # §Partitioned server state) — the sharded ≡ replicated identity and
    # the rule-table matcher contract
    "test_sharded_agg.py::test_sharded_equals_replicated_per_round",
    "test_sharded_agg.py::test_rule_precedence_first_match_wins",
    # round-8 additions: buffered asynchronous rounds (docs/ROBUSTNESS.md
    # §Asynchronous buffered rounds) — the K=cohort/bound-0 ≡ sync
    # identity and the deterministic async-beats-the-barrier claim
    "test_async_buffer.py::test_async_k_cohort_bound0_bitwise_equals_sync",
    "test_async_buffer.py::test_async_straggler_beats_sync_barrier_virtual_clock",
    # round-11 additions: million-client data plane (docs/PERFORMANCE.md
    # §Streaming & cohort bucketing; docs/ROBUSTNESS.md §Hierarchical
    # tiers) — streamed ≡ materialized, bucketing on ≡ off, and the
    # 2-tier tree ≡ flat pairwise identity
    "test_streaming.py::test_streamed_engine_bitwise_equals_materialized",
    "test_streaming.py::test_bucketing_on_equals_off_per_round_and_pipelined",
    "test_hierarchy_tiers.py::test_pairwise_sum_block_composition_property",
    "test_hierarchy_tiers.py::test_tree_equals_flat_loopback_bitwise",
    # round-12 addition: the fedlint static gate (docs/ANALYSIS.md) — the
    # live tree stays clean modulo the committed annotated baseline
    "test_fedlint.py::test_live_tree_clean_modulo_baseline",
}


def pytest_collection_modifyitems(config, items):
    seen, files = set(), set()
    for item in items:
        base = item.nodeid.split("/")[-1].split("[")[0]
        seen.add(base)
        files.add(base.split("::")[0])
        if base in _SMOKE_TESTS:
            item.add_marker(pytest.mark.smoke)
    # a renamed test must not silently shrink the smoke gate: if a smoke
    # entry's FILE was collected but the entry matched nothing, fail loudly
    # (skipped under -k/node selection, where partial collection is normal)
    selective = bool(config.getoption("keyword", "")) or \
        any("::" in a for a in config.args)
    stale = {t for t in _SMOKE_TESTS
             if t not in seen and t.split("::")[0] in files}
    if stale and not selective:
        raise pytest.UsageError(
            "_SMOKE_TESTS entries match no collected test (renamed or "
            f"removed?): {sorted(stale)}")
    # longest files first. Under `-n N --dist loadfile` a file is one work
    # unit, handed out in collection order, and a long one queued
    # mid-alphabet sets the wall clock by when it starts (the DARTS
    # supernet tests, one 12-minute file until PR 31 split it, pushed a
    # cold six-worker run to its 1470 s limit).
    first = ("test_nas_darts_search.py", "test_nas_gdas_search.py")
    items.sort(key=lambda it: not it.nodeid.split("::")[0].endswith(first))


@pytest.fixture(scope="session")
def mesh8():
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return Mesh(np.asarray(devs[:8]), ("clients",))


@pytest.fixture
def nas_setup():
    """The FedNAS search tests' tiny job: ``nas_setup(seed=0, **api_kw)``
    gives (data, FedNASAPI) on two clients of 12x12 images (shared by
    test_nas_darts_search.py, test_nas_gdas_search.py, test_nas_derived.py)."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.algorithms.fednas import FedNASAPI
    from fedml_tpu.data.synthetic import synthetic_images

    def make(seed=0, **api_kw):
        data = synthetic_images(num_clients=2, image_shape=(12, 12, 3),
                                num_classes=3, samples_per_client=16,
                                test_samples=24, seed=seed,
                                size_lognormal=False)
        cfg = FedAvgConfig(comm_round=2, client_num_in_total=2,
                           client_num_per_round=2, epochs=1, batch_size=4,
                           lr=0.02, seed=seed)
        return data, FedNASAPI(data, cfg, layers=2, init_filters=8,
                               arch_lr=3e-3, **api_kw)

    return make
