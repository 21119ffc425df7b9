"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (multi-host TPU topology is
simulated the way the reference simulates multi-node clusters with in-process
fixtures — SURVEY.md §4 "lesson"). Must be set before jax is imported
anywhere in the process; worker subprocesses inherit the env and therefore
also stay off the real TPU.
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Arm the lock-order witness for the whole tier-1 run (and, via env
# inheritance, every worker subprocess the tests spawn); the session
# fixture at the bottom fails the run if any acquisition-order cycle
# (potential deadlock) was observed. Opt out with
# RAY_TPU_LOCK_WITNESS=0.
os.environ.setdefault("RAY_TPU_LOCK_WITNESS", "1")

# The CPU pins, before anything imports jax: tests never use a chip
# (that is chip_smoke.py's job, through the chip tool), and a test
# process that reached for one would take it from whoever holds it.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if "host_platform_device_count" not in f]
    + ["--xla_force_host_platform_device_count=8"])

import pytest  # noqa: E402


def pytest_configure(config):
    import jax

    assert jax.device_count() == 8 and jax.devices()[0].platform == "cpu", (
        "tests require the virtual 8-device CPU mesh, got "
        f"{jax.devices()}"
    )

    # Native artifacts are not committed (ADVICE r3): build them from
    # src/ before any test imports a ctypes loader.
    from ray_tpu._private.native_build import ensure_native

    ensure_native()

    # xdist hands files out in the order ``_tier_order`` leaves them in,
    # not by its own count of cases (the ``dest`` of its
    # ``--no-loadscope-reorder``; without xdist nothing reads it).
    config.option.loadscopereorder = False


# --- test tiers (reference: Bazel size/team tags,
# python/ray/tests/BUILD:21-92). Whole modules land in a tier here;
# individual tests can still carry @pytest.mark.slow/chaos/scale inline.
# Everything not in a slower tier is `fast`. Tier-1 is `-m 'not slow'`
# in the driver's form (`/root/TESTS_LAST_RUN.json` `commands`): six
# xdist workers, `--dist loadfile`, a 1,470-s limit. A whole run takes
# most of it (ROADMAP C11 (c) has the seconds: cut at the limit at PR 54,
# back under it since PR 55 by `_FIRST_FILES` below and by
# `tests/_small_models.py`), so a new file of tests is measured there.

_CHAOS_MODULES = {
    "test_stress",
}
_SLOW_MODULES: set = set()

# What stays slow: a test that takes over ~20 s alone, or needs more
# than the CPU box has. Most entries below were marked by an older
# rule (>= ~4 s, for a 300-s serial run) and have not been measured
# against this one: unmark a component's tests after three clean runs
# of its files in the driver's form. Under loadfile a file runs on ONE
# worker, so a file's total is what bounds the run, not the sum. A test
# that passes in one run and fails in another stays marked and is named
# in ROADMAP C11. test_core::test_simple_task is deliberately NOT here:
# its time is one-time cluster warmup (native build + worker jax
# imports) that whichever test runs first would pay anyway, and it is
# the canary.
_SLOW_TESTS = {
    "test_graft_entry::test_dryrun_multichip_8",
    "test_train_elastic::test_elastic_restart_shrinks_world",
    "test_streaming_generators::test_error_mid_stream",
    "test_core::test_actor_handle_passing",
    "test_train_integrations::test_tensorflow_trainer_multiworker",
    "test_rllib_dreamerv3::test_dreamerv3_trains_and_losses_improve",
    "test_data::test_from_tf",
    "test_train_integrations::test_transformers_report_callback",
    "test_train_torch::test_torch_trainer_ddp_converges_and_syncs",
    "test_dashboard_data::test_dashboard_memory_profiler",
    "test_rllib::test_algorithm_is_tune_trainable",
    "test_rllib::test_ppo_remote_env_runners",
    "test_rllib_offline::test_cql_learns_expert_policy_offline",
    "test_rllib::test_impala_trains_with_async_runners",
    "test_rllib_algos::test_appo_runs_cartpole",
    "test_rllib_dreamerv3::test_dreamerv3_checkpoint_roundtrip",
    "test_train::test_trainer_dp_two_workers_loss_drops",
    "test_llm_e2e::test_openai_http_endpoints",
    "test_multislice::test_hierarchical_train_step_2x4",
    "test_doc_examples::test_doc_example_runs[llm_quickstart.py]",
    "test_doc_examples::test_doc_example_runs[train_torch_quickstart.py]",
    "test_llm_sampling::test_serving_n_and_best_of",
    "test_doc_examples::test_doc_example_runs[rllib_quickstart.py]",
    "test_head_ft::test_kill_head_restart_recovers",
    "test_llm_sampling::test_batched_prefill_matches_sequential",
    "test_llm::test_single_request_roundtrip",
    "test_llm_spec::TestSpeculativeDecoding::test_smaller_draft_architecture",
    "test_fault_tolerance::test_reconstruction_cap",
    "test_rllib_offline::test_cql_checkpoint_restores_targets_and_bc_counter",
    "test_dashboard_data::test_dashboard_sampling_profiler",
    "test_device_channel::test_device_edge_between_actors",
    "test_llm::test_tp2_decode_matches_tp1",
    "test_jax_distributed::test_two_process_jax_cluster",
    "test_rllib_algos::test_sac_runs_pendulum",
    "test_doc_examples::test_doc_example_runs[device_channel_pipeline.py]",
    "test_device_channel::test_device_edge_repeated_executions",
    "test_tune::test_asha_stops_bad_trials",
    "test_tune::test_pbt_synch_exploits_better_config",
    "test_rllib_multi_agent::test_multi_agent_ppo_learns_signal_match",
    "test_jax_distributed::test_jax_trainer_distributed_on",
    "test_head_ft::test_external_store_head_ha",
    "test_rllib::test_ppo_learns_cartpole",
    "test_device_channel::test_device_edge_pytree_and_driver_read",
    "test_llm_spec::TestSpeculativeDecoding::test_near_cache_capacity",
    "test_llm_spec::TestSpeculativeDecoding::"
    "test_perfect_draft_matches_and_accelerates",
    "test_llm::test_pp2_decode_matches_pp1",
    "test_core::test_out_of_order_actor_execution",
    "test_multinode::test_node_label_scheduling",
    "test_llm_e2e::test_batch_inference_over_dataset",
    "test_cpp_api::test_cpp_frontend_builds_and_runs",
    # 2-4 s band (same measurement run):
    "test_tune_hyperband::test_hyperband_prunes_to_best",
    "test_llm_prefix::TestChunkedPrefill::test_llama_arch_rope_offsets",
    "test_llm::test_continuous_batching_staggered_admission",
    "test_llm_lora::test_adapter_changes_output_base_unaffected",
    "test_refcount_borrowing::test_ref_in_actor_state_outlives_passing_task",
    "test_tune::test_max_concurrent_trials_and_time_fields",
    "test_llm_prefix::TestChunkedPrefill::test_matches_whole_prompt_prefill",
    "test_ownership::test_result_lands_in_owner_store",
    "test_llm::test_greedy_matches_reference_generate",
    "test_async_actors::test_cancel_queued_actor_call",
    "test_refcount_borrowing::test_ref_returned_inside_container",
    "test_fault_tolerance::test_reconstruction_is_transparent_to_wait",
    "test_ownership::test_dependent_task_fetches_from_owner",
    "test_ownership::test_fire_and_forget_then_dependent",
    "test_rllib::test_env_runner_batch_layout",
    "test_llm_prefix::TestChunkedPrefill::test_near_cache_capacity",
    "test_refcount_borrowing::test_borrow_churn_stress",
    "test_multinode::test_p2p_object_transfer_bypasses_head",
    "test_tune::test_tuner_function_trainable",
    "test_multinode::test_node_death_fails_over",
    "test_runtime_env::test_conda_lite_venv_isolated_version",
    "test_refcount_borrowing::test_owner_death_with_live_borrowers",
    "test_ownership::test_error_results_via_owner_plane",
    "test_cli_job_serve::test_serve_deploy_status_shutdown",
    "test_rllib_offline::test_marwil_beats_bc_on_mixed_data",
    "test_rllib_connectors::test_ppo_with_connectors_learns",
    "test_ownership::test_big_results_take_store_path",
    "test_rllib::test_rl_module_forward_and_weights",
    "test_channels::test_compiled_dag_function_node_falls_back",
    "test_head_ft::test_head_restart_readopts_node_agent",
    "test_collective::test_broadcast_slow_joiner",
    "test_refcount_borrowing::test_nested_arg_ref_survives_fire_and_forget",
    "test_rllib::test_compute_single_action_after_training",
    "test_llm::test_default_config_works_with_byte_tokenizer",
    "test_dashboard_data::test_from_huggingface_roundtrip",
    "test_rllib::test_evaluate_and_evaluation_interval",
    "test_rllib::test_ppo_checkpoint_roundtrip",
    "test_rllib_multi_agent::test_multi_agent_shared_policy_and_checkpoint",
    "test_rllib_algos::test_dqn_learns_cartpole",
    "test_rllib_offline::test_marwil_beta_zero_is_bc",
    "test_review_regressions::test_pipelined_nested_get_no_deadlock",
    "test_rllib_dreamerv3::test_symlog_twohot_roundtrip",
    "test_zero_copy::test_nested_and_multiple_arrays_share_one_pin",
    "test_train_torch::test_torch_trainer_single_worker_no_pg",
    "test_llm_prefix::TestPrefixCache::test_multi_slot_interleaving",
    "test_llm_prefix::TestPrefixCache::test_shared_prefix_divergent_tail",
    "test_serve::test_autoscaling_scales_up_under_load",
    "test_doc_examples::test_doc_example_runs[serve_quickstart.py]",
    "test_doc_examples::test_doc_example_runs[tune_quickstart.py]",
    "test_core::test_duplicate_pending_dep_runs_once",
    "test_cpp_client::test_malformed_path_func_id_errors",
    "test_util_bridges::test_pool_map_and_starmap",
}


# The files handed out FIRST: a file of under ten cases that holds a
# case of over ~20 s which tier-1 keeps, so that its count of cases
# says nothing of its length. Under loadfile a file is one worker's,
# and by count such a file starts last and ends the run alone while
# five workers stand idle (the rehearsal, 8 cases and ~650 s, started
# at 811 s of 1,470: ROADMAP C11 (c)). Paths under tests/, the longest
# first (seconds of the whole run in CHANGES.md, PR 55); every other
# file goes out behind them by descending count of cases, as xdist
# itself would order them: a long file of many cases starts early by
# that (`test_kda_layout.py`, `test_smallthinker.py`), and the files of
# a few short cases fill the end. One worker a first file at the
# start: six at most.
_FIRST_FILES = (
    "chipbench/test_chipbench_rehearsal.py",
    "chipbench/test_chipbench_drivers_cpu.py",
    "test_trinity_mini_kernels.py",
    "chipbench/test_chipbench_correct.py",
    "test_chip_smoke.py",
)

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _tier_order(items):
    """``items`` sorted stably by file: the ``_FIRST_FILES`` in their
    order, then every other file by descending count of cases (files of
    one count, and a file's cases, stay as collected)."""
    files = [os.path.relpath(str(item.path), _TESTS_DIR).replace(os.sep, "/")
             for item in items]
    counts = collections.Counter(files)
    rank = {name: i for i, name in enumerate(_FIRST_FILES)}
    keyed = sorted(zip(files, items), key=lambda pair: (
        rank.get(pair[0], len(rank)), -counts[pair[0]]))
    return [item for _, item in keyed]


@pytest.hookimpl(wrapper=True)
def pytest_collection_modifyitems(config, items):
    """Places the tiers' markers before ``-m`` reads them, and orders what
    ``-m`` and ``-k`` left (``_tier_order``) after."""
    _mark_tiers(items)
    yield
    items[:] = _tier_order(items)


def _mark_tiers(items):
    for item in items:
        mod = item.module.__name__.rpartition(".")[2]
        if mod in _CHAOS_MODULES:
            item.add_marker(pytest.mark.chaos)
        elif mod in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        else:
            # item.name carries parametrization ([gpt2]); class-scoped
            # tests join as Class::name to match the set's keys.
            cls = getattr(item, "cls", None)
            key = (f"{mod}::{cls.__name__}::{item.name}" if cls
                   else f"{mod}::{item.name}")
            if key in _SLOW_TESTS:
                item.add_marker(pytest.mark.slow)
        if not any(m.name in ("slow", "chaos", "scale")
                   for m in item.iter_markers()):
            item.add_marker(pytest.mark.fast)


@pytest.fixture(scope="session", autouse=True)
def _lock_witness_gate():
    """Fail the session if the armed lock witness saw an acquisition-
    order cycle anywhere in the run — a potential deadlock even if the
    wedging interleaving never fired (docs/INVARIANTS.md, RT-L003's
    dynamic complement)."""
    yield
    from ray_tpu._private import lockwitness

    if lockwitness.installed() and lockwitness.cycles():
        raise AssertionError(lockwitness.report())


@pytest.fixture
def ray_start():
    """Fresh single-node cluster per test (reference analogue:
    ray_start_regular in python/ray/tests/conftest.py:580)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    """Shared cluster for cheap read-only tests."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def two_backward_kernels(monkeypatch):
    """Inside: no head's dq fits in VMEM by ``ops/attention.py``'s tile
    rule, so the Pallas attention backward takes its two-kernel form ("dq"
    and "dkv") whatever the shapes: what the one-kernel form (PR 38) is
    held against."""
    import importlib

    attention = importlib.import_module("ray_tpu.ops.attention")
    tiles = attention._flash_tiles
    monkeypatch.setattr(
        attention, "_flash_tiles", lambda kernel, *a, **kw: (
            None if kernel == "bwd" else tiles(kernel, *a, **kw)))
