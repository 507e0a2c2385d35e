"""Test config: force an 8-device virtual CPU mesh (the reference's
subprocess-spawn distributed test pattern, SURVEY §4, maps to
xla_force_host_platform_device_count on TPU-less CI).

Set PADDLE_TPU_TESTS=1 to run on the real TPU backend instead — enables
the @pytest.mark.tpu tests (compiled-only paths like the in-kernel
dropout PRNG that have no CPU/interpret lowering). Run those in ONE
process (`-p no:xdist`): a chip belongs to one process."""

import os

if os.environ.get("PADDLE_TPU_TESTS") != "1":
    from paddle_tpu.device import force_virtual_cpu_devices

    # jax may already be imported (pytest plugins); force the CPU backend
    # before any computation initializes it. Nothing here (or in any
    # module a test imports) describes a TPU topology or loads the TPU
    # library: that happens only inside tests/test_chip_compile.py's
    # fixture, so every xdist worker collects the same tests.
    force_virtual_cpu_devices(8)

import time

import numpy as np
import pytest

_SESSION_T0 = time.time()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Wall-time accounting per tier (VERDICT r3 #10): CI output states
    what the tier actually cost, and README budgets come from here."""
    del exitstatus
    dt = time.time() - _SESSION_T0
    expr = (getattr(config.option, "markexpr", "") or "")
    tier = "fast (-m 'not slow')" if "not slow" in expr else (
        "slow-only" if expr == "slow" else "full")
    terminalreporter.write_line(
        f"[paddle_tpu] {tier} tier wall time: {dt / 60:.1f} min")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: needs the real TPU backend (PADDLE_TPU_TESTS=1)")
    config.addinivalue_line(
        "markers", "slow: heavy hybrid-engine compiles; excluded from the "
        "fast tier (pytest -m 'not slow')")


# Slow tier (VERDICT r1 #9): tests measured >= 10 s on the 8-device CPU
# mesh — almost all dominated by repeated hybrid-engine / interpret-mode
# compiles, not by the assertions. `pytest -m "not slow"` is the fast CI
# tier (< 5 min); the full suite is the nightly run (see README).
# Measured via `pytest --durations` (round 2); update when tests move.
_SLOW_TESTS = {
    "test_hybrid_curve_aligns_with_dense", "test_vpp_curve_aligns_with_dense",
    "test_zero_sharded_curve_aligns",
    "test_fused_multi_transformer_dropout_active_in_train",
    "test_fused_multi_transformer_jits_and_grads",
    "test_fused_multi_transformer_prefill_decode_parity",
    "test_ring_attention_impls_agree", "test_ring_attention_long_context_4k",
    "test_ulysses_grad_parity", "test_gpt_generate_matches_full_reforward",
    "test_llama_generate_matches_full_reforward",
    "test_hybrid_grads_match_dense", "test_hybrid_train_step_loss_decreases",
    "test_hybrid_vpp_matches_dense", "test_resnet18_fake_data_one_step",
    "test_finished_rank_not_judged_hung", "test_restart_count_env_increments",
    "test_hybrid_loss_matches_dense", "test_hybrid_vpp_train_step",
    "test_moe_ep_parity_auto_vs_shard_map",
    "test_store_barrier_cross_process", "test_vision_model_zoo_forward",
    "test_flash_attention_bias_mask", "test_flash_attention_segment_ids",
    "test_unpadded_and_flashmask_dispatch",
    "test_interleaved_pipeline_matches_sequential",
    "test_feature_layer_reference_defaults", "test_rpc_many_async",
    "test_zero_bubble_pipeline_matches_dense",
    "test_bert_pretraining_loss_decreases", "test_flash_attention_gqa",
    "test_eager_forward_shape_and_loss",
    "test_hung_worker_detected_via_heartbeat",
    "test_feature_layers_pipeline", "test_elastic_restart_recovers",
    "test_vocab_parallel_embedding", "test_hybrid_parallel_inference_helper",
    "test_flash_attention_window", "test_flash_attention_grads",
    "test_vision_model_zoo_round2_forward", "test_vision_model_zoo_inception",
    "test_fused_multi_transformer_prefill_into_cache_then_decode",
    "test_moe_layer_dense_math", "test_ring_attention_grad_parity",
    "test_eager_gpt_forward_and_fit", "test_dense_forward_matches_eager_math",
    "test_launch_two_workers_env", "test_fused_moe_matches_einsum_moe",
    # round 3
    "test_parity_pass_matches_baseline", "test_amp_pass_contract",
    "test_gradient_merge_pass_contract",
    "test_concurrent_ragged_requests_match_generate",
    "test_blocks_recycled_across_many_requests",
    "test_static_batch_baseline_matches_generate",
    "test_ring_attention_gqa_grad_parity",
    # round 4 (fast tier re-budgeted to <= 10 min: the heaviest spawns and
    # interpret-mode kernel tests move here; `pytest -m slow` is nightly)
    "test_two_process_pipeline_parity",
    "test_two_process_ring_attention_parity",
    "test_tp_sharded_decode_matches_generate",
    "test_static_batch_mixed_prompt_lengths",
    "test_flash_bias_grad_with_dropout_and_window",
    "test_flash_bias_grad_broadcast_shapes",
    "test_flash_learned_bias_grad",
    "test_streamed_matches_dense_training",
    "test_streamed_llama_matches_dense_training",
    "test_ptq_calibrated_gpt_matches_fp",
    # round 5: the heaviest new parity runs move to the slow tier — the
    # two-pass streamed-clip parity (~45 s/param, 2 params; gating stays
    # fast via test_streamed_rejects_grad_clip_and_custom_apply) and the
    # 2-process zero1 spawn (same class as the other spawn parities here)
    "test_streamed_clip_matches_dense_clip",
    "test_two_process_zero1_parity",
    # round 6: heavy ragged-serving engine matrices (each engine build
    # recompiles the interpret-mode unified program). The fast tier
    # keeps the acceptance gates: one-dispatch contract, flags-off
    # bitwise, kernel parity, int8-KV capacity/determinism, the
    # serving_bench CPU smoke, pool-pressure scheduling, and the slim
    # TP-int8 parity smoke.
    "test_tp_int8_kv_pool",
    "test_tp_ragged_matches_generate",
    "test_fp8_kv_pool_runs",
    "test_page_scale_reset_on_block_reuse",
    "test_adaptive_mix_shortens_bursts_under_pressure",
    "test_tp_int8_weights_match_dense_int8_exactly",
    "test_int8_kv_outputs_close_to_float",
    # round 7: elastic-reshard hybrid-engine legs — each builds 2-3 hybrid
    # engines (compile-dominated); the fast tier keeps the pure-checkpoint
    # reshard/carry/fault/CLI coverage and the driver-level elastic resume
    "test_elastic_hybrid_pp_shrink_bitwise",
    "test_elastic_hybrid_zero1_on_to_off_bitwise",
    "test_elastic_hybrid_issue_pair_dp_regroup",
    "test_elastic_hybrid_fp8_carries_rescaled",
    "test_two_process_elastic_restart",
    "test_reshard_1b_checkpoint_throughput",
    # round 8: serving-resilience heavy — the wall-clock overload/SLO
    # acceptance (open-loop arrival schedule, ~30 s of timed waves). The
    # fast tier keeps the deterministic deadline/shed/preempt/replay
    # coverage and the kill-and-replay spawn.
    "test_overload_shedding_preserves_admitted_slo",
    # round 9: ZeRO-stage heavies — the 50-step zero3 acceptance curve,
    # the 4-leg heavy compose matrix (ring/vpp/overlap/moe — each builds
    # 2 hybrid engines) and the cross-mesh quantized-AG carry reset
    # (4 more engine builds). The fast tier keeps the 4-step parity
    # gates, the refusals, flags-off bitwise, the EF primitive, the
    # planner rules and the stage-transition resumes.
    "test_zero3_acceptance_50_steps",
    "test_zero3_compose_slow",
    "test_resume_quantized_zero3_resets_ef_carry",
    "test_two_process_zero3_parity",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.split("[")[0] in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    flags_before = dict(paddle.get_flags())
    yield
    # restore only flags a test changed and forgot to reset (set_flags runs
    # on_set hooks, so a wholesale rewrite would be wasted work)
    flags_after = paddle.get_flags()
    changed = {k: v for k, v in flags_before.items()
               if flags_after.get(k) != v}
    if changed:
        paddle.set_flags(changed)
    # fleet.init / set_hybrid_communicate_group is process-global by design
    # (reference semantics: one fleet per trainer process — the reference
    # isolates by spawning a subprocess per scenario, test_dist_base.py:954);
    # in-process tests must fully reset it, STRATEGY INCLUDED: a leaked
    # fp16_allreduce=True flips every later grad_reduce_dtype="auto" engine
    # to bf16 reductions and breaks 1e-5 parity tolerances.
    from paddle_tpu.distributed.fleet.fleet import fleet as _fleet
    _fleet.reset()
