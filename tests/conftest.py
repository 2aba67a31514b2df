"""Test harness: a virtual 8-device CPU mesh, no TPU required.

The reference's tests spawn real NCCL processes on >=2 physical GPUs
(`mp.spawn` in each `tests/*.py` `__main__`; SURVEY §4 — there are no
cluster-free tests at all). JAX makes distributed testing cheap: we force the
host platform to expose 8 virtual CPU devices and every sharding/collective
path runs in-process.

The platform is pinned to the CPU here, explicitly, whatever the machine
has: these tests ask for the CPU path (and, where a Pallas kernel is under
test, for the interpreter) by name. The chip has its own command,
chip_smoke.py.
"""

import os

# Must be set before the first XLA CPU client is created.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# The CPU's `triangular_solve` (the chunked delta rule, ops/delta_rule.py) is
# a LAPACK call into OpenBLAS, whose worker threads spin: six test workers
# side by side made one 64 x 64 solve take 217 ms where it takes 0.16 alone
# (PR 35). One thread a process, set before the library loads.
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def flash_bwd_calls():
    """`calls(fn, q, k, v)`: the flash backward's `pallas_call`s in the
    jaxpr of grad(sum(fn(q, k, v))), in order, as (name, the buffer count
    each of its blocks names: None where it leaves the pipeline its two)."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"], [
                    m.pipeline_mode and m.pipeline_mode.buffer_count
                    for m in e.params["grid_mapping"].block_mappings]
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    def calls(fn, q, k, v):
        grad = jax.grad(lambda *a: fn(*a).astype("float32").sum(), (0, 1, 2))
        return [c for c in walk(jax.make_jaxpr(grad)(q, k, v).jaxpr)
                if c[0].startswith("flash_bwd")]
    return calls


@pytest.fixture(scope="session")
def cell_step_bytes():
    """`parts(cell)`: (the model of a benchmark cell, rung -> its
    `training/memory.step_bytes`) at the shapes ONE device of the cell
    traces, the model built by the benchmark's own `families/<family>.py`
    from the cell's two files. Nothing is compiled."""
    import functools
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.lib.cells import load_cell
    from benchmark.lib.files import load_module
    from distributed_pytorch_from_scratch_tpu.training import memory

    @functools.lru_cache(maxsize=None)
    def parts(cell):
        workload, config = load_cell(cell)
        mesh = workload.get("mesh", {})
        tp, dp = mesh.get("tp", 1), mesh.get("dp", 1)
        model = load_module("families", config["family"]).build(
            config, mesh, workload["dtype"]).model
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
        # (a family whose head reads half the rows makes two of a token)
        rows = int(workload["seqlen"] / model.head_rows_share)
        return model, memory.traced_step_bytes(
            model, count(shapes) // tp,
            sum(count(shapes[k]) for k in model._layer_keys) // tp,
            int(workload["batch"]) // dp, rows)
    return parts


@pytest.fixture(scope="session", autouse=True)
def _check_devices():
    assert jax.device_count() >= 8, (
        f"expected 8 virtual CPU devices, got {jax.device_count()}"
    )
    yield


# --- the `core` lane (VERDICT r4 #7: default loop < 5 min on a 1-core box)
#
# One curated representative per parallelism axis / feature, selected from
# measured durations (the full "not slow" lane is ~32 min on the build box;
# this list sums to ~4 min including session setup). Subprocess-harness and
# sweep files (multihost/preemption/wide-mesh/e2e/bench) are deliberately
# NOT represented — they live in the slow/fast lanes. Maintained centrally
# here instead of per-file markers so the budget is auditable in one place.
# An entry is either a whole file ("test_x.py": None) or a list of test-name
# prefixes (parametrized ids match by prefix).
CORE_LANE = {
    # foundations: comm ops + parallel layers + preflight (always run whole)
    "test_collectives.py": None,
    "test_parallel_layers.py": None,
    "test_staged_session.py": None,
    "test_interop_ckpt.py": None,
    "test_optim.py": None,
    "test_prefetch.py": None,
    "test_native_data.py": None,
    # one representative per axis/feature
    "test_transformer_equivalence.py": [
        "test_loss_and_grads_match[1-4-vocab_parallel]",
        "test_forward_logits_match[2-4]",
    ],
    "test_pipeline.py": ["test_loss_logits_grads_match_single_device[pp2-"],
    "test_moe.py": ["test_model_loss_logits_grads_match_single_device[ep2-"],
    "test_ring_attention.py": ["test_ring_forward_matches_dense[2-1]",
                               "test_grads_match_dense[ring]"],
    "test_flash_attention.py": ["test_forward_matches_oracle_bf16",
                                "test_gradients_match_oracle"],
    "test_gqa.py": ["test_gqa_matches_vanilla[2-1]"],
    "test_gpt2_model.py": ["test_forward_logits_match_vanilla"],
    "test_kv_decode.py": ["test_kv_matches_nocache[0-prompt0-1]",
                          "TestContextParallelDecode::"
                          "test_cp_decode_matches_cp1[2-1]"],
    # serving: the continuous-batching token-identity anchor (tp=2 covers
    # the tp=1 lowering modulo collectives), the pure-host scheduler
    # properties, and the serve CLI smoke (the chip-less-image rot guard)
    "test_serving.py": ["test_engine_matches_greedy_decoder[2]",
                        "test_scheduler_fifo_bucket_groups",
                        "test_scheduler_backpressure_and_validation",
                        "test_serve_dry_run_smoke"],
    # serving v2 (paged): the paged-vs-slot-vs-greedy identity anchor at
    # tp=2, COW sharing + refcount drain, the chunked-prefill stall bound,
    # the equal-HBM capacity win (both ISSUE 6 acceptance criteria), the
    # pure-host SLO scheduler laws, and the --paged CLI rot guard
    "test_serving_paged.py": [
        "test_paged_matches_slot_and_greedy[2-8]",
        "test_cow_shared_prefix_identity_and_drain",
        "test_chunked_vs_whole_prefill_identity_and_stall_bound",
        "test_capacity_win_at_equal_hbm",
        "test_interleaved_prefill_no_stale_row_scribble",
        "test_slo_scheduler_class_ordering_and_fairness",
        "test_paged_serve_dry_run_smoke",
    ],
    # speculative decoding (ISSUE 7): the greedy token-identity anchor at
    # tp=2 with a disagreeing drafter, the all-accept page-boundary case,
    # the fused-vs-host sampler pin (the bugfix satellite), the config
    # refusals, and the --speculate CLI rot guard; the chi-square
    # distribution test runs in the default lane but not core (~16 s)
    "test_speculative.py": [
        "test_spec_matches_paged_and_greedy[2-2-8]",
        "test_spec_acceptance_boundary_at_page_boundary[7]",
        "test_host_sampler_matches_fused[paged]",
        "test_spec_refuses_invalid_configs",
        "test_spec_serve_dry_run_smoke",
    ],
    # paged-attention kernel (ISSUE 14): the block-level oracle (decode +
    # int8 chunk), the engine token-identity anchor at tp=2 (native +
    # int8 fused dequant), the CPU fallback warning + the CLI scope
    # refusal, the gather-copy pricing pin, and the pallas dry-run rot
    # guard; the full family/GQA/speculative/preempt matrix runs in the
    # default lane
    "test_paged_kernel.py": [
        "test_kernel_decode_matches_dense_oracle[8-2-4]",
        "test_kernel_chunk_matches_dense_oracle[True]",
        "test_pallas_matches_gather_greedy[2-8]",
        "test_pallas_matches_gather_int8_kv[2]",
        "test_pallas_without_interpreter_is_an_error_off_tpu",
        "test_serve_cli_refuses_paged_attn_without_paged",
        "test_paged_decode_hbm_bytes_drops_gather_copy",
        "test_serve_cli_refuses_pallas_off_tpu",
    ],
    # quantized wires + caches (ISSUE 8): the shared-rule round-trip
    # oracles, the int8 DP-wire error pin (the bf16 canary's sibling),
    # one ring_q kernel bound, the int8-KV greedy-quality pin + the
    # equal-HBM capacity criterion, the CLI scope refusals, and the
    # int8 serve dry-run rot guard
    "test_quant.py": [
        "test_quantize_roundtrip_oracles",
        "test_bucketed_reduce_int8_wire_tolerance",
        "test_ring_q_kernels_match_oracles_within_bound[2]",
        "test_int8_kv_greedy_pin[1]",
        "test_int8_kv_capacity_win_at_equal_hbm",
        "test_ring_q_refusals",
        "test_quant_serve_dry_run_smoke",
    ],
    "test_sequence_parallel.py": ["test_model_sp_matches_vanilla[1-1-4]"],
    "test_overlap.py": ["test_ag_matmul_matches_gather_dot_oracle[1-2]",
                        "test_matmul_rs_matches_dot_scatter_oracle[2]",
                        "test_model_ring_overlap_matches_monolithic"
                        "[llama-2]",
                        "test_bucketed_reduce_matches_whole_tree_psum"
                        "[8-1-1-False]"],
    # the ZeRO ladder (ISSUE 9): the stage-1 layout pin, the stage-2
    # reduce-scatter value-parity acceptance pin, and the stage-3
    # gather-on-demand trajectory pin
    "test_zero.py": ["test_moments_are_dp_sharded",
                     "test_zero2_grads_match_whole_tree_reducer",
                     "test_zero3_loss_trajectory_matches_zero1"],
    "test_multi_step.py": ["test_cli_steps_per_dispatch_matches"],
    "test_grad_accum.py": ["test_accum_matches_concatenated_batch[1-1]"],
    "test_checkpoint.py": ["test_save_load_roundtrip"],
    "test_cli_help.py": ["test_help_renders[target0]"],
    "test_run_step.py": ["test_failure_records_real_rc_and_stderr_tail"],
    "test_session_shell.py": [
        "test_bench_line_failure_removes_artifact_and_records_rc"],
    "test_data_pipeline.py": ["test_collate_semantics",
                              "test_token_json_schema",
                              "test_reference_shipped_tokenizer_loads"],
    # graftcheck (ISSUE 11): every rule's positive + negative fixture pin
    # and the clean-repo gate — the contract every future PR inherits.
    # The trace contracts stay in the default lane (they pay compiles).
    "test_graftcheck.py": [
        "test_bad_fixture_triggers_exactly_its_rule[",
        "test_good_fixture_stays_clean[",
        "test_rule_count_meets_acceptance_floor",
        "test_repo_sweep_is_clean",
    ],
    # obs: cheap unit coverage of every component; the train-run smoke
    # stays in the fast lane (it costs a full compile)
    "test_profiler_trace.py": None,
    "test_obs.py": ["test_tracer_emits_valid_chrome_trace",
                    "test_goodput_buckets_sum_to_wall",
                    "test_sentinel_nan_halts_with_dump",
                    "test_watchdog_detects_stall_and_recovery",
                    "test_parse_collectives_counts_and_bytes"],
    # obs v2 (ISSUE 10): the contiguous-timeline acceptance pin (one tiny
    # compile), the flight ring bound + PoolExhausted dump pin, the
    # regression-gate trio, the schema-drift guard, the rank-skew unit,
    # and the traced-serve CLI rot guard
    # obs v3 (ISSUE 12): the exporter endpoint + busy-port refusal, the
    # rotation chain + torn-line resync (the collector's correctness
    # core), fleet rollup math vs hand computation, the cross-process
    # waterfall acceptance pin, the anomaly->profiler cross-link, and the
    # telemetry serve CLI rot guard; the train smoke (slow lane) and the
    # overhead pin (timing-sensitive) stay out of core
    "test_telemetry.py": [
        "test_exporter_endpoint_json_and_prometheus",
        "test_exporter_busy_port_refuses_loudly",
        "test_metrics_rotation_chains_through_schema_valid_events",
        "test_tailer_holds_torn_line_and_resyncs",
        "test_fleet_rollup_matches_hand_computed_attainment",
        "test_crossproc_waterfall_merges_with_deliberate_clock_offset",
        "test_anomaly_dump_cross_links_profiler_capture",
        "test_serve_dry_run_with_telemetry_and_profiler",
        "test_bench_telemetry_flags_gated_on_serving",
    ],
    # obs v4 (ISSUE 15): the committed-fixture round-trip pin (parse +
    # hand-math reconcile), the taxonomy, the silent-zero HBM pins, the
    # schema-v4/collector/obs_top coverage, the gate's measured
    # direction, and the CLI refusals — all pure host, no compiles; the
    # real-capture end-to-end + duty-cycle-law tests (tiny compiles /
    # a dry-run serve) stay in the default lane
    "test_measured_attribution.py": [
        "test_fixture_capture_parses_to_hand_checked_phases",
        "test_fixture_reconcile_drift_hand_math",
        "test_classify_op_taxonomy",
        "test_device_memory_unavailable_is_none_not_zero",
        "test_publish_hbm_exports_unavailable_loudly",
        "test_schema_v4_profile_attribution_and_hbm_watermark",
        "test_fleet_rollup_folds_hbm_and_keeps_unavailable_distinct",
        "test_obs_top_once_renders_hbm_column",
        "test_gate_measured_ms_directional",
        "test_serve_cli_profile_refusals",
        "test_bench_cli_profile_refusals",
        "test_train_cli_profile_refusals",
    ],
    "test_obs_v2.py": [
        "test_paged_request_timelines_contiguous_and_sum_to_wall",
        "test_flight_ring_bound_holds_under_sustained_load",
        "test_pool_exhausted_preemption_dumps_flight",
        "test_gate_passes_on_a_record_vs_itself",
        "test_gate_fails_on_degraded_record",
        "test_gate_skips_on_backend_unavailable",
        "test_metrics_events_carry_schema_version_and_validate",
        "test_schema_validator_fails_loudly_on_drift",
        "test_rank_skew_ranks_stragglers",
        "test_serve_dry_run_with_tracing_and_flight",
    ],
    # obs v5 (ISSUE 16): the control plane — the committed-reconcile
    # pinned decision, the advise/act ladder laws (advise never mutates,
    # act only at safe points), the loadgen-replay adaptation + ledger
    # reconstruction end-to-end, the zero-cost off pin, the schema-v5
    # ledger contracts, and the --controller window gate (whole file:
    # one tiny dry serve + one tiny replay serve, ~8 s)
    "test_control.py": None,
    # obs v6 (ISSUE 17): run forensics — the fixture RunCard pins, the
    # shared outage classifier + the real-r02 never-a-baseline pin, THE
    # ranked-suspect acceptance pin (pages_per_block -> copy), the
    # committed-trajectory changepoint pin, the schema-v6 contracts, and
    # the --explain gate pair — all pure host, no compiles; the obs_diff
    # CLI matrix + the serve stamp e2e stay in the default lane
    # reshard (ISSUE 20): the stamp round-trip, the file->file layout
    # matrix (bit-identity + the peak-host-one-leaf bound), the planner's
    # op/bytes pins, the loud inexpressible refusal, and the elastic
    # file->device ZeRO-3 stream — all tiny-model; the subprocess elastic
    # resume arm (slow) and the fleet width restart stay out of core
    "test_reshard.py": [
        "test_save_stamps_layout_and_resolves_exactly",
        "test_reshard_checkpoint_bit_identical[",
        "test_plan_op_pins_and_minimal_bytes",
        "test_inexpressible_layout_refuses_loudly",
        "test_stream_load_elastic_zero3_bit_identical_and_bounded",
        "test_gate_treats_reshard_record_as_latency",
    ],
    "test_forensics.py": [
        "test_run_card_pins_fixture_run_a",
        "test_outage_classifier_is_shared_with_gate",
        "test_bench_r02_outage_never_baseline",
        "test_pinned_ranked_suspect_pages_per_block_to_copy",
        "test_changepoint_flags_pinned_trajectory_step",
        "test_schema_v6_forensics_contracts",
        "test_gate_explain_attaches_forensics_on_failure",
        "test_gate_explain_silent_on_pass",
    ],
}


def _core_match(name: str, pattern: str) -> bool:
    """Exact test id, or a prefix that ends at a parametrize bracket / a
    partial param id (pattern ending in '[', '-' or ':'). A bare function
    name must NOT prefix-match longer siblings (test_x must not pull in
    test_x_multiblock) — that would silently grow the audited budget."""
    if name == pattern:
        return True
    if pattern.endswith(("[", "-", ":")):
        return name.startswith(pattern)
    return name.startswith(pattern + "[")


def pytest_collection_modifyitems(config, items):
    # Every CORE_LANE file must exist ON DISK, unconditionally (ADVICE r5):
    # the dead-pattern audit below only runs on full-suite collections, so
    # a renamed/deleted file would otherwise drop its whole axis out of the
    # core lane silently — the exact regression the lane guards against.
    here = os.path.dirname(os.path.abspath(__file__))
    missing = [f for f in CORE_LANE
               if not os.path.exists(os.path.join(here, f))]
    assert not missing, (
        f"CORE_LANE lists test files that no longer exist on disk: "
        f"{missing} — update CORE_LANE in tests/conftest.py to match the "
        f"rename/deletion")

    core = pytest.mark.core
    matched = {}  # (file, pattern) -> hit count
    collected_files = set()
    for item in items:
        fname = os.path.basename(str(item.fspath))
        collected_files.add(fname)
        sel = CORE_LANE.get(fname, False)
        if sel is None:
            item.add_marker(core)
        elif sel:
            name = item.nodeid.split("::", 1)[1] if "::" in item.nodeid else ""
            for p in sel:
                if _core_match(name, p):
                    item.add_marker(core)
                    matched[(fname, p)] = matched.get((fname, p), 0) + 1
                    break
    # The curated lane must not silently shrink: when the whole suite is
    # collected, every pattern must still select at least one test (a
    # rename/param change would otherwise drop an axis from the inner loop
    # while -m core stays green). Partial collections (single-file runs)
    # skip the check.
    if collected_files.issuperset(CORE_LANE):
        dead = [(f, p) for f, sel in CORE_LANE.items() if sel
                for p in sel if (f, p) not in matched]
        assert not dead, f"CORE_LANE patterns match no test: {dead}"
