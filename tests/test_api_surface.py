"""The public API index (docs/api.md) stays truthful: every documented
entry point imports and exists. Catches silent breakage of the surface
users program against — and doc drift when something is renamed."""

import importlib

import pytest

SURFACE = {
    "dlrover_tpu.parallel.accelerate": ["accelerate"],
    "dlrover_tpu.parallel.strategy": ["Strategy", "RULE_SETS"],
    "dlrover_tpu.parallel.mesh": ["MeshPlan"],
    "dlrover_tpu.parallel.planner": ["plan_mesh", "estimate",
                                     "plan_stages", "plan_stage_depths",
                                     "ModelSpec", "estimate_decode",
                                     "serve_cache_bytes"],
    "dlrover_tpu.parallel.aot": ["aot_compile_train_step"],
    "dlrover_tpu.parallel.auto_tune": ["dryrun", "search_strategy"],
    "dlrover_tpu.trainer.run": ["main"],
    "dlrover_tpu.trainer.elastic": ["ElasticTrainer"],
    "dlrover_tpu.trainer.executor": ["TrainExecutor"],
    "dlrover_tpu.trainer.conf": ["build_configuration"],
    "dlrover_tpu.trainer.data": ["ElasticDataLoader",
                                 "ElasticDistributedSampler",
                                 "DevicePreloader"],
    "dlrover_tpu.trainer.text_reader": ["LineIndexedFile",
                                        "ByteTokenizer",
                                        "ShardedTextBatches",
                                        "HFTokenizerAdapter"],
    "dlrover_tpu.checkpoint.manager": ["ElasticCheckpointManager",
                                       "abstract_like"],
    "dlrover_tpu.agent.master_client": ["MasterClient"],
    "dlrover_tpu.agent.sharding_client": ["ShardingClient",
                                          "IndexShardingClient"],
    "dlrover_tpu.agent.training_agent": ["ElasticTrainingAgent",
                                         "AgentConfig"],
    "dlrover_tpu.master.local_master": ["start_local_master"],
    "dlrover_tpu.serving.kv_cache": ["KVCacheSpec", "init_kv_cache",
                                     "kv_cache_rules",
                                     "resolve_kv_precision"],
    "dlrover_tpu.serving.engine": ["ServeEngine", "ServeExecutor"],
    "dlrover_tpu.serving.router": ["RequestRouter"],
    "dlrover_tpu.serving.cli": ["main"],
    "dlrover_tpu.master.main": ["main"],
    "dlrover_tpu.ops.flash_attention": [
        "flash_attention", "flash_attention_auto",
        "flash_attention_segmented", "flash_attention_segmented_auto",
        "flash_attention_prefix", "flash_attention_prefix_auto",
        "flash_attention_prefix_lse",
        "segmented_attention", "flash_attention_lse",
    ],
    "dlrover_tpu.ops.ring_attention": ["ring_attention",
                                       "ring_attention_local",
                                       "impl_from_flags"],
    "dlrover_tpu.ops.moe": ["moe_ffn"],
    "dlrover_tpu.optimizers.wsam": ["wsam"],
    "dlrover_tpu.ps.server": ["start_ps_shard", "PsShardServer"],
    "dlrover_tpu.ps.client": ["PsClusterClient", "partition_params"],
    "dlrover_tpu.ps.trainer": ["AsyncPsTrainer"],
    "dlrover_tpu.ps.repartition": ["repartition_checkpoint", "main"],
    "dlrover_tpu.diagnosis.hang_detector": ["HangingDetector",
                                            "touch_heartbeat",
                                            "announce_long_phase"],
    "dlrover_tpu.diagnosis.fault_injection": ["kill_workers",
                                              "make_flaky",
                                              "corrupt_checkpoint"],
    "dlrover_tpu.models.llama": ["init", "apply", "apply_pipelined",
                                 "llama2_7b", "llama3_8b",
                                 "llama3_70b", "segment_positions"],
    "dlrover_tpu.models.gpt_neox": ["init", "apply", "neox_tiny"],
    "dlrover_tpu.models.glm": ["init", "apply", "glm_tiny"],
    "dlrover_tpu.models.bert": ["init", "apply"],
    "dlrover_tpu.models.clip": ["init"],
    "dlrover_tpu.models.deepfm": ["init", "apply"],
    "dlrover_tpu.utils.prof": ["analyze_cost", "DryRunner", "AProfiler"],
    "dlrover_tpu.brain.client": ["BrainClient"],
    "dlrover_tpu.brain.watcher": ["ClusterWatcher", "K8sClusterSource"],
    "dlrover_tpu.telemetry": ["get_registry", "emit_event",
                              "read_events", "span", "mttr_report",
                              "EventKind", "SpanName", "names"],
    "dlrover_tpu.telemetry.exporter": ["MetricsExporter",
                                       "maybe_start_exporter"],
    "dlrover_tpu.telemetry.cli": ["main"],
}


@pytest.mark.parametrize("module_name", sorted(SURFACE))
def test_documented_surface_exists(module_name):
    module = importlib.import_module(module_name)
    missing = [n for n in SURFACE[module_name] if not hasattr(module, n)]
    assert not missing, f"{module_name} lost documented symbols: {missing}"


# The scan-of-K step program and the knob that selected it are gone
# (PR 31): the train loop has one step program, and what hides the host
# is the in-flight window. One case per former way in.
def _parameters(path):
    import inspect

    module_name, _, attr = path.partition(":")
    obj = importlib.import_module(module_name)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return set(inspect.signature(obj).parameters)


def _fields(path):
    import dataclasses

    module_name, _, attr = path.partition(":")
    cls = getattr(importlib.import_module(module_name), attr)
    return {f.name for f in dataclasses.fields(cls)}


def _tpurun_options(_path):
    from dlrover_tpu.trainer.run import build_parser

    return {a.dest for a in build_parser()._actions}


FORMER_WAYS_IN = [
    (_parameters, "dlrover_tpu.trainer.elastic:ElasticTrainer.__init__"),
    (_parameters, "dlrover_tpu.trainer.elastic:ElasticTrainer.retune"),
    (_parameters,
     "dlrover_tpu.trainer.elastic:ElasticTrainer.live_reshard"),
    (_parameters, "dlrover_tpu.parallel.accelerate:accelerate"),
    (_parameters, "dlrover_tpu.trainer.data:DevicePreloader.__init__"),
    (_parameters,
     "dlrover_tpu.trainer.executor:TrainExecutor.request_retune"),
    (_parameters, "dlrover_tpu.parallel.planner:estimate"),
    (_parameters,
     "dlrover_tpu.master.optimizer.calibration:CostCalibrator.price"),
    (_fields, "dlrover_tpu.common.comm:ParallelConfig"),
    (_fields, "dlrover_tpu.common.comm:TrainerConfigReport"),
    (_tpurun_options, "tpurun"),
]


@pytest.mark.parametrize(
    "names_of,path", FORMER_WAYS_IN, ids=[p for _, p in FORMER_WAYS_IN])
def test_nothing_sets_steps_per_call(names_of, path):
    names = names_of(path)
    assert names, path
    assert "steps_per_call" not in names, path
