"""Global flags.

Reference: platform/flags.cc (26 gflags: memory fractions, cudnn knobs,
NCCL tuning, GC thresholds) re-exported to Python via
global_value_getter_setter.cc and the FLAGS_ env contract honored by
__init__.py.

TPU-native: one typed dict; env vars FLAGS_<name> override defaults at
import. Memory/allocator/cudnn knobs are accepted-but-inert (XLA owns
memory and kernels) and documented as such; the live flags control
debugging behavior.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_FLAG_DEFS: Dict[str, Any] = {
    # live flags
    "check_nan_inf": False,            # per-op nan/inf scan (details/nan_inf_utils.h)
    "benchmark": False,                # Executor.run sync + wall-time print
    "print_op_shape_errors": False,    # escalate swallowed layer shape-inference failures
    # static Program-IR verification before lowering (analysis/):
    # "off" | "warn" (log structural findings, never raise) | "strict"
    # (all passes incl. shape re-inference; errors raise
    # ProgramVerificationError BEFORE any JAX lowering)
    "validate_program": "warn",
    # async host/device pipeline (runtime/dispatch BoundStep
    # .run_pipelined / Executor.run_pipelined): number of prepared
    # feeds the feeder thread may run ahead of the device step. 2 is
    # classic double buffering (one batch in flight on device, one
    # being normalized/device_put on the feeder); each extra slot pins
    # one more batch of device memory for marginal jitter absorption
    "dispatch_pipeline_depth": 2,
    # reader.py GeneratorLoader: depth of the async DEVICE-side
    # prefetch buffer (each entry pins batch_bytes of device memory;
    # the historical hard-coded value was 2 — raise it only when
    # paddle_reader_buffer_empty_stall_total shows feed starvation
    # with a bursty/jittery input pipeline)
    "reader_prefetch_depth": 2,
    # serving/engine.py defaults (overridable per ServingEngine):
    # batch closes at serving_max_batch_size ROWS or after
    # serving_batch_timeout_ms from the first queued request, whichever
    # first; a full admission queue (serving_queue_capacity pending)
    # rejects with serving.Overloaded; serving_num_workers Predictor
    # clones share compiled executables via the dispatch cache
    "serving_max_batch_size": 16,
    "serving_batch_timeout_ms": 5.0,
    "serving_queue_capacity": 256,
    "serving_num_workers": 2,
    # generation/engine.py defaults (overridable per GenerationEngine):
    # the paged KV cache preallocates generation_num_pages pages of
    # generation_page_size token slots per layer; the continuous-
    # batching decode lane is a FIXED batch of
    # generation_max_decode_batch sequences (one compiled executable
    # for the engine's whole life); admission queues up to
    # generation_queue_capacity requests before Overloaded; prompts
    # pad up to the generation_prefill_buckets ladder (one prefill
    # executable per touched bucket); generation_max_new_tokens is the
    # per-request default stop
    "generation_page_size": 16,
    "generation_num_pages": 512,
    "generation_max_decode_batch": 8,
    "generation_queue_capacity": 64,
    "generation_max_new_tokens": 64,
    "generation_prefill_buckets": "16,32,64,128,256,512",
    # ragged decode (generation/engine.py "ragged" mode, the default):
    # ONE [lanes, generation_chunk_tokens] mixed prefill+decode
    # executable replaces the two-lane prefill/decode pair — a prompt
    # longer than generation_chunk_tokens prefills in chunks across
    # steps (chunked prefill: a fat prompt never stalls decode ITL);
    # "two_lane" selects the PR-6 engine (the token-identity oracle).
    # generation_spec_tokens > 0 turns on speculative decoding: a
    # draft model (GenerationEngine(draft=...)) proposes up to k
    # tokens per sequence per step and the target verifies them in
    # the same ragged call — greedy-identical by construction.
    # generation_kv_dtype="int8" stores KV pages blockwise-int8
    # quantized (kernels/quant.py scales, one per head x token slot),
    # ~3.6x fewer pool bytes -> ~2x+ resident sequences at a byte
    # budget (accuracy bench-gated; ragged mode only)
    "generation_engine_mode": "ragged",
    "generation_chunk_tokens": 16,
    "generation_spec_tokens": 0,
    "generation_kv_dtype": "float32",
    # radix prefix cache (generation/kvcache.py trie, ragged only):
    # generation_prefix_cache publishes every full KV page into a
    # refcounted prefix trie and admits new prompts ONTO their matched
    # prefix pages (copy-on-write sharing — a warm shared prompt
    # prefills once, ever, and occupies one set of pages).
    # generation_prefix_min_pages is the match granularity floor
    # (matches shorter than this many full pages are ignored);
    # generation_trie_max_pages caps trie-resident pages (0 =
    # unlimited; the pool itself still reclaims trie leaves LRU-first
    # under pressure)
    # generation_trie_tenant_quota caps trie-resident pages PER TENANT
    # (the traffic tier's tenant identity rides submit(tenant=) into
    # publish attribution): a tenant at quota recycles its OWN LRU
    # leaves, so one tenant's boilerplate cannot monopolize the trie
    # (0 = no per-tenant cap)
    "generation_prefix_cache": False,
    "generation_prefix_min_pages": 1,
    "generation_trie_max_pages": 0,
    "generation_trie_tenant_quota": 0,
    # paddle_tpu.quantize (inference weight quantization): "off" keeps
    # fp32/bf16 weights; "int8" (per-output-channel fp32 scales) /
    # "int8_block" (blockwise scales down the contraction axis, block
    # size quantize_block) / "fp8" (e4m3 weights, bf16 compute) make
    # Predictor construction and GenerationEngine rewrite every
    # eligible matmul/fc weight ONCE at load into device-resident
    # quantized buffers + scale planes (fp32 originals dropped — a
    # 2-4x weight-HBM cut), repointing the program onto the
    # quantized_matmul/quantized_fc ops. Composes with
    # generation_kv_dtype="int8" for a fully-quantized ragged decode.
    # Per-instance override: Config.enable_weight_quantization /
    # GenerationEngine(quantize_weights=...).
    "quantize_weights": "off",
    "quantize_block": 256,
    # paddle_tpu.adapters (batched LoRA multiplexing, ragged engine
    # only): adapter_pool_max_bytes > 0 builds an AdapterStore of
    # device-resident rank-bucketed (A, B) factor pools at engine
    # construction, rewrites the ragged program onto the
    # batched_lora_fc/batched_lora_matmul ops (composes with
    # quantize_weights — the delta applies to the dequantized
    # product), and threads the per-row gen_adapter_slots feed
    # through the ragged step so ONE executable serves a different
    # adapter per batch row. adapter_rank_buckets names the bucket
    # ranks ("8,16"): an upload lands in the smallest bucket its rank
    # fits, zero-padded. adapter_slots_per_bucket > 0 overrides the
    # byte-derived per-bucket capacity (adapters per bucket, excluding
    # the reserved zero slot). adapter_tenant_quota caps RESIDENT
    # adapters per tenant: an over-quota tenant self-evicts its own
    # LRU idle adapter (the trie-quota shape). traffic_adapter_quotas
    # is the traffic tier's per-(tenant, adapter) admission table
    # ("alice:summarize=10:20,*:translate=5" — name:adapter=rate[:burst],
    # "*" matches any tenant); "" = no per-adapter admission.
    "adapter_pool_max_bytes": 0,
    "adapter_rank_buckets": "8,16",
    "adapter_slots_per_bucket": 0,
    "adapter_tenant_quota": 0,
    "traffic_adapter_quotas": "",
    # resilience/supervisor.py defaults (overridable per Supervisor /
    # CheckpointPolicy): checkpoint cadence is every-N-steps OR
    # every-T-seconds, whichever fires first (0 disables that trigger);
    # keep_last bounds the retention GC; a step that raises is retried
    # up to resilience_max_retries times with exponential backoff from
    # resilience_retry_backoff_s; a non-finite loss rolls back to the
    # last committed checkpoint at most resilience_max_rollbacks times;
    # resilience_watchdog_timeout_s > 0 runs each step under a hang
    # watchdog; resilience_fault_spec injects deterministic faults
    # ("raise@12,nan@20,hang@30:2.5,kill@40") for chaos testing
    "resilience_ckpt_every_steps": 50,
    "resilience_ckpt_every_secs": 0.0,
    "resilience_keep_last": 3,
    "resilience_max_retries": 3,
    "resilience_retry_backoff_s": 0.05,
    "resilience_max_rollbacks": 2,
    "resilience_watchdog_timeout_s": 0.0,
    "resilience_fault_spec": "",
    # partition/ (logical-axis-rules partitioner) defaults, consumed by
    # PartitionConfig(): partition_mesh is the mesh shape ("dp=4,tp=2";
    # "" = no default mesh, configs must pass mesh_axes=);
    # partition_rules overrides the logical->mesh axis rules table
    # ("batch=dp,heads=tp,embed=", empty right side = replicated; "" =
    # partition.DEFAULT_RULES); partition_zero is the ZeRO level for
    # optimizer state (0 = replicated, 1 = shard accumulators over dp,
    # 3 = shard params too)
    "partition_mesh": "",
    "partition_rules": "",
    "partition_zero": 0,
    # parallel/collectives.py (gradient-collective planner): when
    # collective_bucket_mb > 0 OR collective_quantization != "none",
    # Optimizer.apply_gradients / CompiledProgram.with_partitioning
    # rewrite the train program so the DP gradient all-reduce runs as
    # size-capped per-bucket collectives issued as each bucket's grads
    # are produced (shard_map/psum inside the one jitted step —
    # overlappable with the rest of backward), instead of one
    # monolithic end-of-backward GSPMD blob. collective_bucket_mb caps
    # a bucket's payload (0 = planner off unless quantization asks for
    # it); collective_quantization="int8" swaps each bucket's psum for
    # the EQuARX-style two-shot blockwise-int8 exchange (~3.9x fewer
    # wire bytes at block 256, bench-gated accuracy);
    # collective_quant_block is the per-scale block size in elements.
    # collective_bucket_mb also takes a PER-MESH-AXIS form
    # ("dp=32,dcn=8"... sizes in MB): a reduce whose mesh axis crosses
    # hosts (DCN) picks its own — typically bigger — bucket than one
    # staying on ICI; the single-value form applies everywhere
    # (parallel.collectives.parse_bucket_mb)
    "collective_bucket_mb": "0",
    "collective_quantization": "none",
    "collective_quant_block": 256,
    # kernels/fused_optim.py: replace the unfused XLA m/v/param chain
    # of Adam/Momentum with the one-pass Pallas update over donated
    # buffers. "auto" (default) fuses on real TPU targets (and under
    # PADDLE_TPU_FORCE_PALLAS=1); "on"/"off" force. On non-TPU
    # backends the fused ops lower to the pure-JAX reference, which is
    # op-for-op the unfused chain (bitwise-identical trajectories)
    "optimizer_fuse": "auto",
    # tools/autotune.py cost-model autotuner: profiles keyed by
    # executable fingerprint live under autotune_dir. It names no
    # directory by default, so nothing outside the checkout steers a
    # run: the seam is inert until autotune_dir is set. Then, when
    # autotune_apply is on, Executor._compile (and the serving/
    # generation engine constructors) look up the program's profile
    # and apply its tuned flags — EXCEPT flags the user set explicitly
    # (set_flags / FLAGS_ env always win). apply_autotune_profile()
    # is the same seam invoked by hand.
    "autotune_dir": "",
    "autotune_apply": True,
    # disagg/ (disaggregated prefill/decode serving): the page-store
    # rendezvous between prefill and decode workers.
    # disagg_wire_encoding picks how fp32 KV pages cross the wire —
    # "int8_block" quantizes blockwise at block=head_dim (one fp32
    # scale per head/token slot, ~0.28x the fp32 bytes at head_dim 32;
    # int8 pool pages always ship verbatim), "raw" ships fp32 bytes
    # untouched (bitwise fidelity over bandwidth).
    # disagg_store_endpoint ("host:port") names the page store when
    # the env contract (PADDLE_PAGESTORE_ENDPOINT, or the first
    # PADDLE_TRAINER_ENDPOINTS host at disagg_store_port) does not;
    # disagg_store_max_bytes caps the store's host RAM (LRU leaf
    # eviction; 0 = unbounded); disagg_fetch_timeout_s bounds every
    # store RPC; disagg_handoff_threads sizes the DisaggService's
    # prefill->decode dispatcher pool
    "disagg_wire_encoding": "int8_block",
    "disagg_store_endpoint": "",
    "disagg_store_port": 8793,
    "disagg_store_max_bytes": 268435456,
    "disagg_fetch_timeout_s": 5.0,
    "disagg_handoff_threads": 2,
    # traffic/ (SLO-aware admission + multi-tenant scheduling) defaults,
    # consumed by TrafficConfig.from_flags(): traffic_queue_capacity is
    # the per-PRIORITY-CLASS bounded queue depth (a full class queue
    # sheds with Retry-After instead of queueing into a latency cliff);
    # traffic_tenants declares per-tenant token-bucket quotas
    # ("alice=100:200,bob=50" = name=rate_rps[:burst]); unknown tenants
    # get traffic_default_rate/traffic_default_burst (rate 0 =
    # unlimited); a queued batch/best_effort request older than
    # traffic_aging_ms is promoted one class per interval so strict
    # priority cannot starve it; traffic_shed_headroom scales the
    # service-time estimate when deciding a deadline is provably
    # unmeetable (shed BEFORE a batch slot is spent);
    # traffic_max_inflight bounds requests handed to the engine at once
    # (0 = auto from the engine's batch geometry, keeps ordering in the
    # traffic layer); sustained deadline-miss ratio above
    # traffic_slo_miss_threshold for traffic_slo_window_s dumps the
    # flight recorder; traffic_stream_write_timeout_s cancels a
    # streamed /v1/generate whose client stopped reading (frees its KV
    # pages; 0 disables)
    "traffic_queue_capacity": 64,
    "traffic_tenants": "",
    "traffic_default_rate": 0.0,
    "traffic_default_burst": 0.0,
    "traffic_aging_ms": 500.0,
    "traffic_shed_headroom": 1.2,
    "traffic_max_inflight": 0,
    "traffic_slo_miss_threshold": 0.5,
    "traffic_slo_window_s": 5.0,
    "traffic_stream_write_timeout_s": 30.0,
    # distributed/ (multi-host coordination, distributed/coordinator.py
    # + the two-phase cross-host checkpoint commit in io.py):
    # dist_commit_timeout_s bounds every phase of a multi-host save —
    # the stage-ready handshake, process 0's wait for all shard-done
    # files, and the other ranks' wait for the commit marker; a rank
    # that dies mid-save turns into ONE bounded CheckpointCommitTimeout
    # (never a torn committed checkpoint, never an unbounded hang).
    # dist_barrier_timeout_s is the default Coordinator.barrier()
    # timeout — a coordination-service stall (dead peer) becomes a
    # BarrierTimeout the Supervisor converts to a clean restartable
    # exit (RESTART_EXIT_CODE) for the elastic launcher
    "dist_commit_timeout_s": 120.0,
    "dist_barrier_timeout_s": 300.0,
    # observability/ (unified telemetry): observability_metrics turns
    # on per-step telemetry instruments (wall time, examples/sec) in
    # the dispatch hot path; observability_tracing upgrades span call
    # sites from plain record_event ranges to trace-id/span-id spans
    # (cross-thread flow arrows in timeline traces) and logs each span
    # into the flight recorder; observability_flight keeps the
    # constant-memory crash-time ring buffer (capacity entries) that
    # dumps JSON to observability_dump_dir ("" = the system tempdir)
    # on NaN rollback / watchdog hang / SIGTERM / SIGUSR2;
    # observability_xla_analysis additionally surfaces per-executable
    # XLA memory_analysis()/cost_analysis() gauges at compile time
    # (costs one extra lower+compile per executable — debugging knob)
    "observability_metrics": True,
    "observability_tracing": False,
    "observability_flight": True,
    "observability_flight_capacity": 512,
    "observability_dump_dir": "",
    "observability_xla_analysis": False,
    # fleet observability (observability/fleet.py):
    # observability_fleet_endpoints seeds the FleetAggregator with a
    # comma list of worker metrics endpoints ("name=host:port" or bare
    # "host:port"); observability_fleet_timeout_s is the hard
    # per-endpoint scrape deadline (a hung backend goes stale, never
    # stalls the merge). slo_deadline_miss_budget is the error budget
    # (allowed deadline-miss ratio) the burn rate is measured against;
    # slo_ttft_p99_ms / slo_itl_p99_ms are latency targets (0 = no
    # target, gauges still exported); slo_window_s is the sliding
    # window for miss-ratio/burn math; slo_burn_threshold > 0 arms the
    # sustained-burn trigger (burn above it for a full window fires
    # ONE fleet-wide flight dump, latched until the burn recedes)
    "observability_fleet_endpoints": "",
    "observability_fleet_timeout_s": 1.0,
    "slo_deadline_miss_budget": 0.01,
    "slo_ttft_p99_ms": 0.0,
    "slo_itl_p99_ms": 0.0,
    "slo_window_s": 30.0,
    "slo_burn_threshold": 0.0,
    "eager_delete_tensor_gb": 0.0,     # inert: XLA frees by liveness
    # accepted-but-inert parity flags (reference platform/flags.cc)
    "fraction_of_gpu_memory_to_use": 0.92,
    "allocator_strategy": "naive_best_fit",
    "cudnn_deterministic": False,
    "enable_parallel_graph": False,
    "sync_nccl_allreduce": True,
    "max_inplace_grad_add": 0,
    "cpu_deterministic": False,
    "paddle_num_threads": 1,
    "use_pinned_memory": True,
    "init_allocated_mem": False,
    "free_idle_memory": False,
    "reader_queue_speed_test_mode": False,
    "enable_unused_var_check": False,
    "fuse_parameter_memory_size": -1,
    "tracer_profile_fname": "",
}

_flags: Dict[str, Any] = {}

# bumped on every set_flags: the dispatch fast path (runtime/dispatch)
# snapshots flag-dependent choices per BoundStep and keys on this
# counter instead of re-reading flags every step
_generation = 0

# flags the USER pinned — via FLAGS_<name> env or set_flags — as
# opposed to defaults: an autotune profile never overrides these
# (explicit configuration outranks a recorded sweep)
_explicit: set = set()


def _coerce(default, raw: str):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _init():
    for name, default in _FLAG_DEFS.items():
        env = os.environ.get(f"FLAGS_{name}")
        if env is not None:
            _flags[name] = _coerce(default, env)
            _explicit.add(name)
        else:
            _flags[name] = default


_init()


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n[len("FLAGS_"):] if n.startswith("FLAGS_") else n
        if key not in _flags:
            raise ValueError(f"unknown flag {n!r}")
        out[n] = _flags[key]
    return out


def set_flags(flag_dict: Dict[str, Any]):
    global _generation
    for n, v in flag_dict.items():
        key = n[len("FLAGS_"):] if n.startswith("FLAGS_") else n
        if key not in _flags:
            raise ValueError(f"unknown flag {n!r}")
        _flags[key] = v
        _explicit.add(key)
    _generation += 1


def generation() -> int:
    return _generation


def flag(name: str):
    return _flags[name]


# -- autotune profiles -------------------------------------------------------
# tools/autotune.py sweeps the performance knobs for one workload and
# writes the winners as a JSON profile keyed by the workload's
# executable fingerprint. This seam is the consumer: a later process
# running the same workload calls apply_autotune_profile(fingerprint)
# — Executor._compile and the serving/generation engine constructors
# do it automatically under the `autotune_apply` flag — and comes up
# pre-tuned with zero hand-set flags. Precedence: a flag the user set
# explicitly (set_flags / FLAGS_ env) is never overridden.

AUTOTUNE_PROFILE_VERSION = 1

_logger = None


def _log():
    global _logger
    if _logger is None:
        import logging

        _logger = logging.getLogger("paddle_tpu.autotune")
    return _logger


class AutotuneProfileMismatch(ValueError):
    """The profile on disk records a different executable fingerprint
    than the one requested — a stale/copied profile is refused rather
    than silently mis-tuning a different workload."""


def autotune_dir() -> str:
    return os.path.expanduser(str(flag("autotune_dir")))


def autotune_profile_path(fingerprint: str, dir: str = None) -> str:
    base = os.path.expanduser(dir) if dir else autotune_dir()
    if not base:
        raise ValueError(
            "no autotune profile directory: set the autotune_dir flag "
            "(or pass dir=)")
    # fingerprints are hex digests / identifier-safe keys; sanitize
    # anything else so a weird key cannot escape the profile dir
    safe = "".join(c if (c.isalnum() or c in "._-") else "_"
                   for c in str(fingerprint))
    return os.path.join(base, f"{safe}.json")


def save_autotune_profile(fingerprint: str, flag_updates: Dict[str, Any],
                          evidence: Dict[str, Any] = None,
                          dir: str = None) -> str:
    """Write a tuned-flags profile for one executable fingerprint.
    Unknown flag names are rejected here (at tuner time) so the apply
    side only ever has to warn about cross-version drift."""
    import json

    for n in flag_updates:
        if n not in _FLAG_DEFS:
            raise ValueError(f"save_autotune_profile: unknown flag {n!r}")
    path = autotune_profile_path(fingerprint, dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {
        "version": AUTOTUNE_PROFILE_VERSION,
        "fingerprint": str(fingerprint),
        "flags": dict(flag_updates),
        "evidence": dict(evidence or {}),
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def apply_autotune_profile(fingerprint: str, dir: str = None,
                           missing_ok: bool = False) -> Dict[str, Any]:
    """Load the profile for ``fingerprint`` and apply its flags —
    skipping any flag the user set explicitly — returning the dict of
    flags actually applied. A malformed or wrong-version profile
    degrades to the defaults with a warning (never an exception: a
    corrupt cache file must not take down training); a profile whose
    RECORDED fingerprint disagrees with the requested one raises
    AutotuneProfileMismatch (stale profiles are refused, not guessed
    at)."""
    import json

    global _generation
    path = autotune_profile_path(fingerprint, dir)
    if not os.path.exists(path):
        if missing_ok:
            return {}
        raise FileNotFoundError(
            f"no autotune profile for fingerprint {fingerprint!r} "
            f"(looked at {path}); run tools/autotune.py first")
    try:
        with open(path) as f:
            payload = json.load(f)
        if not isinstance(payload, dict):
            raise ValueError("profile root is not an object")
        version = payload.get("version")
        profile_flags = payload.get("flags")
        recorded = payload.get("fingerprint")
        if version != AUTOTUNE_PROFILE_VERSION:
            raise ValueError(
                f"profile version {version!r} != "
                f"{AUTOTUNE_PROFILE_VERSION}")
        if not isinstance(profile_flags, dict):
            raise ValueError("profile has no 'flags' object")
    except (json.JSONDecodeError, ValueError, OSError) as e:
        _log().warning(
            "autotune profile %s is malformed (%s); ignoring it and "
            "running with default flags", path, e)
        return {}
    if recorded != str(fingerprint):
        raise AutotuneProfileMismatch(
            f"autotune profile {path} records fingerprint {recorded!r} "
            f"but {fingerprint!r} was requested — the profile is stale "
            "(re-run tools/autotune.py for this workload)")
    applied: Dict[str, Any] = {}
    for n, v in profile_flags.items():
        if n not in _FLAG_DEFS:
            _log().warning(
                "autotune profile %s names unknown flag %r; skipping",
                path, n)
            continue
        if n in _explicit:
            continue  # explicit configuration outranks the sweep
        # coerce to the flag's declared type — a value-corrupt profile
        # must degrade HERE with a warning, not crash later at bind
        # time when the runtime consumes the flag
        default = _FLAG_DEFS[n]
        try:
            if isinstance(v, str):
                v = _coerce(default, v)
            elif isinstance(default, bool):
                v = bool(v)
            elif isinstance(default, int):
                v = int(v)
            elif isinstance(default, float):
                v = float(v)
            elif isinstance(default, str):
                v = str(v)
        except (TypeError, ValueError):
            _log().warning(
                "autotune profile %s: flag %r value %r does not coerce "
                "to %s; skipping", path, n, v, type(default).__name__)
            continue
        _flags[n] = v
        applied[n] = v
    if applied:
        _generation += 1
        _log().info("autotune profile applied for %s: %s",
                    fingerprint, applied)
    return applied


# fingerprints already auto-probed this process — the Executor seam
# must cost one set lookup per program, not a disk stat per bind
_autotune_probed: set = set()


def autotune_apply_for(fingerprint: str) -> Dict[str, Any]:
    """The automatic seam (Executor._compile / engine construction):
    best-effort apply of a matching profile under the
    ``autotune_apply`` flag — once per fingerprint per process, and
    never an exception on the construction path."""
    if not (flag("autotune_apply") and flag("autotune_dir")
            and fingerprint):
        return {}
    if fingerprint in _autotune_probed:
        return {}
    _autotune_probed.add(fingerprint)
    try:
        return apply_autotune_profile(fingerprint, missing_ok=True)
    except Exception as e:  # noqa: BLE001 — construction must survive
        _log().warning("autotune profile for %s not applied: %s",
                       fingerprint, e)
        return {}
