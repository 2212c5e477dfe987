"""Runtime configuration from upstream environment variables.

Rebuild of the knob surface the reference reads at startup
(``horovod/common/utils/env_parser.cc`` + ``horovod/runner/common/util/
env.py``): the same ``HOROVOD_*`` variables configure the TPU-native
engine, so launch scripts port unchanged. Variables whose mechanism has no
TPU analogue (e.g. ``HOROVOD_CYCLE_TIME`` — there is no controller cycle
to batch under SPMD) are accepted and recorded but have no effect; they're
listed in :data:`Config.inert` so ``build_info`` can report them.

Read once per :func:`horovod_tpu.init` (upstream reads once at
``horovod_init``); :func:`refresh` re-reads for tests/elastic restarts.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

__all__ = ["Config", "get_config", "refresh"]

_MB = 1024 * 1024


def _env_bytes(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v else default


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclass
class Config:
    # Fusion (fusion_buffer_manager.cc): HOROVOD_FUSION_THRESHOLD bytes.
    fusion_threshold_bytes: int = 64 * _MB
    # Gradient-sync algorithm axis (overlap.py):
    # HOROVOD_ALLREDUCE_ALGORITHM in {auto, psum, rs_ag, chunked_rs_ag,
    # rs_ag_int8, chunked_rs_ag_int8, rs_ag_fp8, chunked_rs_ag_fp8}
    # picks the per-bucket allreduce lowering; HOROVOD_ALLREDUCE_WIRE in
    # {fp32, bf16, int8, fp8} sets the default wire precision (auto is
    # psum on the exact wire and picks a quantized rs_ag variant by
    # bucket size under int8/fp8; bf16 casts the payload around the
    # collective);
    # HOROVOD_OVERLAP_CHUNKS is the pipeline depth of chunked_rs_ag.
    allreduce_algorithm: str = "auto"
    allreduce_wire: str = "fp32"
    overlap_chunks: int = 4
    # Topology override (parallel/mesh.py detect_topology):
    # HOROVOD_TOPOLOGY="XxY" factors the world into a simulated torus on
    # CPU/tests (on TPU the dims come from device coords and this is
    # normally unset). Stored as the normalized spec string; the dims
    # tuple lives on the init context (core.topology()) because the
    # product must be validated against the actual world size at init.
    topology: Optional[str] = None
    # Multi-axis mesh (parallel/mesh.py, parallel/mp.py):
    # HOROVOD_MESH="dpXxmpY" splits the world into a named dp x mp mesh —
    # data-parallel outer (DCN tolerant), model/tensor-parallel inner
    # (ICI hungry). Stored as the normalized spec string; the degrees
    # must factor the actual world and nest with the detected topology,
    # which is validated at init (core.mesh2d()). Unset = pure dp
    # (dp=world, mp=1), the pre-mesh behaviour.
    # HOROVOD_MP_RULES picks the model-parallel rule set mp.partition
    # helpers use: "auto" (per model family), "megatron" (the explicit
    # column/row split), or "off" (replicate weights even under mp>1 —
    # a debugging escape hatch).
    mesh: Optional[str] = None
    mp_rules: str = "auto"
    # Timeline (timeline.cc): HOROVOD_TIMELINE=<path> starts the Chrome
    # trace at init; HOROVOD_TIMELINE_MARK_CYCLES adds cycle markers.
    timeline_path: Optional[str] = None
    timeline_mark_cycles: bool = False
    # Autotune: HOROVOD_AUTOTUNE enables the online tuner;
    # HOROVOD_AUTOTUNE_LOG mirrors upstream's tuning log path.
    # HOROVOD_AUTOTUNE_MODE picks the search: "ladder" (candidate walk) or
    # "bayes" (GP + expected improvement, upstream horovod/runner/autotune).
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_mode: str = "ladder"
    # Bayesian-mode budget: HOROVOD_AUTOTUNE_PROBES GP proposals x
    # HOROVOD_AUTOTUNE_SAMPLES timed steps each (upstream exposes the
    # same budget knobs on its GP tuner).
    autotune_probes: int = 6
    autotune_samples: int = 10
    # Metrics subsystem (metrics.py): HOROVOD_METRICS_FILE enables the
    # background snapshot flusher (.prom/.txt extension -> Prometheus text
    # exposition, anything else JSON); HOROVOD_METRICS_INTERVAL is the
    # write period in seconds. HOROVOD_METRICS_GRAD_NORM=1 additionally
    # records a gradient-norm gauge from inside the training step (a
    # host callback per step — off by default).
    metrics_file: Optional[str] = None
    metrics_interval_seconds: float = 10.0
    metrics_grad_norm: bool = False
    # Stall inspector (stall_inspector.cc): warning threshold + disable.
    # The same knobs gate metrics.StallWatchdog (auto-started by init()).
    stall_check_disable: bool = False
    stall_check_time_seconds: float = 60.0
    # Profiler subsystem (profiler.py): HOROVOD_PROFILE_ON_STALL=1 lets
    # the stall watchdog and serving deadline breaches trigger a bounded,
    # rank-scoped jax.profiler capture; HOROVOD_PROFILE_DIR is where
    # captures land, HOROVOD_PROFILE_SECONDS bounds each capture and
    # HOROVOD_PROFILE_MAX_CAPTURES caps captures per process (a stall
    # storm must not become a disk-filling profile storm).
    profile_on_stall: bool = False
    profile_dir: str = "/tmp/horovod_profile"
    profile_seconds: float = 5.0
    profile_max_captures: int = 2
    # HOROVOD_PROFILER_COST: tri-state — None (unset) lets each call site
    # pick its default (instrumented steps ON, serving engine OFF, whose
    # capture compiles each phase twice); set forces it for both.
    profiler_cost: Optional[bool] = None
    # Serving subsystem (serving/, docs/SERVING.md): HOROVOD_SERVE_SLOTS
    # decode lanes per engine, HOROVOD_SERVE_MAX_LEN max prompt+output
    # tokens, HOROVOD_SERVE_BLOCK_SIZE tokens per paged-KV block,
    # HOROVOD_SERVE_QUEUE_LIMIT backpressure bound,
    # HOROVOD_SERVE_PREFILL_CHUNK prompt tokens per interleaved prefill
    # dispatch (1 = pure token-level interleaving, no second program),
    # HOROVOD_SERVE_KV_QUANT in {"", "int8", "fp8"} for 1-byte KV blocks,
    # HOROVOD_SERVE_HEARTBEAT replica liveness period (replica.py).
    # Socket transport (serving/transport.py): HOROVOD_SERVE_RPC_TIMEOUT
    # per-attempt socket timeout, HOROVOD_SERVE_MAX_RETRIES transport-
    # level retries per RPC (0 = one attempt), HOROVOD_SERVE_HEDGE_MS
    # tail-latency hedge delay for still-queued requests (0 = off),
    # HOROVOD_SERVE_BREAKER_FAILURES consecutive connect/timeout
    # failures that open a replica's circuit, HOROVOD_SERVE_BREAKER_RESET
    # seconds before a half-open probe.
    # Prefix caching + speculative decode (serving/cache.py PrefixIndex,
    # engine verify lane): HOROVOD_SERVE_PREFIX_CACHE=1 turns on the
    # copy-on-write shared-prefix radix index over the paged pool —
    # admission matches full prompt blocks against previously served
    # prompts and attaches them refcounted instead of re-prefilling;
    # HOROVOD_SERVE_SPEC_K drafts k tokens per decode dispatch through
    # the proposer and verifies them in the SAME single jitted decode
    # program (0 = classic one-token decode);
    # HOROVOD_SERVE_SPEC_PROPOSER picks the drafting strategy ("ngram"
    # — prompt/history lookup — is the only one today).
    serve_slots: int = 8
    serve_max_len: int = 512
    serve_block_size: int = 16
    serve_queue_limit: int = 128
    serve_prefill_chunk: int = 8
    serve_kv_quant: str = ""
    serve_prefix_cache: bool = False
    serve_spec_k: int = 0
    serve_spec_proposer: str = "ngram"
    serve_heartbeat_seconds: float = 2.0
    serve_rpc_timeout_seconds: float = 5.0
    # Disaggregated serving (serving/disagg.py, docs/SERVING.md
    # "Disaggregated serving"): HOROVOD_SERVE_ROLE splits replica duties
    # — "prefill" runs chunked prefill only and exports the KV blocks
    # for migration, "decode" (and the default "both") serves full
    # requests; HOROVOD_SERVE_KV_WIRE picks the migration wire format
    # ("" follows the pool storage dtype; fp32/bf16 raw; int8/fp8 via
    # the EQuARX block formats with per-(token,head) scales — ~4x
    # cheaper transfer); HOROVOD_SERVE_AFFINITY routes by prompt-prefix
    # fingerprint (consistent hash over the decode pool) so shared
    # preambles keep hitting the replica whose radix index owns them
    # ("auto" = on whenever role pools exist, "on"/"off" force it).
    serve_role: str = "both"
    serve_kv_wire: str = ""
    serve_affinity: str = "auto"
    serve_transport: str = "stream"
    serve_auth_token: str = ""
    serve_max_retries: int = 3
    serve_hedge_ms: float = 0.0
    serve_breaker_failures: int = 3
    serve_breaker_reset_seconds: float = 1.0
    # Fleet supervisor (serving/fleet.py): HOROVOD_SERVE_FLEET_RESTART_BUDGET
    # restarts per replica before quarantine, HOROVOD_SERVE_FLEET_BACKOFF /
    # HOROVOD_SERVE_FLEET_BACKOFF_CAP jittered-exponential restart backoff
    # base/cap seconds, HOROVOD_SERVE_FLEET_CRASH_LOOP_K deaths within
    # HOROVOD_SERVE_FLEET_CRASH_LOOP_WINDOW seconds that quarantine a
    # crash-looping replica, HOROVOD_SERVE_FLEET_PROBE supervision poll
    # period, HOROVOD_SERVE_FLEET_SPARES warm spare engines held for
    # promotion into a dead rank's slot. Disaggregated fleets:
    # HOROVOD_SERVE_FLEET_PREFILL carves that many of the serving slots
    # into a prefill pool (the rest decode; 0 = monolithic "both"
    # fleet), and HOROVOD_SERVE_FLEET_PREFILL_SPARES says how many of
    # the warm spares are prefill-roled — spares promote same-pool
    # only, so each pool's capacity heals independently.
    serve_fleet_restart_budget: int = 5
    serve_fleet_backoff_seconds: float = 0.5
    serve_fleet_backoff_cap_seconds: float = 10.0
    serve_fleet_crash_loop_k: int = 3
    serve_fleet_crash_loop_window_seconds: float = 30.0
    serve_fleet_probe_seconds: float = 0.5
    serve_fleet_spares: int = 0
    serve_fleet_prefill: int = 0
    serve_fleet_prefill_spares: int = 0
    # Request tracing (serving/reqtrace.py): HOROVOD_REQUEST_TRACE=1 turns
    # on the per-request span layer (trace context minted at dispatcher
    # submit, spans at every hop); HOROVOD_REQUEST_TRACE_DIR is where each
    # process flushes its Chrome-trace shard (unset = buffer only, served
    # via the /trace HTTP endpoint); HOROVOD_REQUEST_TRACE_DECODE_EVERY
    # samples one DECODE span every N decode steps to bound overhead.
    # HOROVOD_METRICS_PORT starts hvd.metrics_http() on replica servers
    # and the fleet supervisor (0 = off; rank r binds port+r; "auto" —
    # stored as -1 — binds an ephemeral port that the status RPC and
    # membership file advertise, so co-hosted fleets never collide).
    request_trace: bool = False
    request_trace_dir: Optional[str] = None
    request_trace_decode_every: int = 16
    metrics_port: int = 0
    # Fleet health plane (timeseries.py / health.py, docs/OBSERVABILITY.md
    # "Fleet health plane"): HOROVOD_HEALTH_INTERVAL is the continuous
    # doctor's evaluation/sampling tick, HOROVOD_HEALTH_WINDOW the
    # sliding window its checks see, HOROVOD_HEALTH_FIRE_N /
    # HOROVOD_HEALTH_CLEAR_M the fire/clear hysteresis (N consecutive
    # bad windows to fire an alert, M good ones to clear it),
    # HOROVOD_HEALTH_ALERTS_FILE the append-only alerts.jsonl path,
    # HOROVOD_FLEET_SCRAPE_INTERVAL the FleetCollector's per-member
    # scrape period. Declared SLOs: HOROVOD_SLO_TTFT_P99_MS (0 = no TTFT
    # SLO) and HOROVOD_SLO_ERROR_RATE (allowed error fraction, 0 = no
    # error SLO), both evaluated as multi-window burn rates that must
    # exceed HOROVOD_SLO_BURN_THRESHOLD in the short AND long window.
    health_interval_seconds: float = 2.0
    health_window_seconds: float = 30.0
    health_fire_n: int = 2
    health_clear_m: int = 2
    health_alerts_file: Optional[str] = None
    fleet_scrape_interval_seconds: float = 1.0
    slo_ttft_p99_ms: float = 0.0
    slo_error_rate: float = 0.0
    slo_burn_threshold: float = 2.0
    # Flight recorder (blackbox.py, docs/OBSERVABILITY.md "Postmortem
    # bundles"): HOROVOD_BLACKBOX=1 arms the always-on black box —
    # bounded rings of the last HOROVOD_BLACKBOX_SECONDS of timeline
    # events, registry snapshots, alerts, fault injections and fleet
    # transitions. Bundles publish into HOROVOD_BLACKBOX_DIR (default
    # <tmpdir>/horovod_blackbox) as postmortem-<label>-<ts>/ dirs,
    # keeping at most HOROVOD_BLACKBOX_MAX_BUNDLES (oldest evicted
    # first). HOROVOD_BLACKBOX_DUMP_ON picks which AUTOMATIC triggers
    # publish (comma list of signal,stall,alert,engine,fault; "none"
    # leaves only explicit hvd.dump_postmortem() and the fleet 'dump'
    # RPC). HOROVOD_FAULTHANDLER=0 opts out of the stdlib faulthandler
    # init() points at the blackbox dir for native-crash stacks.
    blackbox: bool = False
    blackbox_seconds: float = 120.0
    blackbox_dir: Optional[str] = None
    blackbox_max_bundles: int = 8
    blackbox_dump_on: str = "signal,stall,alert,engine,fault"
    faulthandler_enable: bool = True
    # Elastic (runner/elastic): rendezvous/restart timeout.
    elastic_timeout_seconds: float = 600.0
    # Preemption tolerance (checkpoint_sharded.py / faults.py /
    # docs/ELASTIC.md): HOROVOD_PREEMPTION_NOTICE is the seconds of
    # warning the platform gives before a host disappears (GCP TPU-VM
    # preemption notice ~30s) — hvd.doctor() flags a checkpoint cadence
    # slower than this budget, because then a preemption loses more than
    # the notice window could have saved. HOROVOD_FAULT_PLAN is the
    # fault-injection schedule (kill/stall/slow_write at a chosen
    # rank+step; grammar in faults.py) — validated here so a typo'd plan
    # fails at init instead of silently never firing.
    preemption_notice_seconds: float = 30.0
    fault_plan: str = ""
    # Subset-barrier wait (collective.barrier on a process set); its own
    # knob so tuning elastic failover never shortens unrelated barriers.
    barrier_timeout_seconds: float = 600.0
    # Config bus (confbus.py, docs/OBSERVABILITY.md "Config plane"):
    # HOROVOD_CONFIG_LEDGER is the JSONL audit-ledger path (unset =
    # in-memory ring only), HOROVOD_CONFIG_EXPERIMENT_WINDOW the
    # measured-effect window seconds each mutation observes its target
    # metric over, HOROVOD_CONFIG_REVERT_ON_REGRESSION=1 opts into
    # auto-reverting a mutation whose experiment verdict is `regressed`.
    config_ledger_file: Optional[str] = None
    config_experiment_window_seconds: float = 10.0
    config_revert_on_regression: bool = False
    # NOTE: HOROVOD_HIERARCHICAL_ALLREDUCE is deliberately NOT mirrored
    # here — collective.py/adasum.py read it at call time so tests and
    # scripts can toggle it between collectives without a refresh().
    # Logging: HOROVOD_LOG_LEVEL (trace/debug/info/warning/error/fatal).
    log_level: str = "warning"
    # Accepted-but-inert on TPU, with the reason.
    inert: dict = field(default_factory=dict)


_CONFIG: Optional[Config] = None

# Knobs whose mechanism SPMD/XLA deletes; accepted so upstream launch
# scripts run unchanged, surfaced via build_info for transparency.
_INERT_VARS = {
    "HOROVOD_CYCLE_TIME": "no controller cycle under SPMD; XLA schedules",
    "HOROVOD_CACHE_CAPACITY": "response cache is unbounded host-side",
    "HOROVOD_BATCH_D2D_MEMCOPIES": "XLA fuses device copies",
    "HOROVOD_NUM_NCCL_STREAMS": "ICI collectives are compiler-scheduled",
    "HOROVOD_MPI_THREADS_DISABLE": "no MPI backend on TPU",
    "HOROVOD_GLOO_TIMEOUT_SECONDS": "rendezvous rides jax.distributed",
}


def _env_algorithm() -> str:
    from horovod_tpu.overlap import ALGORITHMS
    v = (os.environ.get("HOROVOD_ALLREDUCE_ALGORITHM", "auto")
         .strip().lower() or "auto")
    if v not in ALGORITHMS:
        raise ValueError(
            f"HOROVOD_ALLREDUCE_ALGORITHM={v!r}: expected one of "
            f"{ALGORITHMS}")
    return v


def _env_wire() -> str:
    from horovod_tpu.overlap import WIRES
    v = os.environ.get("HOROVOD_ALLREDUCE_WIRE", "").strip().lower()
    if v in ("", "none", "off"):
        return "fp32"
    if v not in WIRES:
        raise ValueError(
            f"HOROVOD_ALLREDUCE_WIRE={v!r}: expected one of {WIRES}")
    return v


def _env_topology() -> Optional[str]:
    v = os.environ.get("HOROVOD_TOPOLOGY", "").strip().lower()
    if not v:
        return None
    from horovod_tpu.parallel.mesh import parse_topology
    dims = parse_topology(v)   # grammar check: a typo'd spec fails here
    return "x".join(str(d) for d in dims)


def _env_mesh() -> Optional[str]:
    v = os.environ.get("HOROVOD_MESH", "").strip().lower()
    if not v:
        return None
    from horovod_tpu.parallel.mesh import format_mesh, parse_mesh
    dp, mp = parse_mesh(v)   # grammar check: a typo'd spec fails here
    # World/topology fit is validated at init() (needs devices).
    return format_mesh(dp, mp)


_MP_RULE_SETS = ("auto", "megatron", "off")


def _env_mp_rules() -> str:
    v = (os.environ.get("HOROVOD_MP_RULES", "auto").strip().lower()
         or "auto")
    if v not in _MP_RULE_SETS:
        raise ValueError(
            f"HOROVOD_MP_RULES={v!r}: expected one of {_MP_RULE_SETS}")
    return v


def _env_chunks() -> int:
    v = os.environ.get("HOROVOD_OVERLAP_CHUNKS")
    if not v:
        from horovod_tpu.overlap import DEFAULT_CHUNKS
        return DEFAULT_CHUNKS
    try:
        n = int(v)
    except ValueError:
        raise ValueError(
            f"HOROVOD_OVERLAP_CHUNKS={v!r}: expected a positive integer")
    if n < 1:
        raise ValueError(
            f"HOROVOD_OVERLAP_CHUNKS={n}: chunk count must be >= 1")
    return n


def _env_posint(name: str, default: int) -> int:
    v = os.environ.get(name)
    if not v:
        return default
    try:
        n = int(v)
    except ValueError:
        raise ValueError(f"{name}={v!r}: expected a positive integer")
    if n < 1:
        raise ValueError(f"{name}={n}: must be >= 1")
    return n


def _env_nonneg_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if not v:
        return default
    try:
        n = int(v)
    except ValueError:
        raise ValueError(f"{name}={v!r}: expected a non-negative integer")
    if n < 0:
        raise ValueError(f"{name}={n}: must be >= 0")
    return n


def _env_posfloat(name: str, default: float) -> float:
    x = _env_float(name, default)
    if x <= 0:
        raise ValueError(f"{name}={x:g}: must be > 0")
    return x


def _env_nonneg_float(name: str, default: float) -> float:
    x = _env_float(name, default)
    if x < 0:
        raise ValueError(f"{name}={x:g}: must be >= 0")
    return x


def _env_kv_quant() -> str:
    v = os.environ.get("HOROVOD_SERVE_KV_QUANT", "").strip().lower()
    if v in ("", "none", "off", "0"):
        return ""
    if v not in ("int8", "fp8"):
        raise ValueError(f"HOROVOD_SERVE_KV_QUANT={v!r}: expected "
                         f"'int8', 'fp8', or unset")
    return v


_SPEC_PROPOSERS = ("ngram",)


def _env_spec_proposer() -> str:
    v = (os.environ.get("HOROVOD_SERVE_SPEC_PROPOSER", "ngram")
         .strip().lower() or "ngram")
    if v not in _SPEC_PROPOSERS:
        raise ValueError(f"HOROVOD_SERVE_SPEC_PROPOSER={v!r}: expected "
                         f"one of {_SPEC_PROPOSERS}")
    return v


_SERVE_ROLES = ("prefill", "decode", "both")

#: migration wire formats — "" follows the pool storage dtype.
_KV_WIRE_FORMATS = ("", "fp32", "bf16", "int8", "fp8")


def _env_serve_role() -> str:
    v = (os.environ.get("HOROVOD_SERVE_ROLE", "both").strip().lower()
         or "both")
    if v not in _SERVE_ROLES:
        raise ValueError(f"HOROVOD_SERVE_ROLE={v!r}: expected one of "
                         f"{_SERVE_ROLES}")
    return v


def _env_kv_wire() -> str:
    v = os.environ.get("HOROVOD_SERVE_KV_WIRE", "").strip().lower()
    if v in ("", "none", "off", "0"):
        return ""
    if v not in _KV_WIRE_FORMATS:
        raise ValueError(f"HOROVOD_SERVE_KV_WIRE={v!r}: expected one of "
                         f"'fp32', 'bf16', 'int8', 'fp8', or unset "
                         f"(follow the KV pool's storage dtype)")
    return v


def _env_serve_affinity() -> str:
    v = (os.environ.get("HOROVOD_SERVE_AFFINITY", "auto").strip().lower()
         or "auto")
    if v in ("1", "true", "yes"):
        v = "on"
    elif v in ("0", "false", "no"):
        v = "off"
    if v not in ("auto", "on", "off"):
        raise ValueError(f"HOROVOD_SERVE_AFFINITY={v!r}: expected "
                         f"'auto', 'on', or 'off'")
    return v


def _env_serve_transport() -> str:
    v = (os.environ.get("HOROVOD_SERVE_TRANSPORT", "stream")
         .strip().lower() or "stream")
    if v not in ("stream", "legacy"):
        raise ValueError(f"HOROVOD_SERVE_TRANSPORT={v!r}: expected "
                         f"'stream' (persistent multiplexed v2 wire) or "
                         f"'legacy' (one-shot JSON RPC)")
    return v


def _env_auth_token() -> str:
    # Shared secret for the transport hello handshake. Validated for
    # plausibility here but NEVER echoed: error messages and build_info
    # must not leak the value.
    v = os.environ.get("HOROVOD_SERVE_AUTH_TOKEN", "").strip()
    if v and len(v) < 8:
        raise ValueError("HOROVOD_SERVE_AUTH_TOKEN: token too short "
                         "(need >= 8 characters; value not shown)")
    return v


def _env_metrics_port() -> int:
    v = os.environ.get("HOROVOD_METRICS_PORT", "").strip().lower()
    if not v:
        return 0
    if v == "auto":
        return -1          # ephemeral bind; status RPC advertises the port
    try:
        n = int(v)
    except ValueError:
        raise ValueError(f"HOROVOD_METRICS_PORT={v!r}: expected a port "
                         f"number, 'auto', or unset")
    if n < 0:
        raise ValueError(f"HOROVOD_METRICS_PORT={n}: must be >= 0 "
                         f"(or 'auto')")
    return n


_DUMP_ON_TOKENS = ("signal", "stall", "alert", "engine", "fault")


def _env_dump_on() -> str:
    v = os.environ.get("HOROVOD_BLACKBOX_DUMP_ON")
    if v is None or not v.strip():
        return ",".join(_DUMP_ON_TOKENS)
    if v.strip().lower() in ("none", "off"):
        return ""
    toks = [t.strip().lower() for t in v.split(",") if t.strip()]
    bad = sorted(set(toks) - set(_DUMP_ON_TOKENS))
    if bad:
        raise ValueError(
            f"HOROVOD_BLACKBOX_DUMP_ON: unknown trigger(s) {bad}; "
            f"choose from {', '.join(_DUMP_ON_TOKENS)} (or 'none')")
    return ",".join(dict.fromkeys(toks))


def _env_fault_plan() -> str:
    v = os.environ.get("HOROVOD_FAULT_PLAN", "").strip()
    if v:
        from horovod_tpu.faults import parse_plan
        parse_plan(v)   # grammar check: a bad plan fails here, at init
    return v


def refresh() -> Config:
    """Re-read ``HOROVOD_*`` from the environment (called by ``init()``)."""
    global _CONFIG
    cfg = Config(
        fusion_threshold_bytes=_env_bytes("HOROVOD_FUSION_THRESHOLD",
                                          64 * _MB),
        allreduce_algorithm=_env_algorithm(),
        allreduce_wire=_env_wire(),
        overlap_chunks=_env_chunks(),
        topology=_env_topology(),
        mesh=_env_mesh(),
        mp_rules=_env_mp_rules(),
        timeline_path=os.environ.get("HOROVOD_TIMELINE") or None,
        timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES"),
        autotune=_env_bool("HOROVOD_AUTOTUNE"),
        autotune_log=os.environ.get("HOROVOD_AUTOTUNE_LOG") or None,
        autotune_mode=(os.environ.get("HOROVOD_AUTOTUNE_MODE", "ladder")
                       .strip().lower() or "ladder"),
        autotune_probes=int(_env_float("HOROVOD_AUTOTUNE_PROBES", 6)),
        autotune_samples=int(_env_float("HOROVOD_AUTOTUNE_SAMPLES", 10)),
        metrics_file=os.environ.get("HOROVOD_METRICS_FILE") or None,
        metrics_interval_seconds=max(
            0.05, _env_float("HOROVOD_METRICS_INTERVAL", 10.0)),
        metrics_grad_norm=_env_bool("HOROVOD_METRICS_GRAD_NORM"),
        stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE"),
        stall_check_time_seconds=_env_float(
            "HOROVOD_STALL_CHECK_TIME_SECONDS", 60.0),
        profile_on_stall=_env_bool("HOROVOD_PROFILE_ON_STALL"),
        profile_dir=(os.environ.get("HOROVOD_PROFILE_DIR")
                     or "/tmp/horovod_profile"),
        profile_seconds=max(
            0.1, _env_float("HOROVOD_PROFILE_SECONDS", 5.0)),
        profile_max_captures=_env_posint(
            "HOROVOD_PROFILE_MAX_CAPTURES", 2),
        profiler_cost=(None if os.environ.get("HOROVOD_PROFILER_COST",
                                              "").strip() == ""
                       else _env_bool("HOROVOD_PROFILER_COST")),
        serve_slots=_env_posint("HOROVOD_SERVE_SLOTS", 8),
        serve_max_len=_env_posint("HOROVOD_SERVE_MAX_LEN", 512),
        serve_block_size=_env_posint("HOROVOD_SERVE_BLOCK_SIZE", 16),
        serve_queue_limit=_env_posint("HOROVOD_SERVE_QUEUE_LIMIT", 128),
        serve_prefill_chunk=_env_posint("HOROVOD_SERVE_PREFILL_CHUNK", 8),
        serve_kv_quant=_env_kv_quant(),
        serve_prefix_cache=_env_bool("HOROVOD_SERVE_PREFIX_CACHE"),
        serve_spec_k=_env_nonneg_int("HOROVOD_SERVE_SPEC_K", 0),
        serve_spec_proposer=_env_spec_proposer(),
        serve_heartbeat_seconds=max(
            0.1, _env_float("HOROVOD_SERVE_HEARTBEAT", 2.0)),
        serve_rpc_timeout_seconds=_env_posfloat(
            "HOROVOD_SERVE_RPC_TIMEOUT", 5.0),
        serve_role=_env_serve_role(),
        serve_kv_wire=_env_kv_wire(),
        serve_affinity=_env_serve_affinity(),
        serve_transport=_env_serve_transport(),
        serve_auth_token=_env_auth_token(),
        serve_max_retries=_env_nonneg_int(
            "HOROVOD_SERVE_MAX_RETRIES", 3),
        serve_hedge_ms=_env_nonneg_float("HOROVOD_SERVE_HEDGE_MS", 0.0),
        serve_breaker_failures=_env_posint(
            "HOROVOD_SERVE_BREAKER_FAILURES", 3),
        serve_breaker_reset_seconds=_env_posfloat(
            "HOROVOD_SERVE_BREAKER_RESET", 1.0),
        serve_fleet_restart_budget=_env_nonneg_int(
            "HOROVOD_SERVE_FLEET_RESTART_BUDGET", 5),
        serve_fleet_backoff_seconds=_env_posfloat(
            "HOROVOD_SERVE_FLEET_BACKOFF", 0.5),
        serve_fleet_backoff_cap_seconds=_env_posfloat(
            "HOROVOD_SERVE_FLEET_BACKOFF_CAP", 10.0),
        serve_fleet_crash_loop_k=_env_posint(
            "HOROVOD_SERVE_FLEET_CRASH_LOOP_K", 3),
        serve_fleet_crash_loop_window_seconds=_env_posfloat(
            "HOROVOD_SERVE_FLEET_CRASH_LOOP_WINDOW", 30.0),
        serve_fleet_probe_seconds=_env_posfloat(
            "HOROVOD_SERVE_FLEET_PROBE", 0.5),
        serve_fleet_spares=_env_nonneg_int(
            "HOROVOD_SERVE_FLEET_SPARES", 0),
        serve_fleet_prefill=_env_nonneg_int(
            "HOROVOD_SERVE_FLEET_PREFILL", 0),
        serve_fleet_prefill_spares=_env_nonneg_int(
            "HOROVOD_SERVE_FLEET_PREFILL_SPARES", 0),
        request_trace=_env_bool("HOROVOD_REQUEST_TRACE"),
        request_trace_dir=os.environ.get("HOROVOD_REQUEST_TRACE_DIR")
        or None,
        request_trace_decode_every=_env_posint(
            "HOROVOD_REQUEST_TRACE_DECODE_EVERY", 16),
        metrics_port=_env_metrics_port(),
        health_interval_seconds=max(
            0.05, _env_float("HOROVOD_HEALTH_INTERVAL", 2.0)),
        health_window_seconds=_env_posfloat("HOROVOD_HEALTH_WINDOW", 30.0),
        health_fire_n=_env_posint("HOROVOD_HEALTH_FIRE_N", 2),
        health_clear_m=_env_posint("HOROVOD_HEALTH_CLEAR_M", 2),
        health_alerts_file=os.environ.get("HOROVOD_HEALTH_ALERTS_FILE")
        or None,
        fleet_scrape_interval_seconds=_env_posfloat(
            "HOROVOD_FLEET_SCRAPE_INTERVAL", 1.0),
        slo_ttft_p99_ms=_env_nonneg_float("HOROVOD_SLO_TTFT_P99_MS", 0.0),
        slo_error_rate=_env_nonneg_float("HOROVOD_SLO_ERROR_RATE", 0.0),
        slo_burn_threshold=_env_posfloat("HOROVOD_SLO_BURN_THRESHOLD", 2.0),
        blackbox=_env_bool("HOROVOD_BLACKBOX"),
        blackbox_seconds=_env_posfloat("HOROVOD_BLACKBOX_SECONDS", 120.0),
        blackbox_dir=os.environ.get("HOROVOD_BLACKBOX_DIR") or None,
        blackbox_max_bundles=_env_posint(
            "HOROVOD_BLACKBOX_MAX_BUNDLES", 8),
        blackbox_dump_on=_env_dump_on(),
        faulthandler_enable=_env_bool("HOROVOD_FAULTHANDLER", True),
        elastic_timeout_seconds=_env_float("HOROVOD_ELASTIC_TIMEOUT", 600.0),
        preemption_notice_seconds=max(
            0.0, _env_float("HOROVOD_PREEMPTION_NOTICE", 30.0)),
        fault_plan=_env_fault_plan(),
        barrier_timeout_seconds=max(
            1.0, _env_float("HOROVOD_BARRIER_TIMEOUT", 600.0)),
        config_ledger_file=os.environ.get("HOROVOD_CONFIG_LEDGER") or None,
        config_experiment_window_seconds=_env_posfloat(
            "HOROVOD_CONFIG_EXPERIMENT_WINDOW", 10.0),
        config_revert_on_regression=_env_bool(
            "HOROVOD_CONFIG_REVERT_ON_REGRESSION"),
        log_level=os.environ.get("HOROVOD_LOG_LEVEL", "warning").lower(),
        inert={k: reason for k, reason in _INERT_VARS.items()
               if os.environ.get(k)},
    )
    prev, _CONFIG = _CONFIG, cfg

    if prev is not None:
        # A refresh() after init must not silently change resolved
        # values: route every knob diff through the config bus so env
        # mutations and hvd.set_config share one audit trail (WARN +
        # config_epoch bump + ledger entry per changed knob).
        try:
            from horovod_tpu import confbus
            confbus.note_refresh(prev, cfg)
        except Exception:
            pass   # auditing must never turn refresh() into a crash

    import logging
    level = {"trace": logging.DEBUG, "debug": logging.DEBUG,
             "info": logging.INFO, "warning": logging.WARNING,
             "error": logging.ERROR, "fatal": logging.CRITICAL}.get(
                 cfg.log_level, logging.WARNING)
    logging.getLogger("horovod_tpu").setLevel(level)
    return cfg


def get_config() -> Config:
    """The active configuration (reads the environment on first use)."""
    return _CONFIG if _CONFIG is not None else refresh()
