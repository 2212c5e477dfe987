# Developer entry points. The native library has its own Makefile (cpp/).

PY ?= python

.PHONY: trace-smoke overlap-smoke serve-smoke doctor-smoke quant-smoke \
	preempt-smoke topo-smoke net-smoke fleet-smoke prefix-smoke \
	mp-smoke reqtrace-smoke config-smoke fleet-top postmortem \
	chip-smoke test native

# Cross-rank tracing smoke: 2 CPU processes with HOROVOD_TIMELINE shards,
# merged via hvd.merge_timelines; exits nonzero if the merged trace is
# invalid JSON, the straggler report is empty, or the NEGOTIATE/QUEUE/EXEC
# phases of a collective don't share one op-id across ranks. Also runs in
# tier-1 as tests/test_trace_merge.py::TestTwoProcessSmoke.
trace-smoke:
	$(PY) tools/trace_smoke.py

# Overlapped gradient-sync smoke: 2 CPU processes run the same tiny train
# loop with the monolithic psum and the chunked RS+AG pipeline and must
# land on identical parameters on every rank. Also runs in tier-1 as
# tests/test_overlap.py::TestTwoProcessSmoke.
overlap-smoke:
	$(PY) tools/overlap_smoke.py

# Multi-replica serving smoke: 2 CPU replica processes share a request
# spool, overlapping streaming requests land on both, one replica is
# SIGKILLed mid-stream, and the survivor must reclaim its orphaned
# claims (stale heartbeat) and drain the whole queue. Also runs in
# tier-1 as tests/test_serving.py::TestTwoProcessSmoke.
serve-smoke:
	$(PY) tools/serve_smoke.py

# Doctor smoke: 2 CPU processes with a manufactured 750ms straggler and a
# forced recompile (static arg change); hvd.doctor() over the merged trace
# + fused metrics snapshots must rank both — the straggler naming rank 1,
# the recompile naming the blamed argument. Also runs in tier-1 as
# tests/test_doctor.py::TestTwoProcessSmoke.
doctor-smoke:
	$(PY) tools/doctor_smoke.py

# Quantized-wire smoke: 2 CPU processes allreduce the same payload on the
# exact fp32 wire and the block-quantized int8 wire; every rank must hold
# byte-identical dequantized results, the quantized value must sit inside
# the int8 block error bound, and allreduce_wire_bytes_total must show a
# >= 3x wire-byte reduction. Also runs in tier-1 as
# tests/test_quantized_and_sharded.py::TestTwoProcessQuantSmoke.
quant-smoke:
	$(PY) tools/quant_smoke.py

# Preemption smoke: 2 CPU worker processes + 1 hot spare; rank 1 is
# SIGKILLed mid-epoch by HOROVOD_FAULT_PLAN, the launcher promotes the
# spare into the dead rank's slot, and the relaunched world restores from
# the last published sharded manifest. Exits nonzero unless recovery is
# within 2 steps of the kill, every resumed loss BIT-matches an
# uninterrupted golden run, and hvd.doctor() reports the measured
# recovery time as a ranked finding. Also runs in tier-1 as
# tests/test_checkpoint_sharded.py::TestTwoProcessPreemptSmoke.
preempt-smoke:
	$(PY) tools/preempt_smoke.py

# Network-transport serving smoke: 3 socket replicas (JSON-over-TCP,
# serving/transport.py), one SIGKILLed at its 8th RPC and one partitioned
# for 2s by HOROVOD_FAULT_PLAN; every request must reach a typed terminal
# state within its deadline (retries + circuit breakers + failover
# resubmission route around the faults), identical prompts must decode
# identically wherever they land, and hvd.doctor() must rank the breaker
# event. Also runs in tier-1 as tests/test_transport.py::TestNetSmoke.
net-smoke:
	$(PY) tools/net_smoke.py

# Topology smoke: 4 CPU processes simulate a 2x2 torus
# (HOROVOD_TOPOLOGY=2x2) and allreduce the same payload through
# rs_ag_2d / chunked_rs_ag_2d / swing / rs_ag_2d_int8; every rank must
# hold byte-identical results, each schedule must match psum, and the
# per-phase wire-byte legs must be observable. Also runs in tier-1 as
# tests/test_topology.py::TestFourProcessTopoSmoke.
topo-smoke:
	$(PY) tools/topo_smoke.py

# Self-healing fleet smoke: 3 socket replicas + 1 warm spare under a
# FleetSupervisor; HOROVOD_FAULT_PLAN SIGKILLs one replica twice
# (restart-with-backoff must bring it back), crash-loops another into a
# typed quarantine (the spare is promoted into its slot), and partitions
# a third for 2s (tolerated, no spurious restart). Then a rolling
# drain/restart of every live replica runs mid-load with zero dropped
# requests. All assertions come from the metrics snapshot, and
# hvd.doctor() must rank the quarantine. Also runs in tier-1 as
# tests/test_fleet.py::TestFleetSmoke.
fleet-smoke:
	$(PY) tools/fleet_smoke.py

# Shared-prefix + speculative-decode smoke: a high-overlap batch through
# two GPT-2 engines (prefix cache + spec lane on vs both off); asserts
# the shared preamble prefills once ever (index hit/reuse counters +
# per-request prefix_tokens), copy-on-write fires for a capped
# full-prefix match, token parity with offline greedy for all three
# families (T5 auto-disables sharing), a leak-free pool after drain, and
# spec acceptance > 0 with decode_compiles == 1. Also runs in tier-1 as
# tests/test_prefix.py::TestPrefixSmoke.
prefix-smoke:
	$(PY) tools/prefix_smoke.py

# dp×mp mesh smoke: 2 CPU processes on a dp=1×mp=2 named mesh
# (HOROVOD_MESH=dp1xmp2). ZeRO-3 GPT-2 training bit-exact in fp32 vs the
# 1-proc replicated baseline, tensor-parallel serving token-identical to
# offline generate() with decode_compiles == 1 (prefix cache + spec lane
# on) and per-rank param bytes <= 0.55x replicated. Also runs in tier-1
# as tests/test_mp.py::TestTwoProcessMpSmoke.
mp-smoke:
	$(PY) tools/mp_smoke.py

# Request-tracing smoke: 2 socket replicas + a hedging dispatcher, all
# writing request-trace shards (HOROVOD_REQUEST_TRACE=1). Replica 0 is
# rigged slow (busy single lane + a delay@...space=net on the traced
# submit) so the hedge fires and replica 1 wins; the merged trace must
# stitch one trace_id across all three processes, the requestReport
# breakdown must sum to the measured TTFT within 10%, and
# tools/tail_doctor.py must blame rank0's hedge wait. Also runs in
# tier-1 as tests/test_reqtrace.py::TestReqtraceSmoke.
reqtrace-smoke:
	$(PY) tools/reqtrace_smoke.py

# Config-bus smoke: 2 socket replicas under a FleetSupervisor with a
# shared auth token. apply_config(HEDGE_MS) must fan out fleet-wide
# with the driver and both replica audit ledgers agreeing on the epoch;
# a shape-affecting SERVE_SLOTS mutation is refused with a typed
# reason; an injected bad RPC_TIMEOUT mutation spikes retries, is
# measured `regressed`, auto-reverted (revert guard), and fires the
# doctor's config_regression alert — with decode_compiles==1 and token
# parity vs offline generate() held across all mutations. Also runs in
# tier-1 as tests/test_confbus.py::TestConfigSmoke.
config-smoke:
	$(PY) tools/config_smoke.py

# One frame of the fleet health dashboard (hvd.top): per-replica
# UP/QPS/TTFT_P99/SLOTS/BLOCKS/BREAKER from scraped /metrics.json
# windows, plus active alerts. Pass MEMBERS=/path/to/members.json to
# follow a live fleet's membership file; without it the local process
# registry is sampled. Drop --once (run the tool directly) for a live
# refreshing dashboard.
fleet-top:
	$(PY) tools/fleet_top.py --once $(if $(MEMBERS),--membership $(MEMBERS))

# Offline root-cause analysis of the newest flight-recorder bundle
# (HOROVOD_BLACKBOX): ranked findings from the crash-time events ring,
# the bundled metrics window (offline doctor), the pre-death alert tail
# and the queue trend. Pass BUNDLE=/path/to/postmortem-... to analyze a
# specific bundle, DIR=/path/to/blackbox to search elsewhere. Exit 2
# means a confident root cause was identified.
postmortem:
	$(PY) tools/postmortem.py $(BUNDLE) $(if $(DIR),--dir $(DIR))

# Bring-up proof on a machine with a TPU (fails without one): kernels,
# trainer and server at GPT-2 medium width, checked against the
# references; four-chip phases when four chips are present.
chip-smoke:
	$(PY) chip_smoke.py

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

native:
	$(MAKE) -C cpp
