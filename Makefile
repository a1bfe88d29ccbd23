# Repo-level entry points; the native build lives in flexflow_tpu/native.
PYTHON ?= python

.PHONY: native check lint trace-smoke test bench-smoke fault-smoke \
	budget-smoke elastic-smoke preempt-smoke rejoin-smoke fusion-smoke \
	serve-smoke fleet-smoke loadtest-smoke disagg-smoke fleetsim-smoke \
	searchscale-smoke chaos-smoke

# build the native simulator + dataloader libraries
native:
	$(MAKE) -C flexflow_tpu/native

# native build + ctypes smoke of ffsim_simulate, plus repo consistency:
# every injectable fault kind must be documented in README.md's fault
# table and covered by at least one test (tools/check_fault_kinds.py),
# and every FFConfig CLI flag must be accepted by the LM/NMT parsers and
# forwarded through their model configs (tools/check_flag_forwarding.py),
# every emitted obs record kind must be rendered by obs/report.py and
# covered by a test (tools/check_obs_kinds.py), and the static strategy
# verifier must come up clean (lint)
check: lint fusion-smoke serve-smoke disagg-smoke chaos-smoke fleet-smoke loadtest-smoke fleetsim-smoke searchscale-smoke
	$(PYTHON) tools/check_fault_kinds.py
	$(PYTHON) tools/check_flag_forwarding.py
	$(PYTHON) tools/check_obs_kinds.py
	env JAX_PLATFORMS=cpu $(PYTHON) tools/check_strategies.py
	$(MAKE) -C flexflow_tpu/native check

# per-fusion residual account smoke (round 13, jax-free): `report
# fusions` against the committed roofline profiles must uphold the
# account invariants — rows + unattributed sum to the compute residual
# within 1%, every top-10 row verdicted (no unknowns), stable JSON
# schema — and the shipped consumer (add_any -> grad_fanout) must carry
# its recorded roofline-predicted saving
fusion-smoke:
	$(PYTHON) -m flexflow_tpu.apps.report fusions \
	examples/profiles/inception_v3_roofline.json \
	examples/profiles/alexnet_roofline.json --json \
	| $(PYTHON) -c "import json,sys; d=json.loads(sys.stdin.read()); \
	assert d['violations'] == [], d['violations']; \
	a = d['accounts'][0]; \
	assert a['schema'] == 'fusion_account_v1', a['schema']; \
	assert abs(sum(r['excess_ms'] for r in a['rows']) \
	+ a['unattributed_ms'] - a['residual_ms']) \
	<= 0.01 * a['residual_ms'], 'rows do not sum to residual'; \
	assert all(r['verdict'] in ('fusable','pallas_worthy','irreducible') \
	for acc in d['accounts'] for r in acc['rows']), 'unverdicted row'; \
	kinds = {r.get('rewrite') for acc in d['accounts'] \
	for r in acc['rows'] if r.get('predicted_win_ms') is not None}; \
	assert {'grad_fanout'} <= kinds, kinds; \
	print('fusion-smoke ok:', {'residual_ms': round(a['residual_ms'],2), \
	'top3_frac': round(a['top3_frac'],4), \
	'unattributed_ms': round(a['unattributed_ms'],2)})"

# static verification (README "Static verification"): repo-wide python
# lint (ruff when installed, pinned-subset stdlib fallback otherwise)
# plus the three-pass compile-time strategy verifier — source/jaxpr/HLO
# sync-freedom, donation/retrace, and the predicted-time grounded-accept
# audit of the example strategy — on the 8-device virtual mesh
lint:
	$(PYTHON) tools/repo_lint.py
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.lint alexnet --devices 8 \
	--ici-group 4 --strategy examples/strategies/alexnet_2x4.json

# build libffsim.so and assert ffsim_simulate_trace produces a parseable
# Chrome/Perfetto trace for a toy graph (obs/trace.py --smoke)
trace-smoke:
	$(MAKE) -C flexflow_tpu/native trace-smoke

# the tier-1 test selection (CPU, 8-device virtual mesh)
test:
	$(PYTHON) -m pytest tests/ -q -m 'not slow'

# tiny-config bench asserting the metric line carries the round-6
# execution-performance fields (regrid planner hop count + prefetch
# stall residual) and the mixed-precision round's policy fields
# (param_dtype / placed_overlap) — a schema check on the CPU, not a perf
# number: JAX_PLATFORMS=cpu so it can never take the chip, and the
# explicit --cpu-rehearsal so bench.py does not refuse the platform
# (the metric is then named cpu_rehearsal_*)
bench-smoke:
	JAX_PLATFORMS=cpu \
	BENCH_MODEL=alexnet BENCH_BATCH=16 BENCH_ITERS=2 BENCH_WARMUP=1 \
	BENCH_WINDOWS=1 BENCH_DTYPE=float32 BENCH_PARAM_DTYPE=bfloat16 \
	$(PYTHON) bench.py --cpu-rehearsal \
	| $(PYTHON) -c "import json,sys; rec=json.loads(sys.stdin.readline()); \
	assert rec['metric'].startswith('cpu_rehearsal_'), rec; \
	assert rec['device']['platform'] == 'cpu', rec; \
	assert 'mfu' not in rec, rec; \
	assert 'regrid_hops' in rec and 'input_stall_s' in rec, rec; \
	assert 'comm_frac' in rec and 'stall_frac' in rec, rec; \
	assert rec['param_dtype'] == 'bfloat16', rec; \
	assert rec['placed_overlap'] == 'on', rec; \
	assert 'hlo_fingerprint' in rec, rec; \
	assert rec.get('donated_bytes', 0) > 0, rec; \
	assert rec['residual_top_frac'] is not None, rec; \
	print('bench-smoke ok:', {k: rec[k] for k in \
	('metric','device','regrid_hops','input_stall_s','comm_frac', \
	'stall_frac','param_dtype','placed_overlap', \
	'hlo_fingerprint','donated_bytes','residual_top_frac')})"

# deterministic fault-injection smoke (robustness round): loss_nan +
# data_io injected into a tiny HDF5-fed run with --on-divergence
# rollback; asserts the run completes with fault -> rollback -> recovery
# obs records and a finite final loss, and that the guard is byte-inert
# on a healthy run
fault-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m flexflow_tpu.apps.fault_smoke

# elastic-runtime smoke (elastic round + re-expansion round):
# equivalence phase (elastic + watchdog + drain handler enabled, no
# faults: bit-identical to baseline) + lifecycle phase (injected
# device loss shrinks the 8-device simulated mesh to 6 mid-run, then
# the injected device_return grows it back 6 -> 8 after the probe
# streak: exactly two elastic_resize records — one per direction —
# finite losses to completion, and a verified async-committed final
# checkpoint)
elastic-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.elastic_smoke

# preemption-drain smoke (re-expansion round): a subprocess run with
# preempt@5 injected must finish the in-flight step, commit a verified
# checkpoint through the async writer inside --drain-budget-s, emit one
# preempt_drain record, and EXIT 0 (the scheduler contract); a fresh
# resume from the drained checkpoint must be bit-equal to the
# uninterrupted baseline's tail
preempt-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.preempt_smoke

# real 2-process elastic_rejoin smoke (env-gated: skips with the reason
# unless FF_REJOIN_SMOKE=1 — spawning real coordinator services is slow
# and port-sensitive): two fresh worker processes reconnect to the
# coordinator, form the 8-device world, and restore a verified
# checkpoint onto the rejoined mesh
rejoin-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m flexflow_tpu.apps.rejoin_smoke

# serving-runtime smoke (serve/ round): equivalence phase (batching on
# vs off must give bit-identical replies) + autoscale lifecycle phase
# (gap-then-burst load: exactly one 8->6 idle shrink and one 6->8
# queue-depth grow, zero dropped, finite latencies, `report serve`
# renders the latency histogram from the fresh obs dir); stdout is
# exactly one JSON record, asserted like bench-smoke
serve-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.serve --smoke \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert rec['resizes'] == 2, rec; \
	assert rec['dropped'] == 0 and rec['unserved'] == 0, rec; \
	assert math.isfinite(rec['p50_s']) and math.isfinite(rec['p99_s']), rec; \
	assert rec['completed'] == rec['requests'] > 0, rec; \
	assert rec['devices'] == 8, rec; \
	print('serve-smoke ok:', {k: rec[k] for k in \
	('completed','qps','p50_s','p99_s','resizes','devices')})"

# disaggregated-serving smoke (prefill/decode round): two 2-device
# prefill replicas + one 4-device decode pool behind the router on the
# 8-device CPU mesh, serving a seeded multi-turn session load; the smoke
# itself asserts routed replies bit-identical to the single-pool engine,
# >= 1 KV handoff and >= 1 session-affinity hit with zero refetches, a
# clean mid-run drain (in-flight prefills hand off and finish, queued
# work reported unserved), a validated Perfetto trace with the router
# lanes, and a rendered `report serve`; stdout is one JSON record
disagg-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.serve --disagg-smoke \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert rec['completed'] == rec['requests'] == 12, rec; \
	assert rec['unserved'] == 0 and rec['dropped'] == 0, rec; \
	assert rec['devices'] == 8, rec; \
	assert math.isfinite(rec['p50_s']) and math.isfinite(rec['p99_s']), rec; \
	print('disagg-smoke ok:', {k: rec[k] for k in \
	('completed','qps','p50_s','p99_s','devices')})"

# serving-resilience smoke (chaos round): two phases on a 2x2dev
# prefill + 2x2dev decode carve of the 8-device CPU mesh.  Equivalence:
# the armed resilience stack (installed injector with an EMPTY spec,
# RetryPolicy, AdmissionGate) must be byte-inert — replies and summary
# counters bit-identical to a plain router and the single-pool engine.
# Recovery: the seeded spec replica_crash@3 + handoff_drop@5 kills a
# decode replica and drops a KV transfer, and every admitted request
# must still complete with bit-identical replies via >= 1 kv_rebuild,
# exactly 1 replica_down, >= 2 serve_retry records, zero
# unserved/failed/shed — nothing silently lost — with a validated
# Perfetto trace and a rendered resilience report; stdout is one JSON
# record, exit 0
chaos-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.serve --chaos-smoke \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert rec['completed'] == rec['requests'] == 12, rec; \
	assert rec['unserved'] == 0 and rec['dropped'] == 0, rec; \
	assert rec['devices'] == 8, rec; \
	assert math.isfinite(rec['p50_s']) and math.isfinite(rec['p99_s']), rec; \
	print('chaos-smoke ok:', {k: rec[k] for k in \
	('completed','qps','p50_s','p99_s','devices')})"

# sustained-load harness smoke (serving observability round): a small
# deterministic device-count sweep of the patterned load generator
# through the engine; asserts exactly one bench-convention JSON stdout
# line (metric/value/unit/vs_baseline), finite TTFT/TPOT/p50/p99, the
# SLO burn rate present, >= 3 sweep points, a validated Perfetto trace,
# and a written serve_bench_v1 artifact matching the metric line (the
# committed SERVE_r01.json is the same harness at full size)
loadtest-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.loadtest --smoke \
	--out /tmp/ff-loadtest-smoke.json \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert all(k in rec for k in \
	('metric','value','unit','vs_baseline')), rec; \
	assert rec['unit'] == 'req/s', rec; \
	assert all(math.isfinite(rec[k]) for k in \
	('value','p50_s','p99_s','ttft_p50_s','ttft_p99_s','tpot_p50_s', \
	'burn_rate','goodput_qps')), rec; \
	assert rec['sweep_points'] >= 3, rec; \
	assert rec['trace_validated'] is True, rec; \
	art=json.load(open(rec['out'])); \
	assert art['schema'] == 'serve_bench_v1', art; \
	assert art['parsed']['metric'] == rec['metric'] \
	and art['parsed']['value'] == rec['value'], art['parsed']; \
	assert len(art['sweep']) == rec['sweep_points'], art; \
	assert all(math.isfinite(p[k]) for p in art['sweep'] for k in \
	('qps','p50_s','p99_s','ttft_p50_s','tpot_p50_s','goodput_qps')), art; \
	print('loadtest-smoke ok:', {k: rec[k] for k in \
	('metric','value','vs_baseline','sweep_points','p99_s', \
	'ttft_p50_s','burn_rate','trace_validated')})"
	$(PYTHON) -c "import json; \
	art=json.load(open('SERVE_r02.json')); \
	assert art['schema'] == 'serve_bench_v1' and art['disagg'] is True, art; \
	vs=art['vs_r01']; \
	assert vs['baseline'] == 'SERVE_r01.json', vs; \
	pts=vs['points']; \
	assert all(pts[d]['ttft_p99_speedup'] > 1.0 for d in ('2','4')), pts; \
	assert all(pts[d]['goodput_ratio'] > 1.0 for d in ('2','4')), pts; \
	print('loadtest-smoke: SERVE_r02 vs_r01 ok:', {d: \
	{'ttft_p99_speedup': pts[d]['ttft_p99_speedup'], \
	'goodput_ratio': pts[d]['goodput_ratio']} for d in ('2','4')})"

# multi-tenant fleet smoke (fleet/ round): two jobs on the 8-device
# simulated pool trade devices mid-run — training job A shrinks 6->4
# while serving job B's queue burst grows it 2->4, then the trade
# reverses when B's queue drains; asserts both jobs finish with finite
# bit-sane results, exactly two fleet_rebalance records each followed
# by its two directed elastic_resize records, zero fault records, and
# an arbiter packing that reproduces under the fixed seed; stdout is
# exactly one JSON record, asserted like bench-smoke
fleet-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.fleet --smoke \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert rec['rebalances'] == 2, rec; \
	assert rec['jobs'] == rec['done'] == 2 and rec['failed'] == 0, rec; \
	assert math.isfinite(rec['train_final_loss']), rec; \
	assert rec['serve_completed'] == 20 and rec['serve_unserved'] == 0, rec; \
	print('fleet-smoke ok:', {k: rec[k] for k in \
	('jobs','done','rebalances','packs','native_prices', \
	'train_final_loss','serve_completed')})"

# trace-driven fleet-simulation smoke (round 18, jax-free): a seeded
# day of synthetic jobs through the REAL coordinator/arbiter in
# virtual time — asserts one JSON stdout line, the first sweep point
# bit-identical across two in-process runs (repro), the fleet_util
# device-second invariant upheld at EVERY round of every point
# (util_violations == 0 or the harness itself exits non-zero), a
# validated lifecycle Perfetto trace, finite wait percentiles, and a
# fleet_bench_v1 artifact matching the metric line
fleetsim-smoke:
	$(PYTHON) -m flexflow_tpu.apps.fleetsim --smoke \
	--out /tmp/ff-fleetsim-smoke.json \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert all(k in rec for k in \
	('metric','value','unit','vs_baseline')), rec; \
	assert rec['unit'] == 'frac', rec; \
	assert 0.0 < rec['value'] <= 1.0, rec; \
	assert rec['repro'] is True, rec; \
	assert rec['util_violations'] == 0, rec; \
	assert rec['trace_validated'] is True, rec; \
	assert all(math.isfinite(rec[k]) for k in \
	('value','wait_p50_s','wait_p99_s')), rec; \
	art=json.load(open(rec['out'])); \
	assert art['schema'] == 'fleet_bench_v1', art; \
	assert art['parsed']['metric'] == rec['metric'] \
	and art['parsed']['value'] == rec['value'], art['parsed']; \
	assert len(art['points']) == rec['sweep_points'] >= 2, art; \
	assert all(p['util_violations'] == 0 for p in art['points']), art; \
	assert all(p['jobs_done'] + p['jobs_failed'] <= p['jobs'] \
	for p in art['points']), art; \
	print('fleetsim-smoke ok:', {k: rec[k] for k in \
	('metric','value','vs_baseline','sweep_points','wait_p99_s', \
	'rebalances','repro','trace_validated')})"

# decomposed-search smoke (round 19): tiny 4-layer graph on the 8-device
# virtual mesh, searched flat AND decomposed at the same proposal budget
# — proves the stitch passes the plan gate, the shared-block memo hits,
# and the deterministic payload is bit-identical across two runs; the
# second block re-validates the committed SEARCH_r01.json (schema,
# finiteness, and the acceptance pins: decomposed >= 1.15x vs DP AND
# strictly better than flat on the 1.3b headline row, memo hits on
# every multi-layer row)
searchscale-smoke:
	env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m flexflow_tpu.apps.searchscale --smoke \
	| $(PYTHON) -c "import json,math,sys; \
	rec=json.loads(sys.stdin.readline()); \
	assert sys.stdin.readline() == '', 'stdout must be one JSON line'; \
	assert all(k in rec for k in \
	('metric','value','unit','vs_baseline')), rec; \
	assert rec['unit'] == 'x_vs_dp', rec; \
	assert math.isfinite(rec['value']) and rec['value'] >= 1.0, rec; \
	assert rec['repro'] is True, rec; \
	assert rec['memo_hits'] >= 1, rec; \
	assert rec['plan_gate_clean'] is True, rec; \
	assert rec['unique_blocks'] < rec['blocks'], rec; \
	print('searchscale-smoke ok:', {k: rec[k] for k in \
	('metric','value','vs_baseline','blocks','unique_blocks', \
	'memo_hits','repro')})"
	$(PYTHON) -c "import json,math; \
	art=json.load(open('SEARCH_r01.json')); \
	assert art['schema'] == 'searchscale_bench_v1', art; \
	assert art['seed'] == 0, art; \
	assert art['parsed']['unit'] == 'x_vs_dp', art; \
	rows={r['size']: r for r in art['rows']}; \
	head=rows[art['headline']]; \
	assert head['params'] > 1_000_000_000, head['params']; \
	assert head['decomposed']['speedup_vs_dp'] >= 1.15, head; \
	assert head['decomposed']['best_time_s'] \
	< head['flat']['best_time_s'], head; \
	assert art['parsed']['value'] \
	== head['decomposed']['speedup_vs_dp'], art['parsed']; \
	assert all(r['decomposed']['memo_hits'] >= 1 for r in art['rows'] \
	if r['layers'] >= 3), rows.keys(); \
	assert all(r['decomposed']['plan_gate_clean'] for r in art['rows']); \
	assert all(math.isfinite(r[k]) for r in art['rows'] for k in \
	('dp_time_s',)), art; \
	assert all(math.isfinite(r[g][k]) and r[g][k] > 0 \
	for r in art['rows'] for g in ('flat','decomposed') \
	for k in ('best_time_s','speedup_vs_dp')), art; \
	print('searchscale-smoke: SEARCH_r01 ok:', \
	{'headline': art['headline'], \
	'speedup_vs_dp': head['decomposed']['speedup_vs_dp'], \
	'vs_flat': head['decomposed_vs_flat'], \
	'memo_hits': head['decomposed']['memo_hits'], \
	'sizes': [r['size'] for r in art['rows']]})"

# MFU-waterfall smoke (observability): tiny CNN with sampled op timing +
# live metrics export; asserts the step_budget bucket invariant, a
# rendered waterfall from the fresh obs dir, finite throughput gauges
# in the Prometheus textfile (mfu only on a TPU), and validated Perfetto
# counter lanes
budget-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m flexflow_tpu.apps.budget_smoke
