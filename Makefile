# seaweedfs_tpu delivery loop

.PHONY: test stress chaos chaos-ha chaos-geo race protos lint metrics-lint swtpu-lint crashsim

# lint and the crash-state matrix run FIRST so a concurrency-rule,
# exposition-grammar or durability regression fails the default path
# before the suite spends minutes; the suite itself includes the
# cluster.check-against-mini-cluster smoke (tests/test_health.py) so
# health regressions fail tier-1 too
test: lint crashsim
	python -m pytest tests/ -q

# static analysis gate: the repo-specific AST rules (blocking calls in
# async bodies, I/O under locks, wall-clock durations, silenced
# exceptions, unjoined threads, FIPS-fatal md5, context-dropping
# executor hops — devtools/swtpu_lint.py) plus the metrics registry
# lint. `swtpu-lint --json` is the machine-readable mode CI archives.
lint: swtpu-lint metrics-lint

swtpu-lint:
	python -m seaweedfs_tpu.devtools.swtpu_lint seaweedfs_tpu

metrics-lint:
	python -m seaweedfs_tpu.stats.expo_lint

# crash-consistency gate (devtools/crashsim.py): record every fs op a
# real write path performs (utils/fstrack.py shim), enumerate the legal
# ext4-data=ordered crash states (dropped un-fsynced suffixes, torn
# final writes, un-pinned renames), and run the REAL recovery + invariant
# driver on each — acked needles readable, no torn needle served, the
# .vif seal implies synced shards, committed raft entries survive, the
# filer meta log recovers an exact prefix. >= 500 distinct states across
# the volume/ec/raft/filer surfaces or the gate fails; the static mirror
# of the same contract is swtpu-lint's ack-before-fsync /
# rename-no-dir-fsync / vif-write-bypass rules
crashsim:
	JAX_PLATFORMS=cpu python -m seaweedfs_tpu.devtools.crashsim --artifact CRASHSIM.json --min-states 500

# race/stress harness with artifact (tests/stress/run_stress.py);
# bounded ~60s total at 6 s/scenario on an idle box
stress:
	python tests/stress/run_stress.py $${TMPDIR:-/tmp}/STRESS.json 6

# the stress suite under the runtime lock-order/race detector
# (utils/locktrack.py): every threading.Lock/RLock/Condition is wrapped,
# ABBA ordering cycles and >100ms holds are reported at process exit
# and via /debug/locks on every daemon
race:
	SWTPU_LOCKCHECK=1 python tests/stress/run_stress.py $${TMPDIR:-/tmp}/STRESS_race.json 6

# randomized fault schedules against a live mini-cluster (opt-in gate
# like stress); bounded time, failing runs print their seed — replay with
# SWTPU_CHAOS_SEED=<seed> make chaos. The last schedule kills a replica
# holder for good and asserts the health-driven repair loop alone
# converges the verdict back to OK (no manual ec.rebuild/fix.replication).
# Runs with the lock-order detector on: the chaos conftest asserts the
# session ends with zero ordering cycles.
chaos:
	SWTPU_CHAOS=1 SWTPU_LOCKCHECK=1 python -m pytest tests/chaos -q

# HA control-plane chaos lane only: a 3-master raft quorum under >= 3
# leader kill/restart cycles mid-lease-window (bulk + single-put
# writers live throughout). Asserts every acked write readable, zero
# duplicate fids across elections (the sequencer high-water mark rides
# the raft log), breakers re-close, the maintenance cron resumes on
# each NEW leader and never sweeps on followers, and the lock-order
# detector ends the session with zero cycles. Part of `make chaos`
# (tests/chaos discovery); this target runs just the HA lane.
chaos-ha:
	SWTPU_CHAOS=1 SWTPU_LOCKCHECK=1 python -m pytest tests/chaos/test_chaos_ha.py -q

# geo chaos lane only: sever one DC of a 2-DC in-process cluster
# mid-storm (every cross-DC link drops), assert acked reads keep
# serving from the surviving DC, the health-driven repair converges
# after the partition heals within the cross-DC byte budget, the
# geo-replication lag gauge returns under its policy bound, the
# verdict returns to OK, and the lock-order detector ends with zero
# cycles. Part of `make chaos` (tests/chaos discovery).
chaos-geo:
	SWTPU_CHAOS=1 SWTPU_LOCKCHECK=1 python -m pytest tests/chaos/test_chaos_geo.py -q

protos:
	python -m seaweedfs_tpu.pb.build
