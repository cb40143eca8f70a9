#!/usr/bin/env bash
# One-shot CI gate: static analysis + analysis self-test + a fast
# tier-1 smoke subset.  Everything here must stay green on every
# commit; the full tier-1 suite (ROADMAP.md) remains the merge gate.
#
#   tools/ci_check.sh            # run everything
#   SMOKE=0 tools/ci_check.sh    # lint + selfcheck only
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== nomadlint: repo-wide run (36 rules, zero findings) =="
python -m tools.nomadlint

echo "== nomadlint: selfcheck (every rule trips its bad fixture) =="
python -m tools.nomadlint --selfcheck

if [ "${SMOKE:-1}" = "1" ]; then
    echo "== tier-1 smoke subset =="
    # the analysis layer's own tests + the TSAN soak + one
    # pipeline-parity file: fast (<2 min), catches wiring breaks;
    # NOT a substitute for the full tier-1 run
    JAX_PLATFORMS=cpu python -m pytest -q \
        -p no:cacheprovider -m 'not slow' \
        tests/test_nomadlint.py \
        tests/test_flowgraph.py \
        tests/test_tsan.py \
        tests/test_stage_accounting.py

    echo "== cluster chaos smoke (3 servers, leader kills + partition) =="
    # leadership-loss gate: zero lost evals / zero duplicate
    # placements vs the fault-free oracle across repeated leader
    # kills and a healed partition; the coreutils timeout kills a
    # wedged cluster so a failover deadlock fails the gate instead
    # of hanging it
    timeout -k 10 300 python -m nomad_tpu.raft.chaos_smoke \
        --jobs 150 --kills 5 --nodes 6

    echo "== follower fan-out bench (1 vs 3 servers, scaled down) =="
    # horizontal-scaling gate: the same storm workload through a
    # 1-server and a 3-server fan-out cluster — zero lost evals,
    # placement-set parity vs the single-server oracle, fan-out
    # actually engaged (follower plans > 0), no leaked remote
    # leases.  Scaled below the BENCH acceptance run (which asserts
    # the >=2x 3v1 speedup at 12x24x512); the kill-timeout fails a
    # wedged cluster instead of hanging the gate
    timeout -k 10 300 python -m nomad_tpu.server.fanout_bench \
        --servers 1,3 --families 120 --jobs-per 1 --nodes 256 \
        --reps 1

    echo "== cluster chaos smoke with fan-out (3 servers) =="
    # leadership-loss gate UNDER fan-out: followers plan through 3
    # leader kills + a healed partition — remote leases die with
    # each leadership, redelivery reclaims them, and the replicated
    # generation fence rejects deposed-leader plans; zero lost, zero
    # duplicates vs the fault-free oracle
    timeout -k 10 300 python -m nomad_tpu.raft.chaos_smoke \
        --jobs 120 --kills 3 --nodes 6 --fanout

    echo "== swarm overload + mass-death SLO smoke (scaled down) =="
    # the overload-graceful control-plane gate: heartbeat storm +
    # concurrent submitters over the real HTTP API with an injected
    # mass node-death — zero lost evals, zero false node-downs,
    # hb >=99.9%, <=2 storm solves, bounded sheds.  Scaled below the
    # acceptance run (2200/1100/500, exercised by bench) to fit the
    # CI budget; the kill-timeout fails a wedged swarm instead of
    # hanging the gate
    timeout -k 10 300 python -m nomad_tpu.loadgen.swarm_smoke \
        --nodes 600 --submitters 240 --death 120 --ttl 8 \
        --base-jobs 150

    echo "== geo federation smoke (2 regions x 3 servers + kill drill) =="
    # the geo-plane gate: a Multiregion job federated both ways with
    # placement parity vs per-region single-region oracles, zero WAN
    # reads for region-local traffic (?region= escape hatch asserted
    # to count), shed submitters redirected to the healthy region
    # within the SLO, and the full region-kill drill — all three east
    # servers dark at once, zero lost evals in west, failed-over
    # submitters landing via their cached retry-region hint, east
    # re-federating after the heal.  The kill-timeout fails a wedged
    # geo plane instead of hanging the gate
    timeout -k 10 300 python -m nomad_tpu.loadgen.geo_smoke \
        --flood-submitters 96 --redirect-slo 20

    echo "== policy-weighted scoring A/B (scaled down) =="
    # the policy-layer gate: heterogeneity-aware throughput must pull
    # placements onto fast nodes and migration-cost stickiness must
    # cut mass-replan churn at equal-or-better aggregate binpack
    # score, both A/B'd against NOMAD_TPU_POLICY=0 on the same world.
    # Scaled below the BENCH acceptance run (which also asserts the
    # <3% identity-weights kernel overhead at f32 — too noisy to gate
    # on a shared CI box); the kill-timeout fails a wedged world
    timeout -k 10 600 env JAX_PLATFORMS=cpu BENCH_POLICY_C=1024 \
        BENCH_POLICY_KERNEL_REPS=40 BENCH_POLICY_NODES=90 \
        BENCH_POLICY_JOBS=24 python -c "
import bench
out = bench.bench_policy()
assert out['throughput']['fast_share_gain'] > 0.2, out['throughput']
assert out['migration']['fewer_migrations'], out['migration']
assert out['migration']['score_delta'] >= 0.0, out['migration']
print('policy gate green:', {
    'fast_share_gain': out['throughput']['fast_share_gain'],
    'migrations_avoided': out['migration']['migrations_avoided'],
    'score_delta': out['migration']['score_delta'],
})
"

    echo "== cluster observability gate (stitching + fan-in, scaled) =="
    # the cluster-scope observability gate: the fan-out workload with
    # the flight recorder A/B'd on/off — trace overhead within the
    # <5% contract (with the unit gate's additive slack), stitched
    # cross-server traces actually produced (spans from >=2 servers
    # on one leader-side waterfall), zero orphan spans, the leader
    # fan-in query answering at 1/3/5 servers, and the metric
    # history ring capped at its configured depth.  Scaled below the
    # BENCH acceptance run; the kill-timeout fails a wedged cluster
    timeout -k 10 600 env JAX_PLATFORMS=cpu BENCH_OBS_FAMILIES=48 \
        BENCH_OBS_NODES=128 BENCH_OBS_REPS=1 python -c "
import bench
out = bench.bench_cluster_obs()
assert out['overhead_ok'], out
assert out['stitched_traces_min'] > 0, out
assert out['orphan_spans'] == 0, out
assert len(out['fanin_query_latency']) == 3, out
assert out['history_ring']['windows'] == 60, out
print('cluster-obs gate green:', {
    'overhead_pct': out['stitched_overhead_pct'],
    'stitched_min': out['stitched_traces_min'],
    'fanin_ms': out['fanin_query_latency'],
})
"

    echo "== control-loop flight-data gate (ledger A/B + site coverage) =="
    # the flight-data gate: decision-ledger overhead A/B within the
    # <3% contract (with the additive slack every overhead gate uses
    # on this shared box), every registered decision site writing
    # records under the swarm + admission-probe + fan-out soak (the
    # decision-ledger lint's non-vacuity proof), and the SLO engine
    # grading a real history ring.  The placement A/B is scaled down;
    # the swarm runs at the same scale as the swarm gate above
    timeout -k 10 600 env JAX_PLATFORMS=cpu BENCH_SLO_NODES=100 \
        BENCH_SLO_JOBS=12 BENCH_SLO_REPS=1 \
        BENCH_SLO_FANOUT_NODES=96 BENCH_SLO_FANOUT_FAMILIES=24 \
        python -c "
import bench
out = bench.bench_slo()
assert out['overhead_ok'], out
assert not out['sites_missing'], out
assert out['swarm_ok'], out
assert len(out['slo_status']['objectives']) >= 5, out
print('slo gate green:', {
    'ledger_overhead_pct': out['ledger_overhead_pct'],
    'sites': sorted(out['site_records']),
    'worst': out['slo_status']['worst'],
})
"

    echo "== 2-process distributed smoke (CPU backend, gloo) =="
    # the multi-host mesh gate: distributed init, pod-mesh chain with
    # zero lost evals, per-host O(dirty rows) cross-host flush, and
    # the sharded storm solve bit-identical to single-device — the
    # launcher kills a deadlocked world at the timeout, so a
    # collective hang fails the gate instead of wedging it
    python -m nomad_tpu.parallel.dist_smoke --procs 2 --timeout 360

    echo "== composed bigworld smoke (fan-out followers x pod mesh) =="
    # the composed-topology gate at reduced scale: a 3-server cluster
    # seeded via the seed_world FSM command, every follower heading a
    # 2-process jax.distributed pod, schedulers ONLY on the fan-out
    # followers — zero lost evals, placement-set parity vs the
    # single-server oracle, pod digest parity on every mesh launch
    # (POD_CHECK), and a killed follower+peer pair catching back up
    # from the dirty-row log.  Scaled well below the BENCH acceptance
    # run (>=1M nodes / >=10M allocs); the kill-timeout fails a
    # wedged world instead of hanging the gate
    timeout -k 10 1800 python -m nomad_tpu.loadgen.bigworld_smoke \
        --nodes 128 --allocs 1024 --jobs 2 --storm-jobs 8 \
        --timeout 900
fi

echo "ci_check: all green"
