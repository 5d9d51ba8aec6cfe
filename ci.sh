#!/usr/bin/env bash
# CI driver — the `./setup test` analogue (reference: setup + cmake + ctest).
#
#   ./ci.sh            fast tier: full suite minus the slow mid-scale tier
#   ./ci.sh all        everything, including 512–1024-host parity
#   ./ci.sh smoke      config + events + ckpt/obs/telemetry + tune + digest
#                      + txn fast paths (tgen-based tune tests stay in
#                      fast/all), plus a tiny tpu-vs-cpu paritytrace bisect
#                      on the rung-1 config: inject a window-8 corruption,
#                      assert the flight recorder localizes it to exactly
#                      window 8; plus the fault-plane smokes: a shortened
#                      churn-scenario cpu-vs-tpu digest parity run
#                      (churnprobe) and corrupt-checkpoint rejection
#                      (integrity digest); plus the overflow-policy smokes:
#                      an under-capped run under --on-overflow retry must
#                      bit-match its big-cap twin's digest stream, and
#                      --on-overflow halt must exit 4 with paste-ready
#                      cap advice (CapacityExceededError); plus the
#                      perf-attribution smokes: the multi-row bench gate
#                      (dense/sparse/fleet ms-per-round vs BENCH_GATE.json),
#                      the opcensus eqn-drift gate (must trip on an
#                      injected extra-op build) and a phaseprobe
#                      attribution with >=90% coverage; plus the fleet
#                      recovery smokes: fleetprobe --retry (under-capped
#                      sweep retry == big-cap fleet per-lane digests,
#                      cpu+tpu), a 3-trial chaosprobe fleet matrix
#                      (kill-anywhere under forced overflow retry +
#                      forced-lane-halt quarantine), and the quarantined
#                      lane's checkpoint resuming solo bit-identically;
#                      plus the serve-plane smokes: a real daemon round
#                      trip (sequential same-shape jobs -> engine-cache
#                      hit with no recompile, over-budget submission
#                      rejected pre-compile with advice, served digest
#                      streams bit-matching solo CLI runs, SIGTERM drain)
#                      and a kill-during-submit chaos pair (no torn spool
#                      records, restart completes bit-identically); plus
#                      the flow-probe smokes: the watched-flow probe
#                      stream on the rung-1 config must be bit-identical
#                      cpu-vs-tpu, and the flowreport stall detectors
#                      must pass their synthetic self-test; plus the
#                      link-telemetry smokes: the rung-1 per-edge link
#                      records must be bit-identical cpu-vs-tpu with the
#                      drop columns reconciling against the global
#                      counters, the netreport weathermap detectors must
#                      pass their synthetic self-test, and the opcensus
#                      gate doubles as the proof that --link-telem off
#                      (the default) adds zero traced ops
#
# Tests force the CPU platform with 8 virtual devices (tests/conftest.py),
# so CI needs no accelerator; the TPU-hardware path is covered separately
# by chip_smoke.py (fails without a chip) and tests/test_backend_parity.py
# (slow tier; skips where the default platform is the CPU).
set -euo pipefail
cd "$(dirname "$0")"

tier="${1:-fast}"
case "$tier" in
  smoke)
    python -m pytest tests/test_bringup.py tests/test_config.py tests/test_events.py tests/test_rng.py tests/test_ckpt_obs.py tests/test_telemetry.py tests/test_tune.py tests/test_digest.py tests/test_txn.py tests/test_fleet.py tests/test_fleet_recover.py tests/test_preempt.py tests/test_perfobs.py tests/test_serve.py tests/test_probes.py tests/test_pcap.py tests/test_links.py -q -m "not slow" -k "not tgen"
    echo "== paritytrace bisect smoke (rung-1, injected corruption) =="
    # CPU platform like the pytest tiers (conftest forces it there; the
    # tool inherits the env) — the smoke must not depend on an accelerator.
    out=$(JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.paritytrace \
          configs/rung1_filexfer.yaml tpu cpu \
          --windows 16 --chunk 8 --inject 8:rng --no-localize 2>/dev/null) && rc=0 || rc=$?
    [ "$rc" -eq 3 ] || { echo "paritytrace: expected divergence exit 3, got $rc" >&2; exit 1; }
    echo "$out" | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])["first_divergence"]
assert d == {"window": 8, "subsystems": ["rng"]}, d
print("paritytrace localized the injected corruption to", d)
'
    echo "== churn-scenario parity smoke (fault plane, cpu vs tpu) =="
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.churnprobe \
        configs/churn_filexfer.yaml --sides cpu,tpu --windows 40 --chunk 20 \
        2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"], d
assert d["digest_windows_compared"]["tpu"] == 40, d
print("churnprobe: 40-window digest parity ok;",
      "restarts:", d["counters"]["tpu"]["host_restarts"],
      "down_pkts:", d["counters"]["tpu"]["down_pkts"])
'
    echo "== overflow-retry parity smoke (txn plane) =="
    # A deliberately under-capped PHOLD run under --on-overflow retry must
    # (a) actually retry, (b) produce a digest stream bit-identical to the
    # same config run straight at the final (grown) caps; plus one halt
    # exit-code check (CapacityExceededError → exit 4, advice on stderr).
    of_cfg=$(mktemp /tmp/shadow1_of_XXXX.yaml)
    cat > "$of_cfg" <<'YAML'
general: {seed: 5, stop_time: 40 ms}
engine: {scheduler: tpu, ev_cap: 8}
network: {single_vertex: {latency: 1 ms}}
hosts:
  - {name: h, count: 8}
app:
  model: phold
  params: {mean_delay_ns: 2000000.0, init_events: 6}
YAML
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - "$of_cfg" <<'EOF'
import sys
import shadow1_tpu
from shadow1_tpu.ckpt import run_chunked
from shadow1_tpu.config.experiment import load_experiment
from shadow1_tpu.consts import EngineParams
from shadow1_tpu.core.digest import DIGEST_FIELDS
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.telemetry.ring import drain_ring
from shadow1_tpu.txn import OverflowGuard
import dataclasses

exp, params, _ = load_experiment(sys.argv[1])
params = dataclasses.replace(params, metrics_ring=10, state_digest=1)

def stream(eng, guard=None):
    rows, start = {}, [0]
    def on_chunk(st, _d):
        for r in drain_ring(st, eng.window, start=start[0]):
            if r["type"] == "ring":
                rows[r["window"]] = tuple(r[f] for f in DIGEST_FIELDS)
        start[0] = int(st.metrics.windows)
    st = run_chunked(eng, n_windows=40, chunk=10, guard=guard,
                     on_chunk=on_chunk)
    return rows, st

eng = Engine(exp, params)
guard = OverflowGuard(eng, make_engine=lambda p: Engine(exp, p), mode="retry")
rows_retry, st = stream(eng, guard)
assert guard.chunk_retries >= 1, "under-capped config did not retry"
assert int(st.metrics.ev_overflow) == 0, "committed stream must be clean"
big = guard.final_caps["ev_cap"]
rows_big, _ = stream(Engine(exp, dataclasses.replace(params, ev_cap=big)))
assert rows_retry == rows_big, "retry digest stream != big-cap twin"
print(f"overflow retry: {guard.chunk_retries} chunk(s) replayed, "
      f"ev_cap 8 -> {big}, 40-window digest parity with the big-cap twin")
EOF
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu "$of_cfg" \
        --on-overflow halt >/dev/null 2>/tmp/_of_halt.log && rc=0 || rc=$?
    [ "$rc" -eq 4 ] || { echo "halt: expected CapacityExceededError exit 4, got $rc" >&2; exit 1; }
    grep -q "Paste-ready fix" /tmp/_of_halt.log || { echo "halt: advice missing" >&2; exit 1; }
    echo "halt: exit 4 with paste-ready cap advice ok"
    rm -f "$of_cfg" /tmp/_of_halt.log
    echo "== fleet digest-parity smoke (3-experiment sweep vs solo, cpu+tpu) =="
    # A 3-experiment fleet (seed change, loss-rate change, churn schedule)
    # run as ONE vmapped program: every lane's per-window digest stream
    # must be bit-identical to running that experiment alone, on both the
    # solo batched engine and the cpu oracle (the fleet contract,
    # docs/SEMANTICS.md).
    fl_cfg=$(mktemp /tmp/shadow1_fl_XXXX.yaml)
    cat > "$fl_cfg" <<'YAML'
general: {seed: 7, stop_time: 80 ms}
engine: {scheduler: tpu, ev_cap: 32, outbox_cap: 16}
network: {single_vertex: {latency: 10 ms}}
hosts:
  - {name: h, count: 8}
app:
  model: phold
  params: {mean_delay_ns: 2.0e7, init_events: 2}
sweep:
  seeds: [7, 8, 9]
  vary:
    - {}
    - {network: {single_vertex: {loss: 0.05}}}
    - {faults: {hosts: [{group: h, down_at: 30 ms, up_at: 60 ms}]}}
YAML
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.fleetprobe \
        "$fl_cfg" --sides tpu,cpu 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"], d
assert d["experiments"] == 3, d
assert d["streams_compared"] == {"tpu": 3, "cpu": 3}, d
print("fleetprobe: 3 experiments x", d["windows"],
      "windows bit-identical fleet<->solo on tpu and cpu sides")
'
    rm -f "$fl_cfg"
    echo "== fleet recovery smoke (transactional retry + lane quarantine) =="
    # The PR 13 acceptance gates. (1) fleetprobe --retry: a deliberately
    # under-capped sweep under --on-overflow retry must actually retry and
    # every lane's committed digest stream must bit-match the straight
    # big-cap fleet run (tpu side) AND the eager oracle at the final caps
    # (cpu side) — the PR 5 solo proof, fleet-wide.
    fr_cfg=$(mktemp /tmp/shadow1_fr_XXXX.yaml)
    cat > "$fr_cfg" <<'YAML'
general: {seed: 5, stop_time: 40 ms}
engine: {scheduler: tpu, ev_cap: 8}
network: {single_vertex: {latency: 1 ms}}
hosts:
  - {name: h, count: 8}
app:
  model: phold
  params: {mean_delay_ns: 2000000.0, init_events: 6}
sweep:
  seeds: [5, 6, 7]
YAML
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.fleetprobe \
        "$fr_cfg" --retry --windows 20 --json-only 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"], d
assert d["chunk_retries"] >= 1, d
assert d["mismatches"] == [], d
print("fleetprobe --retry:", d["chunk_retries"], "chunk(s) replayed,",
      "final caps", d["final_caps"], "- per-lane digest parity with the",
      "big-cap fleet (tpu) and the oracle (cpu)")
'
    # (2) Fleet-recovery chaos matrix (3 trials total): kill-anywhere +
    # forced-overflow retry (2 trials), then forced-lane-halt quarantine
    # (1 trial) — each relaunched to completion and bit-compared per
    # surviving lane against the straight run; the quarantine trial must
    # slice out exactly lane 1 on both sides.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.chaosprobe \
        "$fr_cfg" --fleet --extra "--on-overflow retry" \
        --windows 40 --chunk 10 --trials 2 --seed 1 --json-only 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"] and d["trials"] == 2, d
print("chaosprobe fleet-retry matrix:", d["trials"],
      "kill trials bit-identical under forced overflow retry")
'
    fq_cfg=$(mktemp /tmp/shadow1_fq_XXXX.yaml)
    cat > "$fq_cfg" <<'YAML'
general: {seed: 5, stop_time: 40 ms}
engine: {scheduler: tpu, ev_cap: 8}
network: {single_vertex: {latency: 1 ms}}
hosts:
  - {name: h, count: 8}
app:
  model: phold
  params: {mean_delay_ns: 2000000.0, init_events: 6}
sweep:
  seeds: [5, 6, 7]
  vary:
    - {network: {single_vertex: {loss: 0.5}}}
    - {}
    - {network: {single_vertex: {loss: 0.5}}}
YAML
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.chaosprobe \
        "$fq_cfg" --fleet --extra "--on-overflow halt --on-lane-fail quarantine" \
        --expect-quarantine 1 --windows 40 --chunk 10 --trials 1 --seed 2 \
        --json-only 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"] and d["trials"] == 1, d
assert d["quarantined"] == [1], d
print("chaosprobe quarantine matrix: lane 1 quarantined, sweep completed",
      "2/3, kill trial bit-identical")
'
    # (3) The quarantined lane's sliced checkpoint must resume SOLO: run
    # the quarantine sweep once (quarantine snapshots land beside the
    # --ckpt path), then load its .q1 snapshot into the solo engine.
    fq_dir=$(mktemp -d /tmp/shadow1_fqd_XXXX)
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu \
        "$fq_cfg" --fleet --on-overflow halt --on-lane-fail quarantine \
        --windows 20 --ckpt "$fq_dir/q.npz" --supervised-child \
        >"$fq_dir/q.out" 2>/dev/null
    qck="$fq_dir/q.npz.q1.npz"
    [ -f "$qck" ] || { echo "quarantine ckpt $qck missing" >&2; exit 1; }
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - "$fq_cfg" "$qck" <<'EOF'
import json, sys
import shadow1_tpu
from shadow1_tpu.ckpt import load_state
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.fleet.expand import load_sweep
import numpy as np

plan = load_sweep(sys.argv[1])
exp, params = plan.exps[1], plan.params
solo = Engine(exp, params)
lane = load_state(solo.init_state(), sys.argv[2])
w0 = int(np.asarray(lane.win_start)) // solo.window
st = solo.run(lane, n_windows=20 - w0)
straight = Engine(exp, params).run(n_windows=20)
assert Engine.metrics_dict(st) == Engine.metrics_dict(straight)
print(f"quarantined-lane ckpt resumed solo from window {w0}: final "
      f"metrics bit-match the straight solo run")
EOF
    rm -rf "$fr_cfg" "$fq_cfg" "$fq_dir"
    echo "== preemption smoke (SIGTERM drain + kill-anywhere chaos trials) =="
    # SIGTERM mid-run must commit the in-flight chunk, write a final
    # snapshot and exit the documented preempted code (consts.py taxonomy);
    # rerunning the same command must resume, not restart.
    pre_ck=$(mktemp -u /tmp/shadow1_pre_XXXX.npz)
    window_ns=$(JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -c '
import shadow1_tpu
from shadow1_tpu.config.experiment import load_experiment
print(load_experiment("configs/rung1_filexfer.yaml")[0].window)')
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" SHADOW1_SUPERVISE_BACKOFF_S=0 \
        SHADOW1_OBS_SIGTERM_SELF_AT_NS=$((20 * window_ns)) \
        python -m shadow1_tpu configs/rung1_filexfer.yaml --windows 40 \
        --heartbeat 10 --ckpt-every-s 0 --ckpt "$pre_ck" \
        >/tmp/_pre_drain.out 2>/dev/null && rc=0 || rc=$?
    exp_rc=$(python -c 'from shadow1_tpu.consts import EXIT_PREEMPTED; print(EXIT_PREEMPTED)')
    [ "$rc" -eq "$exp_rc" ] || { echo "drain: expected EXIT_PREEMPTED=$exp_rc, got $rc" >&2; exit 1; }
    python -c '
import json
rec = json.loads(open("/tmp/_pre_drain.out").read().strip().splitlines()[-1])
assert rec["preempted"] is True and rec["signal"] == "SIGTERM", rec
assert rec["win_start"] > 0, rec
print("drain: EXIT_PREEMPTED with parseable record at sim_ns", rec["win_start"])
'
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" SHADOW1_SUPERVISE_BACKOFF_S=0 \
        python -m shadow1_tpu configs/rung1_filexfer.yaml --windows 40 \
        --heartbeat 10 --ckpt-every-s 0 --ckpt "$pre_ck" \
        >/tmp/_pre_resume.out 2>/dev/null
    python -c '
import json
out = json.loads(open("/tmp/_pre_resume.out").read().strip().splitlines()[-1])
assert out["resumed"] is True, out
print("drain: rerun resumed from the preemption snapshot")
'
    rm -f /tmp/_pre_drain.out /tmp/_pre_resume.out "$pre_ck"*
    # Kill-anywhere chaos trials (tools/chaosprobe.py): the first three
    # trial kinds are the deterministic special ones — a mid-run SIGTERM
    # drain, a torn-head mid-checkpoint-write kill, and a corrupt-head
    # lineage fallback — each must end bit-identical to the straight run.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.chaosprobe \
        configs/rung1_filexfer.yaml --windows 40 --chunk 10 --trials 3 \
        --seed 1 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"], d
assert d["trials"] == 3, d
assert d["preempted_exits"] >= 1, d
assert d["lineage_fallbacks"] >= 1, d
print("chaosprobe:", d["trials"], "kill trials bit-identical;",
      d["preempted_exits"], "drain(s),", d["lineage_fallbacks"],
      "lineage fallback(s)")
'
    echo "== memory-plane smoke (pre-flight budget + sub-batch parity) =="
    # The deliberately oversubscribed config must be rejected BEFORE any
    # compile with the dedicated memory exit code, a parseable stdout
    # record and per-plane advice (shadow1_tpu/mem.py; the budget comes
    # from the env override — the CPU backend reports no device memory).
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" SHADOW1_MEM_BYTES=$((8<<30)) \
        python -m shadow1_tpu configs/mem_overbudget.yaml \
        >/tmp/_mem_ob.out 2>/tmp/_mem_ob.err && rc=0 || rc=$?
    exp_rc=$(python -c 'from shadow1_tpu.consts import EXIT_MEMORY; print(EXIT_MEMORY)')
    [ "$rc" -eq "$exp_rc" ] || { echo "mem: expected EXIT_MEMORY=$exp_rc, got $rc" >&2; exit 1; }
    python -c '
import json
d = json.loads(open("/tmp/_mem_ob.out").read().strip().splitlines()[-1])
assert d["error"] == "memory_budget", d
assert d["estimated"] > d["budget"], d
assert d["planes"]["evbuf"] > (16 << 30), d
assert "Remedies" in d["advice"], d
print("mem: pre-flight rejected", round(d["estimated"]/2**30, 1),
      "GiB estimate before compile, advice block present")
'
    grep -q "MemoryBudgetError" /tmp/_mem_ob.err || { echo "mem: stderr advice missing" >&2; exit 1; }
    rm -f /tmp/_mem_ob.out /tmp/_mem_ob.err
    # Sub-batched-fleet == full-fleet bit-exactness (the --on-oom
    # downshift contract): per-lane digest streams and parity counters
    # must be identical when the sweep runs as sequential sub-batches.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.memprobe \
        configs/sweep_phold.yaml --subbatch --sub 3 --windows 16 \
        --json-only 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"], d
sb = d["subbatch"]
assert sb["experiments"] == 4 and sb["streams_compared"] == 4, sb
print("memprobe: 4-lane sweep sub-batched (3+1) bit-identical per lane,",
      sb["windows"], "windows")
'
    echo "== serve-plane smoke (daemon round-trip: cache hit + admission + digest parity + resilience) =="
    # The serve acceptance gates (ISSUE 14 / docs/SEMANTICS.md §"Serving
    # contract"), all in one probe: spawn a real daemon on CPU, submit two
    # same-shape jobs SEQUENTIALLY (second batch must be an engine-cache
    # HIT — no re-trace, no recompile), submit one over-budget job (must
    # be rejected pre-compile with the memory_budget advice record and
    # EXIT_MEMORY while the others run), bit-compare both completed jobs'
    # digest streams against solo CLI runs, and SIGTERM-drain the daemon
    # (EXIT_SERVE_SHUTDOWN). --resilience then runs a SECOND daemon under
    # a squeezed budget (ISSUE 19): one tenant parks in waiting_headroom
    # and later completes bit-exact, a depth-2 queue rejects the fourth
    # submit with queue_full + retry_after_s advice, a --queue-ttl-s
    # tenant expires with deadline_expired, and an injected transient
    # crash is retried to a bit-exact finish.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.serveprobe \
        configs/serve_phold.yaml --seeds 5,6 \
        --overbudget configs/mem_overbudget.yaml --mem-bytes $((8<<30)) \
        --resilience --json-only 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"], d
assert d["jobs"] == 2 and d["cache_hits"] >= 1, d
assert d["rejected_overbudget"] is True, d
assert all(n >= 40 for n in d["windows_compared"].values()), d
r = d["resilience"]
assert r["waiting_headroom"] and r["queue_full"], r
assert r["queue_ttl_expired"] and r["transient_retried"], r
assert r["bit_exact_jobs"] == 3, r
print("serveprobe: 2 jobs bit-identical to solo,", d["cache_hits"],
      "cache hit(s) (no recompile), over-budget job rejected with advice,",
      "daemon drained rc", d["shutdown_rc"])
print("serveprobe --resilience: waiting_headroom + queue_full +",
      "queue-TTL expiry + transient retry,", r["bit_exact_jobs"],
      "jobs bit-identical over", r["windows_compared"], "windows")
'
    # Kill-anywhere chaos for the serve plane: SIGKILL the daemon and
    # assert NO torn spool record (the write_json_atomic / atomic-move
    # contract), restart, and every surviving job must complete
    # bit-identical to the solo run. Beyond the two random-offset kills
    # (covering mid-accept), three aimed kills land at the resilience
    # states of ISSUE 19: a tenant parked in waiting_headroom, a batch
    # inside its retry-backoff window, and just after a queue-TTL expiry
    # (whose terminal deadline_expired record must survive the restart).
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.chaosprobe \
        configs/serve_phold.yaml --serve 5 --seed 3 \
        --serve-kinds random,random,waiting_headroom,retry_backoff,deadline \
        --json-only 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["ok"] and d["trials"] == 5, d
assert d["torn_records"] == [], d
assert all(v["ok"] for v in d["verdicts"]), d
kinds = [v["kind"] for v in d["verdicts"]]
assert kinds == ["random", "random", "waiting_headroom",
                 "retry_backoff", "deadline"], kinds
print("chaosprobe --serve:", d["trials"], "daemon-kill trials",
      "(2 random + waiting_headroom + retry_backoff + deadline),",
      "no torn records, jobs bit-identical to solo")
'
    echo "== bench regression gate (BENCH_GATE.json, ms/round per row) =="
    # ROADMAP item 5: the gate now carries THREE rows — dense smoke PHOLD,
    # the sparse rung-1 TCP config and the 4-lane fleet sweep — each gated
    # on >tolerance ms/round regression vs its committed per-backend
    # baseline (a TPU baseline coexists with the CPU one; rows without a
    # baseline for this backend report instead of auto-skipping the gate).
    # Intentional trade-offs override once with
    # SHADOW1_BENCH_GATE_ACCEPT="why" and then re-baseline via --update.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.benchgate \
        | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["gate"] in ("ok", "no_baseline"), d
for name, r in d.get("rows", {}).items():
    assert r["gate"] in ("ok", "accepted", "no_baseline_for_backend",
                         "skipped_host_mismatch"), (name, r["gate"])
    print("benchgate:", name, r["gate"], "-", r.get("ms_per_round"),
          "ms/round vs", r.get("baseline_ms_per_round"), "baseline")
'
    echo "== op/fusion census drift gate (OPCENSUS.json) =="
    # Performance attribution plane: per-phase traced eqn counts must stay
    # within tolerance of the committed baseline (the static early warning
    # for ROADMAP item 1 kernel work) — and the gate must actually TRIP
    # on an injected extra-op build.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.opcensus \
        2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["gate"] in ("ok", "accepted"), d
eq = {k: v["eqns"]["rounds"] for k, v in d["census"].items()}
print("opcensus:", d["gate"], "- rounds-phase eqns", eq)
'
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.opcensus \
        smoke --inject 100 >/dev/null 2>&1 && rc=0 || rc=$?
    [ "$rc" -eq 1 ] || { echo "opcensus: injected drift did not trip the gate (rc=$rc)" >&2; exit 1; }
    echo "opcensus: injected 100-eqn drift tripped the gate (exit 1)"
    echo "== phase attribution smoke (phaseprobe, >=90% coverage) =="
    # The wall-clock half of the attribution plane: the phase split must
    # account for >=90% of the straight run's measured ms/round.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.phaseprobe \
        smoke --hosts 512 --windows 8 --warmup 4 --reps 2 \
        --min-coverage 0.9 2>/dev/null | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["coverage"] >= 0.9, d
print("phaseprobe: coverage", d["coverage"], "- rounds",
      d["phases"]["rounds"]["pct"], "% of", d["ms_per_round"], "ms/round")
'
    echo "== flow-probe parity smoke (cpu vs tpu) + stall self-test =="
    # The flow probe plane (docs/SEMANTICS.md §"Flow probe contract"):
    # the watched-flow stream on the rung-1 TCP config must be
    # bit-identical between the batched engine's [W,K,F] ring and the
    # eager oracle's per-boundary mirror, and the watched flow must have
    # actually moved (an all-zero parity proves nothing).
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import dataclasses
import shadow1_tpu
from shadow1_tpu.config.experiment import load_experiment, resolve_watchlist
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.telemetry.probes import drain_probes

exp, params, _ = load_experiment("configs/rung1_filexfer.yaml")
watch = resolve_watchlist(["client:0", "server"], exp.dns,
                          params.sockets_per_host)
params = dataclasses.replace(params, probes=watch, metrics_ring=64)
eng = Engine(exp, params)
st = eng.run(n_windows=40)
trows = sorted(drain_probes(st, eng.window, watch),
               key=lambda r: (r["window"], r["host"], r["sock"]))
ceng = CpuEngine(exp, params)
ceng.run(n_windows=40)
crows = sorted(ceng.probe_rows,
               key=lambda r: (r["window"], r["host"], r["sock"]))
assert trows == crows, "probe stream diverged cpu vs tpu"
assert any(r["inflight"] > 0 for r in trows if r["sock"] == 0), \
    "watched flow never moved"
print(f"flow probes: {len(trows)} rows bit-identical cpu<->tpu, 40 windows")
EOF
    # The stall detectors must flag a synthetic RTO storm and must NOT
    # flag its clean prefix (false-positive guard) — flowreport's own
    # self-test.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.flowreport \
        --selftest | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["selftest"] == "ok", d
assert "rto_storm" in d["storm_flagged"], d
assert d["clean_prefix_flagged"] == [], d
print("flowreport selftest:", d["storm_flagged"], "flagged, clean prefix quiet")
'
    echo "== link-telemetry parity smoke (cpu vs tpu) + weathermap self-test =="
    # The link plane (docs/SEMANTICS.md §"Link telemetry contract"): the
    # rung-1 per-edge cumulative snapshots must be bit-identical between
    # the batched engine's [V,V,F] accumulator and the eager oracle's
    # per-edge mirror, and every drop column must reconcile EXACTLY with
    # its global counter (path-aware attribution loses nothing). The
    # opcensus gate above doubles as the links-off zero-op proof: its
    # committed baseline predates the plane and the default build
    # (--link-telem off) must still match it.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import dataclasses
import shadow1_tpu
from shadow1_tpu.config.experiment import load_experiment
from shadow1_tpu.core.engine import Engine
from shadow1_tpu.cpu_engine import CpuEngine
from shadow1_tpu.telemetry.links import drain_links

exp, params, _ = load_experiment("configs/rung1_filexfer.yaml")
params = dataclasses.replace(params, link_telem=1)
key = lambda r: (r["src_vertex"], r["dst_vertex"], r["window"])
eng = Engine(exp, params)
st = eng.run(n_windows=40)
trows = sorted(drain_links(st, eng.window), key=key)
tm = Engine.metrics_dict(st)
ceng = CpuEngine(exp, params)
ceng.run(n_windows=40)
assert trows == sorted(ceng.link_rows, key=key), \
    "link records diverged cpu vs tpu"
assert trows and any(r["pkts"] > 0 for r in trows), "no traffic observed"
for rows, m in ((trows, tm), (ceng.link_rows, ceng.metrics)):
    assert sum(r["pkts"] for r in rows) == m["pkts_sent"]
    assert sum(r["loss_drops"] for r in rows) == m["pkts_lost"]
    assert sum(r["link_down_drops"] for r in rows) == m["link_down_pkts"]
    assert sum(r["nic_backlog_drops"] for r in rows) == m["nic_tx_drops"]
print(f"link records: {len(trows)} edge rows bit-identical cpu<->tpu, "
      f"40 windows; pkts=={tm['pkts_sent']} and all drop columns "
      f"reconcile with the global counters")
EOF
    # The weathermap detectors must flag all four synthetic pathologies
    # (loss concentration, egress saturation, dark link, elephant skew)
    # and must NOT flag the clean topology — netreport's own self-test.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m shadow1_tpu.tools.netreport \
        --selftest | python -c '
import json, sys
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert d["selftest"] == "ok", d
print("netreport selftest: all four pathology detectors fired, clean topology quiet")
'
    echo "== corrupt-checkpoint recovery smoke (integrity digest) =="
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -c '
import tempfile, os
import shadow1_tpu
from shadow1_tpu.ckpt import (CorruptCheckpointError, load_state,
                              save_state, verify_file)
from shadow1_tpu.config.compiled import single_vertex_experiment
from shadow1_tpu.consts import MS, EngineParams
from shadow1_tpu.core.engine import Engine
eng = Engine(single_vertex_experiment(
    n_hosts=8, seed=4, end_time=20 * MS, latency_ns=1 * MS, model="phold",
    model_cfg={"mean_delay_ns": float(2 * MS)}), EngineParams())
st = eng.run(n_windows=5)
path = os.path.join(tempfile.mkdtemp(), "snap.npz")
save_state(st, path)
assert verify_file(path)[0]
load_state(eng.init_state(), path)
import numpy as np
with np.load(path) as d:
    arrs = {k: d[k].copy() for k in d.files}
leaf = next(k for k in arrs if k.startswith("leaf_")
            and arrs[k].size and arrs[k].dtype != np.bool_)
arrs[leaf].reshape(-1).view(np.uint8)[0] ^= 0x20
np.savez(path, **arrs)  # payload changed, stored integrity now stale
ok, why = verify_file(path)
assert not ok, "bit flip must not verify"
try:
    load_state(eng.init_state(), path)
except (CorruptCheckpointError, ValueError):
    pass
else:
    raise AssertionError("corrupt snapshot loaded silently")
print("corrupt checkpoint rejected:", why)
'
    ;;
  fast)  exec python -m pytest tests/ -q -m "not slow" ;;
  all)   exec python -m pytest tests/ -q ;;
  *) echo "usage: $0 [smoke|fast|all]" >&2; exit 2 ;;
esac
