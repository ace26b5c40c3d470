"""Fault-tolerance drill (run in a subprocess under 8-device sim).

Proves the runtime health loop end-to-end on real jitted training:

* **kill drill** — worker 1 dies mid-step (``InjectedFailure`` at
  step 7, round 2).  The supervisor must replan on the 3 survivors,
  restore the newest committed checkpoint, replay the deterministic
  data stream, and lose at most ``checkpoint_every`` steps — and the
  post-recovery losses/grad-norms must match an *uninterrupted*
  survivor-fleet run restored from the same checkpoint to <= 1e-6
  normalized.
* **straggler drill** — worker 3 reports 2x-slow step times.  The
  monitor must demote it within the hysteresis window (+ cooldown
  slack), the latched quantized speeds must reach
  ``distributor.assign_blocks`` (the demoted schedule carries less
  modeled compute on worker 3), and flipping plans must go through the
  plan cache (the demoted key misses exactly once, then re-hits).
* **pod drill** — pod 1 of a 2x2 fleet dies mid-step (pod-scoped
  ``InjectedFailure`` at step 5).  The supervisor must shrink the pod
  dimension to the surviving pod, reset the error-feedback residuals,
  restore, and replay (<= ``checkpoint_every`` steps lost) while the
  overlapping-recovery thread pre-warms the regrow path; the rejoin at
  step 9 must re-hit the pre-shrink plan-cache keys (asserted via
  ``elastic.replan_key``) with zero plan misses and zero recompiles
  after it, and both the survivor and post-rejoin losses/grad-norms
  must match an uninterrupted reference run to <= 1e-6 normalized.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       PYTHONPATH=src python tests/multidevice/run_fault_drill.py \
       [tier1|full]
"""

import os
import pathlib
import shutil
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np                                              # noqa: E402

from repro.configs.base import (ParallelConfig, TrainConfig,    # noqa: E402
                                smoke_config)
from repro.core import cost_model as cm                         # noqa: E402
from repro.launch.train import Supervisor                       # noqa: E402
from repro.runtime import elastic                               # noqa: E402
from _common import grid                                        # noqa: E402

N0, TPW0, BS = 4, 512, 128
# the kill drill runs one 256-token block per worker: the same replan,
# restore and replay at a sixth of the compile time of (TPW0, BS)
KILL_TPW = KILL_BS = 256
CKPT_EVERY = 2
FAIL_STEP, FAIL_WORKER, FAIL_ROUND = 7, 1, 2
TOTAL = 12
# pod drill geometry: 2 pods x 2 workers, pod 1 dies, regrows at 9
P0, POD_WORKERS, POD_TPW = 2, 2, 256
POD_FAIL_STEP, POD_REJOIN = 5, 9


def _cfg():
    return smoke_config("stablelm_1_6b").replace(param_dtype="float32")


def _pcfg(**kw):
    kw.setdefault("block_size", BS)
    kw.setdefault("remat", False)
    kw.setdefault("coalesce", 4)
    kw.setdefault("in_dtype_bytes", 4.0)
    kw.setdefault("checkpoint_every", CKPT_EVERY)
    return ParallelConfig(**kw)


def _sup(pcfg, ckpt_dir, tpw=TPW0, **kw):
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=TOTAL)
    kw.setdefault("dist", "real_world")
    # keep every checkpoint: the reference run restores from a pruned
    # copy of the directory, so step_{resume-1} must survive GC
    kw.setdefault("checkpoint_keep", 8)
    return Supervisor(_cfg(), pcfg, tcfg, n_workers=N0,
                      tokens_per_worker=tpw, checkpoint_dir=ckpt_dir,
                      verbose=False, **kw)


def kill_drill(tmp: pathlib.Path) -> None:
    d = tmp / "primary"
    sup = _sup(_pcfg(block_size=KILL_BS), d, tpw=KILL_TPW)
    fail = elastic.InjectedFailure(worker=FAIL_WORKER, step=FAIL_STEP,
                                  round=FAIL_ROUND)
    sup.run(TOTAL, fail=fail)

    assert len(sup.recoveries) == 1, sup.recoveries
    rec = sup.recoveries[0]
    assert rec["failed_step"] == FAIL_STEP
    assert rec["worker"] == FAIL_WORKER
    assert rec["n_workers"] == N0 - 1
    # bounded step loss: the newest committed checkpoint is at most
    # checkpoint_every steps behind the failed step
    assert 0 <= rec["steps_lost"] <= CKPT_EVERY, rec
    fails = [e for e in sup.monitor.events if e.kind == "fail"]
    assert fails and fails[0].workers == (FAIL_WORKER,)
    # every step to TOTAL committed, pre-failure on 4 workers,
    # post-recovery on 3
    by_fleet = {}
    for r in sup.history:
        by_fleet.setdefault(r.n_workers, []).append(r.step)
    assert by_fleet[N0][-1] == FAIL_STEP - 1
    assert by_fleet[N0 - 1][-1] == TOTAL - 1
    print(f"  kill drill: lost {rec['steps_lost']} step(s) "
          f"(<= {CKPT_EVERY}), resumed at {rec['resume_step']} "
          f"on {rec['n_workers']} workers")

    # reference: an UNINTERRUPTED 3-worker run restored from the same
    # checkpoint the recovery used (prune everything newer first)
    d2 = tmp / "reference"
    shutil.copytree(d, d2)
    for p in d2.iterdir():
        if (p.name.startswith("step_") and not p.name.endswith(".tmp")
                and int(p.name.split("_")[1]) > rec["resume_step"] - 1):
            shutil.rmtree(p)
    ref = _sup(_pcfg(block_size=KILL_BS), d2, tpw=KILL_TPW,
               start_fleet=N0 - 1)
    ref.run(TOTAL)
    want = {r.step: r for r in ref.history}
    got = {r.step: r for r in sup.history if r.n_workers == N0 - 1}
    assert sorted(got) == sorted(want)
    diffs = []
    for s in got:
        diffs.append(abs(got[s].loss - want[s].loss)
                     / max(abs(want[s].loss), 1e-9))
        diffs.append(abs(got[s].gnorm - want[s].gnorm)
                     / max(abs(want[s].gnorm), 1e-9))
    assert max(diffs) <= 1e-6, max(diffs)
    print(f"  kill drill: post-recovery loss/gnorm match the "
          f"uninterrupted survivor run (max normalized diff "
          f"{max(diffs):.2e} <= 1e-6)")


def modeled_worker_loads(sched, speeds=None) -> np.ndarray:
    """Per-worker modeled compute time of one schedule: cost-model
    block FLOPs summed by owner, divided by actual worker speed."""
    costs = cm.block_q_flops(sched.batch, sched.deps, 2, 64,
                             sched.spec.mask)
    loads = np.bincount(sched.assignment, weights=costs,
                        minlength=sched.spec.n_workers).astype(float)
    if speeds is not None:
        loads = loads / np.asarray(speeds, float)
    return loads


def straggler_drill() -> None:
    window, cooldown = 3, 4
    pcfg = _pcfg(checkpoint_every=0, health_window=window,
                 demote_cooldown=cooldown)
    sup = _sup(pcfg, None)
    skew = {3: 2.0}
    sup.run(TOTAL, skew=skew)

    demotes = [e for e in sup.monitor.events if e.kind == "demote"]
    assert demotes, "2x-slow worker was never demoted"
    first = demotes[0]
    # demoted within the hysteresis window (+ cooldown slack for the
    # quantized latch settling)
    assert first.step < window + cooldown, first
    assert 3 in first.workers
    speeds = sup.monitor.planning_speeds()
    assert speeds is not None and speeds[3] <= 0.6, speeds
    print(f"  straggler drill: demoted worker 3 at step {first.step} "
          f"(window {window}), latched speeds {speeds}")

    # measured speeds reached assign_blocks: every worker still owns
    # exactly ``slots`` blocks (memory constraint), so demotion shifts
    # block *cost* — the slow worker's modeled compute must drop well
    # below what uniform placement hands it
    sched = next(iter(sup.last_scheds.values()))
    real = np.array([1.0, 1.0, 1.0, 0.5])
    uniform = elastic.replan(
        sched.batch.seqlens, N0, BS, n_q_heads=2, n_kv_heads=2,
        head_dim=64, mask=sched.spec.mask, pcfg=pcfg, verify=False)
    loads = modeled_worker_loads(sched)
    loads_uni = modeled_worker_loads(uniform)
    assert loads[3] < 0.9 * loads_uni[3], (loads, loads_uni)
    # and the demoted placement beats the uniform one under the real
    # 2x skew: modeled step time (max over workers of load/speed) drops
    t_uni = (loads_uni / real).max()
    t_dem = (loads / real).max()
    assert t_dem < t_uni, (t_dem, t_uni)
    print(f"  straggler drill: modeled step time ratio "
          f"{t_dem / t_uni:.2f} (demoted vs uniform placement), "
          f"slow-worker load {loads[3] / loads_uni[3]:.2f}x of uniform")

    # plan-cache discipline: latched speeds mint one new key per
    # (composition, speed-latch) pair — they miss once, then every
    # later step re-hits (no per-step churn from the closed loop)
    s = sup.plan_cache.stats
    n_comps = len({tuple(c) for c in sup.loader.compositions})
    n_latches = 1 + len(demotes)
    assert s.misses <= n_comps * n_latches * len(sup.group_masks), \
        s.to_dict()
    assert s.hits + s.misses >= TOTAL
    assert s.hits >= TOTAL - s.misses, s.to_dict()
    print(f"  straggler drill: plan cache {s.hits} hits / "
          f"{s.misses} misses across the demotion flip")


def _pod_sup(ckpt_dir, start_fleet=None):
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=TOTAL,
                       grad_compression=True)
    # checkpoint_keep wide enough that step_{resume-1} survives the
    # GC to the end of the run — the reference below restores from a
    # pruned copy of the directory, so it must still hold that step
    return Supervisor(_cfg(), _pcfg(), tcfg, n_workers=POD_WORKERS,
                      tokens_per_worker=POD_TPW, pods=P0,
                      dist="real_world", checkpoint_dir=ckpt_dir,
                      checkpoint_keep=8, verbose=False,
                      start_fleet=start_fleet)


def pod_drill(tmp: pathlib.Path) -> None:
    d = tmp / "pod_primary"
    sup = _pod_sup(d)
    fail = elastic.InjectedFailure(pod=1, step=POD_FAIL_STEP, round=1)
    sup.run(TOTAL, fail=fail, rejoin_step=POD_REJOIN)

    # -- shrink: pod-scoped recovery within the checkpoint budget ------
    assert len(sup.recoveries) == 1, sup.recoveries
    rec = sup.recoveries[0]
    assert rec["failed_step"] == POD_FAIL_STEP
    assert rec["pod"] == 1 and "worker" not in rec, rec
    assert rec["pods"] == 1 and rec["n_workers"] == POD_WORKERS, rec
    assert 0 <= rec["steps_lost"] <= CKPT_EVERY, rec
    # EF residuals must reset over the survivors, never be reused
    assert rec.get("ef_reset"), rec
    fails = [e for e in sup.monitor.events if e.kind == "fail"]
    assert fails and fails[0].pod == 1, fails
    assert set(fails[0].workers) == {2, 3}, fails   # pod 1's flat slots
    print(f"  pod drill: lost {rec['steps_lost']} step(s) "
          f"(<= {CKPT_EVERY}), resumed at {rec['resume_step']} on "
          f"{rec['pods']}x{rec['n_workers']} survivors, EF reset")

    # -- overlapping recovery: the prewarm thread did its three jobs ---
    assert len(sup.rejoins) == 1, sup.rejoins
    rj = sup.rejoins[0]
    assert rj["step"] == POD_REJOIN and rj["pods"] == P0, rj
    pw = rj["prewarm"]
    assert "error" not in pw, pw
    assert pw["survivor_schedules_verified"] >= 1, pw
    assert pw["violations"] == 0, pw
    assert pw["plans_prefetched"] >= 1, pw
    assert pw["staged_step"] == rec["resume_step"] - 1, (pw, rec)
    print(f"  pod drill: prewarm verified "
          f"{pw['survivor_schedules_verified']} survivor schedule(s) "
          f"(0 violations), staged checkpoint step "
          f"{pw['staged_step']}, prefetched {pw['plans_prefetched']} "
          f"regrow plan(s)")

    # -- rejoin: re-hits pre-shrink plans, zero misses / recompiles ----
    assert rj["plan_keys_cached"] is True, rj
    s = sup.plan_cache.stats
    assert s.misses == rj["plan_misses_before"], (s.to_dict(), rj)
    assert len(sup.compiled_at) == rj["compiles_before"], \
        (sup.compiled_at, rj)
    # the exact key contract: the full-strength replan_key reduces to
    # the pre-shrink key, so the regrown fleet re-hits the warmup plans
    m = sup.group_masks[0]
    key = elastic.replan_key(
        sup.loader.composition(POD_REJOIN)[1], POD_WORKERS, BS,
        mask=m, pcfg=sup.pcfg, pods=P0, base_pods=P0)
    assert key in sup.plan_cache, "regrow key missing from plan cache"
    print(f"  pod drill: rejoin at step {POD_REJOIN} re-hit cached "
          f"plans (replan_key asserted), 0 plan misses and "
          f"0 recompiles after rejoin ({rj['rejoin_ms']:.0f}ms)")

    # -- equivalence: survivor AND post-rejoin phases match an
    # uninterrupted reference restored from the same checkpoint -------
    d2 = tmp / "pod_reference"
    shutil.copytree(d, d2)
    for p in d2.iterdir():
        if (p.name.startswith("step_") and not p.name.endswith(".tmp")
                and int(p.name.split("_")[1]) > rec["resume_step"] - 1):
            shutil.rmtree(p)
    ref = _pod_sup(d2, start_fleet=(1, POD_WORKERS))
    ref.run(TOTAL, rejoin_step=POD_REJOIN)
    want = {(r.step, r.pods): r for r in ref.history}
    got = {(r.step, r.pods): r for r in sup.history
           if (r.step, r.pods) in want}
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    assert any(p == P0 for _, p in got), "no post-rejoin steps compared"
    diffs = []
    for k in got:
        diffs.append(abs(got[k].loss - want[k].loss)
                     / max(abs(want[k].loss), 1e-9))
        diffs.append(abs(got[k].gnorm - want[k].gnorm)
                     / max(abs(want[k].gnorm), 1e-9))
    assert max(diffs) <= 1e-6, max(diffs)
    print(f"  pod drill: survivor + post-rejoin loss/gnorm match the "
          f"uninterrupted reference (max normalized diff "
          f"{max(diffs):.2e} <= 1e-6 over {len(got)} steps)")


def main() -> int:
    full = grid() == "full"
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="fault_drill_"))
    try:
        print("kill drill (worker 1 dies at step 7, round 2):")
        kill_drill(tmp)
        if full:
            print("straggler drill (worker 3 at 2x step time):")
            straggler_drill()
            print(f"pod drill (pod 1 of {P0} dies at step "
                  f"{POD_FAIL_STEP}, rejoin at {POD_REJOIN}):")
            pod_drill(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("ALL FAULT DRILL CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
