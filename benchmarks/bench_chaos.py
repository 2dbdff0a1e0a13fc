"""Chaos soak benchmark: the durability & elasticity acceptance gate.

Three deterministic fault scenarios exercise the DESIGN.md §7 machinery on
the virtual clock, each against an unfaulted run of the same trace:

- **kill_restore** (K=1): the engine crashes mid-trace (``crash=R`` with
  periodic checkpointing armed), then restores — once from the crash
  checkpoint and once from the *earliest* periodic checkpoint — and each
  restored run replays the full trace (the admission queue swallows every
  already-seen rid). Gated on bit-identical outputs: lm token streams
  exactly equal, single-shot logits ``np.array_equal``, total token counts
  equal, and zero lost terminal requests.

- **shard_lost** (K=4): a replica dies mid-trace and recovers later
  (``shard_lost=R*1,shard_back=R2``). The dead shard's slot-pinned entries
  evacuate into survivors (overflow parks on the request), the mesh
  resizes to K-1 within the injection round, then re-grows. Gated on zero
  ``FAILED`` requests, every request completed, lm streams exactly equal
  to the clean K=4 run, and both resize events landing at their armed
  rounds.

- **soak** (K=2): the combined mix — compile failure (quarantine +
  degradation), executor exception, slow round, poisoned topologies,
  shard loss, a crash while running on the shrunken mesh, restore, regrow,
  with work stealing armed throughout. Gated like bench_faults (all
  terminal, poison contained as ``BAD_TOPOLOGY``, healthy outputs match
  clean) plus checkpoint/restore/resize counters being live.

Forces ``--xla_force_host_platform_device_count=4`` before jax initializes
so the sharded scenarios run on CPU CI.

    PYTHONPATH=src python -m benchmarks.bench_chaos [--out BENCH_chaos.json]
"""

from __future__ import annotations

import os


def _force_host_devices(n: int = 4) -> None:
    """Must run before jax is first imported (device count locks at init)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


_force_host_devices()

import argparse      # noqa: E402
import json          # noqa: E402
import tempfile      # noqa: E402

import numpy as np   # noqa: E402

from repro.models.workloads import SERVE_FAMILIES, make_workload  # noqa: E402
from repro.serve import (InjectedCrash, ServeEngine,              # noqa: E402
                         latest_checkpoint, list_checkpoints, synth_trace)
from repro.serve.faults import FaultInjector, poison_requests     # noqa: E402
from repro.serve.queue import COMPLETED, FAILED, TERMINAL         # noqa: E402
from repro.launch.jaxcache import enable_compilation_cache        # noqa: E402

from .common import (add_obs_args, emit, maybe_enable_obs,        # noqa: E402
                     platform_payload, write_obs)

FAMILIES = ["lm", "tree", "lattice"]
DEADLINE = 500.0     # generous: the gates measure durability, not SLO pressure


def chaos_trace(workloads, n, rate, max_new, seed):
    reqs = synth_trace(FAMILIES, n, rate, max_new, workloads, seed)
    for r in reqs:
        r.deadline = r.arrival + DEADLINE
    return reqs


def ledger(eng):
    """The engine's request ledger in rid order. Two runs of the same trace
    draw different rids from the process-wide counter, so equivalence is
    checked position-aligned on the sorted ledgers, never by rid value."""
    return [eng.requests[rid] for rid in sorted(eng.requests)]


def ledger_match(a, b):
    """Position-aligned output equivalence between two ledgers.
    Returns (statuses_equal, exact_lm, bitwise_single, close_single)."""
    statuses = len(a) == len(b) and all(
        x.status == y.status for x, y in zip(a, b))
    exact_lm = bitwise = close = True
    for x, y in zip(a, b):
        if x.status != COMPLETED or y.status != COMPLETED:
            continue
        if x.family == "lm":
            exact_lm = exact_lm and x.out == y.out
        else:
            bitwise = bitwise and bool(np.array_equal(x.result, y.result))
            close = close and np.allclose(x.result, y.result,
                                          rtol=1e-4, atol=1e-5)
    return statuses, exact_lm, bitwise, close


def serve_clean(workloads, reqs, *, max_slots, n_shards=1):
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=max_slots,
                      n_shards=n_shards)
    eng.submit_many(reqs)
    return eng, eng.run()


# -- scenario A: kill + restore ----------------------------------------------


def scenario_kill_restore(workloads, *, requests=12, rate=3.0, max_new=3,
                          max_slots=4, seed=0, crash_round=6,
                          checkpoint_every=3) -> dict:
    clean_eng, clean_stats = serve_clean(
        workloads, chaos_trace(workloads, requests, rate, max_new, seed),
        max_slots=max_slots)
    clean = ledger(clean_eng)

    entry: dict = {"requests": requests, "crash_round": crash_round,
                   "checkpoint_every": checkpoint_every}
    with tempfile.TemporaryDirectory(prefix="chaos_ckpt_") as ckdir:
        trace = chaos_trace(workloads, requests, rate, max_new, seed)
        eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                          continuous=True, max_slots=max_slots,
                          fault_injector=FaultInjector(
                              crash_rounds=[crash_round]),
                          checkpoint_dir=ckdir,
                          checkpoint_every=checkpoint_every)
        eng.submit_many(trace)
        crashed = False
        try:
            eng.run()
        except InjectedCrash:
            crashed = True
        ckpts = list_checkpoints(ckdir)
        entry.update({"crashed": crashed, "n_checkpoints": len(ckpts)})
        if not crashed or not ckpts:
            entry["ok"] = False
            return entry

        # Restore twice: from the crash checkpoint (resume exactly where
        # the process died) and from the earliest periodic one (replay
        # several uninterrupted rounds) — both must reproduce the clean
        # run bit-for-bit, which is the determinism claim of DESIGN.md §7.
        for tag, src in (("from_crash", latest_checkpoint(ckdir)),
                         ("from_periodic", ckpts[0][1])):
            r_eng = ServeEngine.restore(src, dict(workloads))
            r_eng.submit_many(trace)       # full-trace replay: all dupes
            r_stats = r_eng.run()
            statuses, exact_lm, bitwise, _ = ledger_match(
                ledger(r_eng), clean)
            done = sum(r.status == COMPLETED for r in ledger(r_eng))
            ok = (statuses and exact_lm and bitwise
                  and done == requests
                  and r_stats.requests_failed == 0
                  and r_stats.tokens_out == clean_stats.tokens_out
                  and r_eng.queue.duplicates >= requests
                  and r_stats.n_restores == 1)
            entry[tag] = {"completed": done,
                          "tokens_out": r_stats.tokens_out,
                          "lm_tokens_exact": exact_lm,
                          "single_shot_bitwise": bitwise,
                          "duplicates_swallowed": r_eng.queue.duplicates,
                          "restored_round": src.rsplit("_", 1)[-1],
                          "ok": ok}
    entry["clean_tokens_out"] = clean_stats.tokens_out
    entry["completed"] = entry["from_crash"]["completed"]
    entry["tokens_out"] = entry["from_crash"]["tokens_out"]
    entry["lm_tokens_exact"] = (entry["from_crash"]["lm_tokens_exact"]
                                and entry["from_periodic"]["lm_tokens_exact"])
    entry["ok"] = entry["from_crash"]["ok"] and entry["from_periodic"]["ok"]
    return entry


# -- scenario B: replica loss + regrow ----------------------------------------


def scenario_shard_lost(workloads, *, requests=16, rate=3.0, max_new=3,
                        n_shards=4, seed=1, lost_round=5, dead_shard=1,
                        back_round=12) -> dict:
    max_slots = 2 * n_shards       # slots_per_shard=2: forces the park path
    clean_eng, clean_stats = serve_clean(
        workloads, chaos_trace(workloads, requests, rate, max_new, seed),
        max_slots=max_slots, n_shards=n_shards)
    clean = ledger(clean_eng)

    trace = chaos_trace(workloads, requests, rate, max_new, seed)
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=max_slots,
                      n_shards=n_shards,
                      fault_injector=FaultInjector(
                          shard_lost={lost_round: dead_shard},
                          shard_back_rounds=[back_round]))
    eng.submit_many(trace)
    stats = eng.run()

    statuses, exact_lm, bitwise, close = ledger_match(ledger(eng), clean)
    done = sum(r.status == COMPLETED for r in trace)
    # The shrink must complete within the round the loss fires at (the
    # resize is synchronous at the round boundary — this pins it).
    shrink = [e for e in eng.resize_log if e["new"] == n_shards - 1]
    regrow = [e for e in eng.resize_log if e["new"] == n_shards]
    resize_prompt = (len(shrink) == 1 and shrink[0]["round"] == lost_round
                     and len(regrow) == 1
                     and regrow[0]["round"] == back_round)
    ok = (statuses and exact_lm and close and done == requests
          and stats.requests_failed == 0 and resize_prompt
          and stats.n_resize_events == 2
          and stats.tokens_out == clean_stats.tokens_out)
    return {"requests": requests, "n_shards": n_shards,
            "lost_round": lost_round, "back_round": back_round,
            "completed": done, "failed": stats.requests_failed,
            "tokens_out": stats.tokens_out,
            "clean_tokens_out": clean_stats.tokens_out,
            "lm_tokens_exact": exact_lm,
            "single_shot_close": close,
            "single_shot_bitwise": bitwise,
            "resize_log": list(eng.resize_log),
            "entries_evacuated": stats.n_entries_evacuated,
            "resize_on_time": resize_prompt, "ok": ok}


# -- scenario C: combined soak -------------------------------------------------


SOAK_SPEC = ("compile_fail=1,exec_rounds=3,slow=5*2.0,poison=2,"
             "shard_lost=4*1,crash=7,shard_back=10")


def scenario_soak(workloads, *, requests=12, rate=2.5, max_new=3,
                  n_shards=2, seed=2, checkpoint_every=3) -> dict:
    max_slots = 2 * n_shards
    clean_eng, clean_stats = serve_clean(
        workloads, chaos_trace(workloads, requests, rate, max_new, seed),
        max_slots=max_slots, n_shards=n_shards)
    clean = ledger(clean_eng)

    entry: dict = {"requests": requests, "n_shards": n_shards,
                   "fault_spec": SOAK_SPEC,
                   "checkpoint_every": checkpoint_every}
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as ckdir:
        injector = FaultInjector.from_spec(SOAK_SPEC)
        trace = chaos_trace(workloads, requests, rate, max_new, seed)
        poisoned = poison_requests(injector.poison, arrival=1.0)
        eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                          continuous=True, max_slots=max_slots,
                          n_shards=n_shards, fault_injector=injector,
                          checkpoint_dir=ckdir,
                          checkpoint_every=checkpoint_every,
                          steal_threshold=1)
        eng.submit_many(trace + poisoned)
        crashed = False
        try:
            eng.run()
        except InjectedCrash:
            crashed = True
        entry["crashed"] = crashed
        if not crashed:
            entry["ok"] = False
            return entry

        # Resume on the shrunken mesh with the crash disarmed but the
        # replica recovery still scheduled; work stealing re-balances onto
        # the regrown shard.
        r_eng = ServeEngine.restore(
            latest_checkpoint(ckdir), dict(workloads),
            fault_injector=FaultInjector(
                shard_back_rounds=injector.shard_back_rounds),
            steal_threshold=1)
        r_eng.submit_many(trace + poisoned)
        stats = r_eng.run()

    led = ledger(r_eng)
    trace_led, poison_led = led[:requests], led[requests:]
    all_terminal = all(r.status in TERMINAL for r in led)
    poison_failed = len(poison_led) == injector.poison and all(
        r.status == FAILED and r.error["code"] == "BAD_TOPOLOGY"
        for r in poison_led)
    statuses, exact_lm, bitwise, close = ledger_match(trace_led, clean)
    done = sum(r.status == COMPLETED for r in trace_led)
    clean_done = sum(r.status == COMPLETED for r in clean)
    ok = (all_terminal and poison_failed and statuses and exact_lm
          and close and done == clean_done
          and stats.n_checkpoints >= 1 and stats.n_restores == 1
          and stats.n_resize_events >= 1
          and stats.n_contained_errors >= 1)
    entry.update({
        "all_terminal": all_terminal, "poison_failed": poison_failed,
        "completed": done, "clean_completed": clean_done,
        "tokens_out": stats.tokens_out,
        "clean_tokens_out": clean_stats.tokens_out,
        "lm_tokens_exact": exact_lm,
        "single_shot_close": close,
        "single_shot_bitwise": bitwise,
        "resize_log": list(r_eng.resize_log),
        "checkpoints": stats.n_checkpoints,
        "restores": stats.n_restores,
        "entries_evacuated": stats.n_entries_evacuated,
        "entries_stolen": stats.n_entries_stolen,
        "quarantine_events": stats.n_quarantine_events,
        "contained_errors": stats.n_contained_errors,
        "tier_rounds": dict(stats.tier_rounds),
        "ok": ok})
    return entry


# -- driver -------------------------------------------------------------------


def run(out: str = "", model_size: int = 8, seed: int = 0) -> dict:
    workloads = {f: make_workload(SERVE_FAMILIES[f], model_size, seed)
                 for f in FAMILIES}
    result: dict = {"model_size": model_size, "deadline": DEADLINE}
    all_ok = True
    scenarios = (
        ("kill_restore", lambda: scenario_kill_restore(workloads)),
        ("shard_lost", lambda: scenario_shard_lost(workloads)),
        ("soak", lambda: scenario_soak(workloads)),
    )
    for name, fn in scenarios:
        try:
            entry = fn()
        except Exception as exc:                     # the no-crash gate
            entry = {"ok": False,
                     "crash": f"{type(exc).__name__}: {exc}"}
        result[name] = entry
        all_ok = all_ok and entry["ok"]
        emit(f"bench_chaos/{name}", 0.0,
             ";".join(f"{k}={entry[k]}" for k in
                      ("completed", "tokens_out", "lm_tokens_exact")
                      if k in entry) + f";ok={entry['ok']}")
    result["ok"] = all_ok
    # Stamped after the measured phases so the obs_metrics snapshot carries
    # the run's counters, not an empty registry.
    result.update(platform_payload())
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--model-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    maybe_enable_obs(args)
    res = run(out=args.out, model_size=args.model_size, seed=args.seed)
    write_obs(args)
    # CI gate (chaos-smoke): kill-and-restore reproduces the clean run
    # bit-for-bit from either checkpoint, replica loss drains to completion
    # on K-1 with zero FAILED and on-time resizes, and the combined soak
    # stays terminal with poison contained and durability counters live.
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
