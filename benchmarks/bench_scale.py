"""Replica-scaling serve benchmark: the sharded-bucketed-plan acceptance
gate. Writes ``BENCH_scale.json`` so CI records the scaling trajectory.

One mixed serve trace (lm-heavy with tree + lattice single-shots, offered
at a rate that keeps every slot busy) is served at increasing replica
counts. Capacity scales with replicas — each shard keeps a fixed
``slots_per_shard`` lm slot pool — so adding replicas admits more
concurrent decode work per round at the same one-dispatch-per-round cost.

Acceptance (checked here, recorded in the JSON, gated in CI's shard-smoke
job):

- **round throughput** (lm tokens per scheduler round) increases
  monotonically from 1 replica to the max measured,
- one XLA compile per bucket signature at every replica count
  (``n_compiles <= n_buckets``; recurring round shapes never recompile),
- sharded outputs equal the single-replica engine's outputs exactly
  (same tokens, same single-shot logits).

Forces ``--xla_force_host_platform_device_count`` before jax initializes
so the whole measurement runs on CPU CI; on real multi-device backends the
flag is a no-op for non-CPU platforms.

    PYTHONPATH=src python -m benchmarks.bench_scale [--out BENCH_scale.json]
"""

from __future__ import annotations

import os


def _force_host_devices(n: int = 8) -> None:
    """Must run before jax is first imported (device count locks at init)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


_force_host_devices()

import argparse      # noqa: E402
import json          # noqa: E402

import numpy as np   # noqa: E402

from repro.core.cache import FIFOCache, LRUCache            # noqa: E402
from repro.launch.mesh import make_data_mesh                # noqa: E402
from repro.models.workloads import make_workload            # noqa: E402
from repro.serve import ServeEngine, synth_trace            # noqa: E402
from repro.launch.jaxcache import enable_compilation_cache        # noqa: E402

from .common import (add_obs_args, emit, maybe_enable_obs,        # noqa: E402
                     platform_payload, write_obs)

FAMILY_MIX = ["lm", "lm", "lm", "tree", "lattice"]


def scale_trace(workloads, n, rate, max_new, seed=0, arrivals="constant"):
    return synth_trace(FAMILY_MIX, n, rate, max_new, workloads, seed,
                       prompt_lo=3, prompt_hi=8, tree_leaves=(4, 7),
                       lattice_chars=(5, 9), arrivals=arrivals)


def serve_at(workloads, reqs, *, n_shards, slots_per_shard):
    eng = ServeEngine(workloads, compiled=True, bucketed=True,
                      continuous=True, n_shards=n_shards,
                      max_slots=slots_per_shard * n_shards,
                      plan_cache=FIFOCache(256),
                      schedule_cache=FIFOCache(512),
                      bucket_cache=LRUCache(64))
    eng.submit_many(reqs)
    stats = eng.run()
    return eng, stats


def run(out: str = "", model_size: int = 16, requests: int = 40,
        rate: float = 8.0, max_new: int = 8, slots_per_shard: int = 8,
        seed: int = 0, replicas: tuple[int, ...] = (1, 2, 4),
        arrivals: str = "constant") -> dict:
    workloads = {"lm": make_workload("ChainLM", model_size, seed),
                 "tree": make_workload("TreeLSTM", model_size, seed),
                 "lattice": make_workload("LatticeLSTM", model_size, seed)}
    mesh = make_data_mesh(max(replicas))
    result: dict = {"model_size": model_size, "requests": requests,
                    "rate": rate, "max_new": max_new, "arrivals": arrivals,
                    "slots_per_shard": slots_per_shard,
                    "replicas": list(replicas), "scale": {}}

    baseline: list | None = None
    for k in replicas:
        reqs = scale_trace(workloads, requests, rate, max_new, seed,
                           arrivals)
        eng, stats = serve_at(workloads, reqs, n_shards=k,
                              slots_per_shard=slots_per_shard)
        d = stats.as_dict()
        d["tokens_per_round"] = stats.tokens_per_round
        d["n_buckets"] = len(eng.bucket_cache)
        d["compiles_le_buckets"] = stats.n_compiles <= d["n_buckets"]
        # Replica scaling must not change what is computed: same tokens,
        # same single-shot logits as the 1-replica engine.
        outputs = [(r.out if r.family == "lm" else np.asarray(r.result))
                   for r in reqs]
        if baseline is None:
            baseline = outputs
            d["matches_single_replica"] = True
        else:
            d["matches_single_replica"] = all(
                (a == b if isinstance(a, list)
                 else (a.shape == b.shape and
                       np.allclose(a, b, rtol=1e-5, atol=1e-5)))
                for a, b in zip(baseline, outputs))
        result["scale"][str(k)] = d
        emit(f"bench_scale/replicas_{k}", stats.wall_s * 1e6,
             f"tok_per_round={stats.tokens_per_round:.2f};"
             f"tok_per_s={stats.tok_per_s:.1f};rounds={stats.n_rounds};"
             f"compiles={stats.n_compiles};"
             f"sharded_dispatches={stats.n_sharded_dispatches};"
             f"fallback_rounds={stats.n_shard_fallback_rounds}")

    tpr = [result["scale"][str(k)]["tokens_per_round"] for k in replicas]
    result["tokens_per_round_by_replicas"] = dict(zip(map(str, replicas), tpr))
    result["monotonic_round_throughput"] = all(
        b > a for a, b in zip(tpr, tpr[1:]))
    result["all_compiles_le_buckets"] = all(
        result["scale"][str(k)]["compiles_le_buckets"] for k in replicas)
    result["all_match_single_replica"] = all(
        result["scale"][str(k)]["matches_single_replica"] for k in replicas)
    emit("bench_scale/monotonic", 0.0,
         f"monotonic={result['monotonic_round_throughput']};"
         f"tokens_per_round={'/'.join(f'{t:.2f}' for t in tpr)}")

    # Stamped after the measured phases so the obs_metrics snapshot carries
    # the run's counters, not an empty registry.
    result.update(platform_payload(mesh))
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--model-size", type=int, default=16)
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots-per-shard", type=int, default=8)
    ap.add_argument("--replicas", default="1,2,4",
                    help="comma-separated replica counts to measure")
    ap.add_argument("--arrivals", choices=["constant", "poisson", "burst"],
                    default="constant")
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    maybe_enable_obs(args)
    replicas = tuple(int(x) for x in args.replicas.split(",") if x.strip())
    res = run(out=args.out, model_size=args.model_size,
              requests=args.requests, rate=args.rate, max_new=args.max_new,
              slots_per_shard=args.slots_per_shard, replicas=replicas,
              arrivals=args.arrivals)
    write_obs(args)
    # CI gate: adding replicas must raise round throughput monotonically,
    # never change outputs, and never compile more than once per bucket
    # signature.
    ok = (res["monotonic_round_throughput"]
          and res["all_compiles_le_buckets"]
          and res["all_match_single_replica"])
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
