"""Tenant-sharded transform banks: per-shard residency + dispatch throughput.

The sharded topology's two headline claims, measured at 256–4096 tenants:

  * **residency** — a shard holds ``Tl·(2K+2N)·4`` bank bytes, shrinking
    ~1/S with shard count S at fixed tenant count (the scaling move past
    ~10k tenants the ROADMAP flags);
  * **throughput** — the shard-bucketed ``shard_map`` dispatch must not
    regress vs the dense single-replica banked kernel at S=1 (on a CPU
    backend both run the interpreted kernel; the S>1 numbers there document
    the host-bucketing + launch overhead, not real-device scaling).

Every configuration asserts BITWISE f32 parity against the dense kernel
before it is timed.  Emits ``benchmarks/results/BENCH_sharded_bank.json``.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.transforms import ShardedTransformBank, TransformBank
from repro.kernels import ops
from repro.launch.mesh import make_tenant_mesh
from repro.serving.server import ShardedBankDispatcher

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "results",
                            "BENCH_sharded_bank.json")


def _timeit(fn, repeat=10):
    fn()                                   # warm (trace/compile)
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat


def _random_bank(rng, t, k, n) -> TransformBank:
    return TransformBank(
        betas=jnp.asarray(rng.uniform(0.05, 1.0, (t, k)), jnp.float32),
        weights=jnp.asarray(rng.uniform(0.1, 2.0, (t, k)), jnp.float32),
        src_quantiles=jnp.asarray(
            np.sort(rng.uniform(0, 1, (t, n)), -1), jnp.float32),
        ref_quantiles=jnp.asarray(
            np.sort(rng.uniform(0, 1, (t, n)), -1), jnp.float32))


def run(quick: bool = False) -> dict:
    k, n = 4, 256
    b = 2048 if quick else 8192
    tenant_counts = (256, 1024) if quick else (256, 1024, 4096)
    shard_counts = [s for s in (1, 2, 4, 8) if s <= jax.device_count()]
    repeat = 5 if quick else 10
    rng = np.random.default_rng(0)

    rows: list[dict] = []
    for t in tenant_counts:
        bank = _random_bank(rng, t, k, n)
        dense_bytes = t * (2 * k + 2 * n) * 4
        scores = rng.uniform(0, 1, (b, k)).astype(np.float32)
        tid = rng.integers(0, t, b)
        tid_j = jnp.asarray(tid.astype(np.int32))
        scores_j = jnp.asarray(scores)

        def dense_call():
            return jax.block_until_ready(ops.score_pipeline_banked(
                scores_j, tid_j, bank.betas, bank.weights,
                bank.src_quantiles, bank.ref_quantiles))

        dense_s = _timeit(dense_call, repeat)
        dense = np.asarray(dense_call())
        rows.append({
            "tenants": t, "shards": 0, "path": "dense",
            "us_per_batch": dense_s * 1e6,
            "events_per_s": b / dense_s,
            "resident_bytes": dense_bytes,
            "residency_ratio": 1.0,
            "bitwise_parity": True,
        })

        for s in shard_counts:
            sbank = ShardedTransformBank.from_dense(bank, s)
            disp = ShardedBankDispatcher(make_tenant_mesh(s))
            got = disp(scores, tid, sbank)
            parity = bool(np.array_equal(got.view(np.uint32),
                                         dense.view(np.uint32)))
            sh_s = _timeit(lambda: disp(scores, tid, sbank), repeat)
            rows.append({
                "tenants": t, "shards": s, "path": "sharded",
                "us_per_batch": sh_s * 1e6,
                "events_per_s": b / sh_s,
                "resident_bytes": sbank.per_shard_bytes,
                "residency_ratio": sbank.per_shard_bytes / dense_bytes,
                "bitwise_parity": parity,
            })

    t_max = tenant_counts[-1]
    s_max = shard_counts[-1]
    by = {(r["tenants"], r["shards"], r["path"]): r for r in rows}
    dense_row = by[(t_max, 0, "dense")]
    s1_row = by[(t_max, 1, "sharded")]
    smax_row = by[(t_max, s_max, "sharded")]
    result = {
        "batch": b, "experts": k, "knots": n,
        "tenant_counts": list(tenant_counts),
        "shard_counts": shard_counts,
        "rows": rows,
        "max_tenants": t_max,
        "max_shards": s_max,
        "residency_ratio_at_smax": smax_row["residency_ratio"],
        "per_shard_bytes_at_smax": smax_row["resident_bytes"],
        "us_per_batch_smax": smax_row["us_per_batch"],
        # >= 1.0 means the S=1 sharded path costs no more than dense
        "throughput_ratio_s1": (s1_row["events_per_s"]
                                / dense_row["events_per_s"]),
        "all_bitwise_parity": all(r["bitwise_parity"] for r in rows),
    }
    os.makedirs(os.path.dirname(RESULTS_PATH), exist_ok=True)
    with open(RESULTS_PATH, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    r = run()
    print(f"# wrote {RESULTS_PATH}")
    print(f"{'tenants':>8} {'shards':>7} {'path':>8} {'us/batch':>10} "
          f"{'events/s':>12} {'resident_kb':>12} {'1/S ratio':>10}")
    for row in r["rows"]:
        print(f"{row['tenants']:>8} {row['shards']:>7} {row['path']:>8} "
              f"{row['us_per_batch']:>10.1f} {row['events_per_s']:>12.0f} "
              f"{row['resident_bytes'] / 1024:>12.1f} "
              f"{row['residency_ratio']:>10.3f}")
    print(f"# residency@S={r['max_shards']}: {r['residency_ratio_at_smax']:.3f}"
          f" of dense; throughput_ratio_s1={r['throughput_ratio_s1']:.2f}x;"
          f" bitwise_parity={r['all_bitwise_parity']}")


if __name__ == "__main__":
    main()
