"""Kernel microbenchmarks: Pallas kernels vs pure-jnp oracle.

The kernels compile to Mosaic on a TPU and run in the Pallas interpreter
on any other backend.  Under the interpreter wall-clock favors the jnp
path, so a CPU run checks CORRECTNESS at benchmark scale plus the
op-count/fusion story (one fused kernel vs K+2 staged HBM round trips);
its timings are not device numbers.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _timeit(fn, repeat=20):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeat


def run(quick: bool = False) -> dict:
    rng = np.random.default_rng(0)
    results = {}

    # quantile map @ 64k scores, 256-knot tables
    n, nq = (16_384 if quick else 65_536), 256
    scores = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    src = jnp.asarray(np.sort(rng.uniform(0, 1, nq)), jnp.float32)
    refq = jnp.asarray(np.sort(rng.uniform(0, 1, nq)), jnp.float32)
    jnp_qm = jax.jit(ref.quantile_map)
    t_ref = _timeit(lambda: jnp_qm(scores, src, refq))
    out_k = ops.quantile_map(scores, src, refq)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(jnp_qm(scores, src, refq)),
                               rtol=1e-4, atol=1e-5)
    results["quantile_map_jnp_64k"] = {"us_per_call": t_ref * 1e6,
                                       "ns_per_score": t_ref / n * 1e9,
                                       "kernel_allclose": True}

    # fused score pipeline @ 64k x 8 experts
    k = 8
    raw = jnp.asarray(rng.uniform(0, 1, (n, k)), jnp.float32)
    betas = jnp.asarray(rng.uniform(0.02, 0.5, k), jnp.float32)
    weights = jnp.ones((k,), jnp.float32)
    jnp_sp = jax.jit(ref.score_pipeline)
    t_sp = _timeit(lambda: jnp_sp(raw, betas, weights, src, refq))
    out_k = ops.score_pipeline(raw, betas, weights, src, refq)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(jnp_sp(raw, betas, weights, src, refq)),
        rtol=1e-4, atol=1e-5)
    results["score_pipeline_jnp_64kx8"] = {"us_per_call": t_sp * 1e6,
                                           "ns_per_event": t_sp / n * 1e9,
                                           "kernel_allclose": True}

    # banked (tenant-indexed) pipeline: block-skip fast path story.  The
    # prefetched kernel skips the one-hot gather matmuls on all-one-tenant
    # blocks; a sorted-by-tenant layout (what shard-bucketing produces)
    # skips every block, the adversarial interleave skips none.
    from repro.core.transforms import banked_score_pipeline
    from repro.kernels.score_pipeline import banked_skip_stats
    t_bank = 64
    banked_betas = jnp.asarray(rng.uniform(0.05, 1.0, (t_bank, k)), jnp.float32)
    banked_w = jnp.asarray(rng.uniform(0.1, 2.0, (t_bank, k)), jnp.float32)
    banked_src = jnp.asarray(np.sort(rng.uniform(0, 1, (t_bank, nq)), -1),
                             jnp.float32)
    banked_ref = jnp.asarray(np.sort(rng.uniform(0, 1, (t_bank, nq)), -1),
                             jnp.float32)
    # sorted: equal block-aligned per-tenant runs (what shard-bucketed,
    # per-tenant-bursty windows look like); adversarial: row-interleaved
    tid_sorted = jnp.asarray(np.repeat(np.arange(t_bank, dtype=np.int32),
                                       n // t_bank))
    tid_adv = jnp.asarray((np.arange(n) % t_bank).astype(np.int32))
    block = 256

    def banked(tid):
        return ops.score_pipeline_banked(
            raw, tid, banked_betas, banked_w, banked_src, banked_ref,
            block=block)

    t_sorted = _timeit(lambda: banked(tid_sorted), repeat=3)
    t_adv = _timeit(lambda: banked(tid_adv), repeat=3)
    oracle = jax.jit(banked_score_pipeline)
    for tid in (tid_sorted, tid_adv):
        np.testing.assert_allclose(
            np.asarray(banked(tid)),
            np.asarray(oracle(raw, tid, banked_betas, banked_w, banked_src,
                              banked_ref)),
            rtol=1e-4, atol=1e-5)
    skip_sorted = banked_skip_stats(np.asarray(tid_sorted), block=block)
    skip_adv = banked_skip_stats(np.asarray(tid_adv), block=block)
    results[f"score_pipeline_banked_{n // 1024}kx{k}"] = {
        "us_per_call": t_sorted * 1e6,
        "us_per_call_adversarial": t_adv * 1e6,
        "skip_rate_sorted": skip_sorted["skip_rate"],
        "skip_rate_adversarial": skip_adv["skip_rate"],
        "kernel_allclose": True,
    }

    # flash attention 1k x 8h GQA vs oracle
    b, t, hq, hkv, d = 1, (256 if quick else 1024), 8, 2, 64
    q = jnp.asarray(rng.normal(0, 1, (b, t, hq, d)), jnp.bfloat16)
    kk = jnp.asarray(rng.normal(0, 1, (b, t, hkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (b, t, hkv, d)), jnp.bfloat16)
    jnp_fa = jax.jit(lambda a, b_, c: ref.flash_attention(a, b_, c, causal=True))
    t_fa = _timeit(lambda: jnp_fa(q, kk, v), repeat=5)
    out_k = ops.flash_attention(q, kk, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(jnp_fa(q, kk, v), np.float32),
        rtol=3e-2, atol=3e-2)
    results[f"flash_attention_jnp_{t}"] = {"us_per_call": t_fa * 1e6,
                                           "kernel_allclose": True}

    # decode attention over 16k cache
    s = 4096 if quick else 16_384
    qd = jnp.asarray(rng.normal(0, 1, (4, hq, d)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(0, 1, (4, s, hkv, d)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(0, 1, (4, s, hkv, d)), jnp.bfloat16)
    vlen = jnp.full((4,), s, jnp.int32)
    jnp_da = jax.jit(ref.decode_attention)
    t_da = _timeit(lambda: jnp_da(qd, kc, vc, vlen), repeat=10)
    out_k = ops.decode_attention(qd, kc, vc, vlen)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32),
        np.asarray(jnp_da(qd, kc, vc, vlen), np.float32),
        rtol=3e-2, atol=3e-2)
    results[f"decode_attention_jnp_{s}"] = {"us_per_call": t_da * 1e6,
                                            "kernel_allclose": True}
    return results


def main() -> None:
    res = run()
    for k, v in res.items():
        print(f"{k:>30}: {v}")


if __name__ == "__main__":
    main()
