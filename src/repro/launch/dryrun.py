import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST precede every other import (including any
# ``from repro...``) — jax locks the device count at first backend init.

__doc__ = """Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell: build the production mesh,
apply the sharding rules, ``jit(step).lower(*abstract_args).compile()``, and
record memory_analysis + cost_analysis + the collective schedule.  Succeeds
for BOTH the single-pod (16x16) and multi-pod (2x16x16) meshes.

The XLA_FLAGS line above MUST run before any jax import — jax locks the
device count at first backend initialization.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--both]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, applicable_shapes
from repro.launch import mesh as mesh_lib
from repro.launch import roofline, shardings, specs
from repro.training.optimizer import AdamWState
from repro.training.train import TrainState

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _logits_sharding(mesh, batch, vocab, ndim):
    model = mesh_lib.model_axis_size(mesh)
    daxes = mesh_lib.data_axes(mesh)
    dsize = mesh_lib.data_axis_size(mesh)
    spec = [None] * ndim
    if batch % dsize == 0 and dsize > 1:
        spec[0] = daxes
    if vocab % model == 0:
        spec[-1] = "model"
    return NamedSharding(mesh, P(*spec))


# per-device param-bytes budget above which we switch to FSDP (ZeRO-3)
FSDP_THRESHOLD_BYTES = 4 * 2**30


def _decide_fsdp(params, mesh) -> bool:
    per_dev = shardings.total_param_bytes(params) / mesh_lib.model_axis_size(mesh)
    return per_dev > FSDP_THRESHOLD_BYTES


def build_shardings(bundle: specs.StepBundle, mesh, *,
                    fsdp: bool | None = None):
    """(in_shardings, out_shardings, fsdp_used) pytrees for this step."""
    rep = shardings.replicated(mesh)
    b = bundle.shape.global_batch
    cfg = bundle.cfg
    batch_sh = NamedSharding(mesh, shardings.batch_pspec(b, mesh, 0))

    if bundle.kind == "train":
        state, tokens, labels = bundle.abstract_args
        if fsdp is None:
            # training state is ~3x f32 params: 12 bytes/param
            fsdp = _decide_fsdp(state.params, mesh) or _decide_fsdp(
                state.opt.mu, mesh)
        psh = shardings.params_shardings(state.params, mesh, fsdp=fsdp)
        state_sh = TrainState(
            params=psh,
            opt=AdamWState(step=rep, mu=psh, nu=psh),
        )
        tok_sh = shardings.tokens_sharding(b, mesh)
        metrics_sh = jax.tree.map(lambda _: rep, bundle.step_fn and
                                  _abstract_metrics())
        return (state_sh, tok_sh, tok_sh), (state_sh, metrics_sh), fsdp

    params = bundle.abstract_args[0]
    if fsdp is None:
        fsdp = _decide_fsdp(params, mesh)
    psh = shardings.params_shardings(params, mesh, fsdp=fsdp)

    if bundle.kind == "prefill":
        inputs = bundle.abstract_args[1]
        in_sh = {
            k: NamedSharding(mesh, shardings.batch_pspec(
                b, mesh, v.ndim - 1))
            for k, v in inputs.items()
        }
        # step may carry bare-PartitionSpec constraints / shard_map
        with jax.set_mesh(mesh):
            out = jax.eval_shape(bundle.step_fn, *bundle.abstract_args)
        out_sh = {}
        if "logits" in out:
            out_sh["logits"] = _logits_sharding(
                mesh, b, cfg.vocab_size, out["logits"].ndim)
        out_sh["risk_score"] = batch_sh
        if "cache" in out:
            out_sh["cache"] = shardings.cache_shardings(out["cache"], b, mesh)
        return (psh, in_sh), out_sh, fsdp

    # decode
    _, cache, inputs, _pos = bundle.abstract_args
    cache_sh = shardings.cache_shardings(cache, b, mesh)
    in_sh = {
        k: NamedSharding(mesh, shardings.batch_pspec(b, mesh, v.ndim - 1))
        for k, v in inputs.items()
    }
    out_sh = {
        "logits": _logits_sharding(mesh, b, cfg.vocab_size, 2),
        "risk_score": batch_sh,
        "cache": cache_sh,
    }
    return (psh, cache_sh, in_sh, shardings.replicated(mesh)), out_sh, fsdp


def _abstract_metrics():
    from repro.training.train import StepMetrics
    z = jax.ShapeDtypeStruct((), jnp.float32)
    return StepMetrics(z, z, z, z)


def variant_build_kwargs(variant: str, kind_hint: str, mesh) -> dict:
    """§Perf optimization bundles, keyed by --variant.

    ``opt``:
      train/prefill -> sequence-parallel residual stream (T on "model") +
                        bf16 master-weight cast before collectives (train);
      decode        -> weight-stationary layout: residual d on "data" so
                        FSDP'd weights are contracted in place instead of
                        all-gathered per step.
    """
    if variant == "baseline":
        return {}
    daxes = mesh_lib.data_axes(mesh)
    out: dict = {}
    if kind_hint == "decode":
        out["act_pspec"] = P(None, None, "data")
    elif kind_hint == "train":
        out["act_pspec"] = P(daxes, "model", None)
        out["cast_params_bf16"] = True
    else:
        out["act_pspec"] = P(daxes, "model", None)
    if variant in ("opt2", "opt3"):
        out["moe_ep_constraint"] = True
    if variant == "opt3" and kind_hint == "train":
        out["remat"] = False  # drop the remat re-forward weight-gather pass
    if variant == "opt4" and kind_hint != "decode":
        out["moe_impl"] = "a2a"  # shard_map all-to-all expert parallelism
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             moment_dtype=jnp.float32, verbose: bool = True,
             extra_tag: str = "", fsdp: bool | None = None,
             variant: str = "baseline",
             **build_kwargs) -> dict:
    t0 = time.perf_counter()
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    kind_hint = specs.SHAPES[shape_name].kind
    build_kwargs = {**variant_build_kwargs(variant, kind_hint, mesh),
                    **build_kwargs}
    bundle = specs.build_step(arch, shape_name, moment_dtype=moment_dtype,
                              **build_kwargs)
    in_sh, out_sh, fsdp_used = build_shardings(bundle, mesh, fsdp=fsdp)

    with jax.set_mesh(mesh):
        jitted = jax.jit(
            bundle.step_fn,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=bundle.donate_argnums,
        )
        lowered = jitted.lower(*bundle.abstract_args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    if bundle.kind == "train":
        # params + grads + both moments in training dtype
        pb = shardings.total_param_bytes(bundle.abstract_args[0].params)
        mb = shardings.total_param_bytes(bundle.abstract_args[0].opt.mu)
        param_bytes = pb * 2 + mb * 2
        cache_bytes = 0.0
    else:
        param_bytes = shardings.total_param_bytes(bundle.abstract_args[0])
        cache_bytes = (
            shardings.total_param_bytes(bundle.abstract_args[1])
            if bundle.kind == "decode" else 0.0
        )
    report = roofline.analyze(compiled, bundle.cfg, bundle.shape,
                              bundle.kind, mesh, arch,
                              param_bytes_global=param_bytes,
                              cache_bytes_global=cache_bytes)
    mem = compiled.memory_analysis()
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": report.mesh_desc,
        "multi_pod": multi_pod,
        "kind": bundle.kind,
        "fsdp": fsdp_used,
        "variant": variant,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        } if mem is not None else None,
        "roofline": report.as_dict(),
    }
    if verbose:
        ma = result["memory_analysis"] or {}
        arg_gb = (ma.get("argument_bytes") or 0) / 2**30
        tmp_gb = (ma.get("temp_bytes") or 0) / 2**30
        print(
            f"[dryrun] {arch:>26s} x {shape_name:<12s} mesh={report.mesh_desc:<16s}"
            f" compile={t_compile:7.1f}s args/dev={arg_gb:7.2f}GiB"
            f" temp/dev={tmp_gb:6.2f}GiB flops/dev={report.flops_per_chip:.3e}"
            f" coll/dev={report.collective_bytes_per_chip:.3e}B"
            f" bottleneck={report.bottleneck}"
        )
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = "pod2" if multi_pod else "pod1"
    if variant != "baseline":
        tag += f"_{variant}"
    if extra_tag:
        tag += f"_{extra_tag}"
    fname = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{tag}.json")
    with open(fname, "w") as f:
        json.dump(result, f, indent=2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(specs.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod AND multi-pod meshes")
    ap.add_argument("--bf16-moments", action="store_true",
                    help="AdamW moments in bf16 (memory optimization)")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt", "opt2", "opt3", "opt4"],
                    help="§Perf optimization bundle (see variant_build_kwargs)")
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    moment_dtype = jnp.bfloat16 if args.bf16_moments else jnp.float32
    meshes = [False, True] if args.both else [args.multi_pod]

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in applicable_shapes(arch):
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, multi_pod=mp, moment_dtype=moment_dtype,
                         variant=args.variant,
                         extra_tag="bf16m" if args.bf16_moments else "")
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] FAILED {arch} x {shape} multi_pod={mp}: {e}")
                if not args.continue_on_error:
                    traceback.print_exc()
                    raise
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells) * len(meshes)} dry-run cells compiled OK")


if __name__ == "__main__":
    main()
