"""Perf-iteration driver: lower+compile one cell under a named variant and
report the roofline terms.  Used by the EXPERIMENTS.md §Perf loop.

  PYTHONPATH=src python -m repro.launch.hillclimb --arch granite-34b \
      --shape decode_32k --variant unroll
"""

import argparse
import json
import os
import time


def _setup_xla_env() -> None:
    """Fake a 512-device host for mesh experiments.  Called from main()
    BEFORE jax is imported (run() imports it lazily): mutating XLA_FLAGS at
    module import time would leak into anything that merely imports this
    module (tests, tooling) and silently poison an already-initialized jax.
    Caller-provided XLA_FLAGS are preserved; the device-count flag this
    module REQUIRES (the production mesh lays out over 512 fake devices)
    is appended unless the caller already pinned one."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " " if flags else "") \
            + "--xla_force_host_platform_device_count=512"
    if os.environ.get("REPRO_XLA_EXTRA"):
        flags += " " + os.environ["REPRO_XLA_EXTRA"]
    os.environ["XLA_FLAGS"] = flags

VARIANTS: dict[str, dict] = {
    "baseline": {},
    # decode
    "unroll": {"overrides": {"scan_layers": False}},
    "unroll_seqshard": {"overrides": {"scan_layers": False}, "kv_seq": "model"},
    # train
    "remat_dots": {"overrides": {"remat": "dots"}},
    "micro4": {"n_microbatches": 4},
    "micro16": {"n_microbatches": 16},
    "micro4_dots": {"n_microbatches": 4, "overrides": {"remat": "dots"}},
    "no_fsdp": {"fsdp": False},
    "ragged_moe": {"overrides": {"moe_impl": "ragged"}},
    "ragged_micro4": {"overrides": {"moe_impl": "ragged"}, "n_microbatches": 4},
    "cap10": {"overrides": {"capacity_factor": 1.0}},
    "micro4_cap10": {"n_microbatches": 4, "overrides": {"capacity_factor": 1.0}},
    "qblock1k": {"qblock": 1024},
    "scores_bf16": {"overrides": {"attn_scores_dtype": "bfloat16"}},
    "scores_bf16_micro4": {"overrides": {"attn_scores_dtype": "bfloat16"},
                           "n_microbatches": 4},
}


def run(arch: str, shape: str, variant: str) -> dict:
    import jax

    from repro.configs import get_config
    from repro.distributed import sharding as shlib
    from repro.launch import cells as cell_lib
    from repro.launch.dryrun import HBM_BW, LINK_BW, PEAK_FLOPS
    from repro.launch.hlo_analysis import analyze_hlo_text
    from repro.launch.mesh import make_production_mesh

    spec = dict(VARIANTS[variant])
    if spec.pop("kv_seq", None):
        shlib.LOGICAL_RULES["kv_seq"] = "model"
    if spec.pop("qblock", None):
        pass  # q_block is currently fixed in the model; reserved
    cfg = get_config(arch)
    mesh = make_production_mesh()
    fn, args, donate = cell_lib.build_cell(
        cfg, shape, mesh, fsdp=spec.pop("fsdp", True),
        n_microbatches=spec.pop("n_microbatches", None),
        overrides=spec.pop("overrides", None))

    t0 = time.time()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    r = analyze_hlo_text(compiled.as_text())
    out = {
        "arch": arch, "shape": shape, "variant": variant,
        "compile_s": round(compile_s, 1),
        "compute_ms": r["flops_per_device"] / PEAK_FLOPS * 1e3,
        "memory_ms": r["bytes_per_device"] / HBM_BW * 1e3,
        "collective_ms": r["collective_bytes_per_device"] / LINK_BW * 1e3,
        "temp_gib": getattr(mem, "temp_size_in_bytes", 0) / 2**30,
        "arg_gib": getattr(mem, "argument_size_in_bytes", 0) / 2**30,
        "collectives": r["collectives"],
    }
    out["step_ms_bound"] = max(out["compute_ms"], out["memory_ms"],
                               out["collective_ms"])
    return out


def main():
    _setup_xla_env()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()
    out = run(args.arch, args.shape, args.variant)
    out.pop("collectives")
    print(json.dumps(out))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
