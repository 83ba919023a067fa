import os
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    if os.environ.get("REPRO_XLA_EXTRA"):  # e.g. --xla_dump_to=... to debug
        os.environ["XLA_FLAGS"] += " " + os.environ["REPRO_XLA_EXTRA"]

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

Run as a program, the lines above MUST stay the first statements in this
module (before any jax import) — jax locks the platform and the device
count on first init.  The dry-run runs on the CPU's virtual devices only,
and its subprocesses inherit that environment, so neither it nor its
children ever claim an accelerator.  Importing the module (for its peak
constants) leaves the environment alone.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all [--multi-pod both]
With --arch all, each cell runs in a subprocess (crash isolation, bounded
RSS); results land in artifacts/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

# v5e hardware constants (per chip)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
LINK_BW = 50e9           # bytes/s per ICI link


def run_cell(arch: str, shape_name: str, multi_pod: bool, save_hlo: bool = False) -> dict:
    import jax

    from repro.configs import get_config
    from repro.configs.base import cells
    from repro.launch import cells as cell_lib
    from repro.launch.hlo_analysis import analyze_hlo_text
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    if shape_name not in cells(arch):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long_500k skipped for full-attention arch (DESIGN.md)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    fn, args, donate = cell_lib.build_cell(cfg, shape_name, mesh)

    t0 = time.time()
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else (ca or {})
    hlo = compiled.as_text()
    mine = analyze_hlo_text(hlo)

    flops_dev = mine["flops_per_device"]
    bytes_dev = mine["bytes_per_device"]
    coll_dev = mine["collective_bytes_per_device"]

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "xla_cost_analysis": {"flops": ca.get("flops"),
                              "bytes_accessed": ca.get("bytes accessed")},
        "per_device": {"flops": flops_dev, "bytes": bytes_dev,
                       "collective_bytes": coll_dev},
        "collectives": mine["collectives"],
        "roofline_s": {
            "compute": flops_dev / PEAK_FLOPS,
            "memory": bytes_dev / HBM_BW,
            "collective": coll_dev / LINK_BW,
        },
    }
    terms = result["roofline_s"]
    result["bottleneck"] = max(terms, key=terms.get)
    if save_hlo:
        result["hlo_path"] = _artifact_path(arch, shape_name, multi_pod, ext=".hlo.txt")
        with open(result["hlo_path"], "w") as f:
            f.write(hlo)
    # spec-mandated prints
    print(mem)
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    return result


def _artifact_path(arch, shape, multi_pod, ext=".json"):
    os.makedirs(ARTIFACTS, exist_ok=True)
    mesh = "2x16x16" if multi_pod else "16x16"
    return os.path.join(ARTIFACTS, f"{arch}__{shape}__{mesh}{ext}")


def _run_one_subprocess(arch, shape, multi_pod, save_hlo) -> dict:
    path = _artifact_path(arch, shape, multi_pod)
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--multi-pod", "on" if multi_pod else "off",
           "--out", path]
    if save_hlo:
        cmd.append("--save-hlo")
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3600)
    if r.returncode != 0 or not os.path.exists(path):
        return {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "error": (r.stderr or "")[-2000:]}
    with open(path) as f:
        return json.load(f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", default="off", choices=["on", "off", "both"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    from repro.configs import list_archs
    from repro.configs.base import cells

    archs = list_archs() if args.arch == "all" else [args.arch]
    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]

    if len(archs) == 1 and args.shape != "all":
        # single cell, in-process
        res = {}
        for mp in pods:
            try:
                res = run_cell(archs[0], args.shape, mp, save_hlo=args.save_hlo)
            except Exception:
                res = {"arch": archs[0], "shape": args.shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "error": traceback.format_exc()[-3000:]}
            out = args.out or _artifact_path(archs[0], args.shape, mp)
            with open(out, "w") as f:
                json.dump(res, f, indent=1)
            print(json.dumps({k: res.get(k) for k in
                              ("arch", "shape", "mesh", "compile_s",
                               "bottleneck", "error", "skipped")}))
        sys.exit(0 if "error" not in res else 1)

    failures = 0
    for arch in archs:
        shapes = cells(arch) if args.shape == "all" else [args.shape]
        for shape in shapes:
            for mp in pods:
                t0 = time.time()
                res = _run_one_subprocess(arch, shape, mp, args.save_hlo)
                ok = "error" not in res
                failures += not ok
                print(f"{'OK  ' if ok else 'FAIL'} {arch:22s} {shape:12s} "
                      f"{'2x16x16' if mp else '16x16':8s} "
                      f"{time.time()-t0:6.1f}s  bottleneck={res.get('bottleneck')}",
                      flush=True)
                if not ok:
                    print("  " + res["error"].splitlines()[-1] if res.get("error") else "")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
