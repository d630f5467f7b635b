import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import (device count locks on first jax init).

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, with zero real allocation (ShapeDtypeStruct
inputs):
  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — FLOPs / bytes for §Roofline
  * collective payload bytes    — parsed from the partitioned HLO
and writes a JSON report consumed by launch/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch, list_archs
from repro.configs.base import ArchDef
from repro.distributed.sharding import batch_sharding, param_sharding
from repro.launch import hlo_analysis, steps
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.train.optimizer import AdamWState

REPORT_DIR = Path(__file__).resolve().parents[3] / "reports" / "dryrun"


def _dp(mesh):
    axes = dp_axes(mesh)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def cache_sharding(cache_spec, mesh, batch: int):
    """LM decode cache: batch over dp when divisible, else seq over dp;
    the trailing latent/head dim shards over 'model' when divisible."""
    dp = _dp(mesh)
    dps = _dp_size(mesh)
    mp = mesh.shape.get("model", 1)

    def leaf(path, s):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name == "length":
            return NamedSharding(mesh, P())
        dims = [None] * len(s.shape)          # (L, B, T, ...) layouts
        if batch % dps == 0 and batch >= dps:
            dims[1] = dp
        elif s.shape[2] % dps == 0:
            dims[2] = dp
        if s.shape[-1] % mp == 0:
            dims[-1] = "model"
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(leaf, cache_spec)


def opt_sharding(opt_spec: AdamWState, params_sh) -> AdamWState:
    mesh = jax.tree.leaves(params_sh)[0].mesh
    return AdamWState(step=NamedSharding(mesh, P()), m=params_sh,
                      v=params_sh, master=params_sh)


def _param_bytes(params_spec) -> float:
    return sum(np.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree.leaves(params_spec))


def shardings_for(arch: ArchDef, shape: str, kind: str, state, mesh):
    # inference replicates weights over the data axes when they fit HBM
    # (<= 8 GB/device after model-axis sharding) — ZeRO all-gathers are a
    # training-only cost (§Perf iteration: granite prefill collectives)
    mp = mesh.shape.get("model", 1)
    drop_fsdp = (kind != "train"
                 and _param_bytes(state[0]) / mp <= 8e9)
    params_sh = param_sharding(state[0], mesh, arch.family,
                               drop_fsdp=drop_fsdp)
    # non-TP families spread the batch over EVERY mesh axis — leaving the
    # 'model' axis idle replicates compute mp-fold (§Perf iteration)
    batch_logical = "batch" if arch.family == "lm" else "batch_all"
    if kind == "train":
        opt_sh = opt_sharding(state[1], params_sh)
        overrides = {"^query$": P()} if arch.family == "ssh" else {}
        batch_sh = batch_sharding(state[2], mesh, overrides,
                                  batch_logical=batch_logical)
        return (params_sh, opt_sh, batch_sh), (params_sh, opt_sh, None), (0, 1)
    if kind == "decode":
        b = arch.shapes[shape].meta["batch"]
        cache_sh = cache_sharding(state[1], mesh, b)
        tok_sh = batch_sharding(state[2], mesh)
        return (params_sh, cache_sh, tok_sh), (None, cache_sh), (1,)
    # single-batch-arg kinds
    overrides = {}
    if arch.family == "ssh":
        overrides = {"query": P()}
    batch_sh = batch_sharding(state[1], mesh, overrides,
                              batch_logical=batch_logical)
    return (params_sh, batch_sh), None, ()


def run_cell(arch_name: str, shape: str, multi_pod: bool,
             report_dir: Path = REPORT_DIR, verbose: bool = True) -> dict:
    arch = get_arch(arch_name)
    mesh_name = "multi" if multi_pod else "single"
    out_path = report_dir / f"{arch_name}__{shape}__{mesh_name}.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind, state = steps.abstract_state(arch, shape)
    step = steps.make_step(arch, shape, kind)
    in_sh, out_sh, donate = shardings_for(arch, shape, kind, state, mesh)

    from repro.distributed.constraints import activation_sharding
    with mesh, activation_sharding(mesh):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*state)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # NOTE: XLA cost_analysis counts while bodies once and is per-device —
    # the executed_costs parser multiplies loop trip counts (validated
    # exact on hand-countable programs; see tests/test_hlo_graph.py).
    raw_flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    from repro.launch.hlo_graph import executed_costs
    hlo = compiled.as_text()
    execd = executed_costs(hlo)
    n_chips = int(mesh.devices.size)
    flops_total = execd.dot_flops * n_chips      # whole-mesh dot FLOPs
    coll_bytes = execd.total_coll_bytes          # per-device payload bytes
    terms = hlo_analysis.roofline_terms(flops_total, bytes_accessed * n_chips,
                                        coll_bytes, n_chips)

    report = {
        "arch": arch_name, "shape": shape, "mesh": mesh_name,
        "kind": kind, "n_chips": n_chips,
        "compile_seconds": round(time.time() - t0, 1),
        "flops": flops_total,
        "flops_per_device": execd.dot_flops,
        "cost_analysis_raw_flops": raw_flops,
        "bytes_accessed_per_device": bytes_accessed,
        "collectives": {k: {"bytes": execd.coll_bytes[k],
                            "count": execd.coll_counts[k]}
                        for k in execd.coll_bytes},
        "collective_bytes": coll_bytes,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "roofline": terms,
    }
    out_path.write_text(json.dumps(report, indent=2))
    if verbose:
        print(f"[OK] {arch_name}/{shape}/{mesh_name}: "
              f"compile={report['compile_seconds']}s "
              f"flops={flops_total:.3e} bytes={bytes_accessed:.3e} "
              f"coll={coll_bytes:.3e}B dominant={terms['dominant']}")
        print(f"     memory_analysis: {mem}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--report-dir", type=str, default=str(REPORT_DIR))
    args = ap.parse_args()

    cells = []
    if args.all:
        for name in list_archs():
            arch = get_arch(name)
            for shape in arch.shapes:
                cells.append((name, shape))
    else:
        arch = get_arch(args.arch)
        shapes = [args.shape] if args.shape else list(arch.shapes)
        cells = [(args.arch, s) for s in shapes]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    rdir = Path(args.report_dir)
    failures = []
    for name, shape in cells:
        for mp in meshes:
            tag = f"{name}__{shape}__{'multi' if mp else 'single'}"
            if args.skip_existing and (rdir / f"{tag}.json").exists():
                print(f"[skip] {tag}")
                continue
            try:
                run_cell(name, shape, mp, rdir)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append(tag)
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nAll dry-run cells compiled.")


if __name__ == "__main__":
    main()
