"""Performance probes: link calibration (library) + the perf-iteration cell
probe (CLI).

**Library entry point** -- ``probe_links(mesh) -> MachineProfile`` runs the
``repro.obs.calibrate`` microbenchmarks (ring ppermutes per mesh axis,
jit'd matmul peak) and returns the fitted α–β machine profile the planner
consumes via ``build_plan(profile=...)``.  Importing this module is
side-effect free (no env mutation, no jax init).

**CLI** -- the default ``__main__`` mode calibrates and writes the
machine-profile JSON:

    PYTHONPATH=src python -m repro.launch.perf_probe \
        --profile-out machine_profile.json --devices 8 --mesh-shape 2x2

``--tune`` additionally runs the measured kernel autotune search
(``repro.tune``) over ``--tune-shapes`` and embeds the resulting
``TuningTable`` in the profile (and, with ``--tune-out``, as its own
artifact) -- one probe run yields both calibration halves: fitted α–β
links for the comm side and measured kernel seconds for the compute side
of ``calibrated_total_s``.

The legacy perf-iteration mode (lower ONE arch x shape cell with config
overrides and print the roofline terms; the Sec.-Perf hillclimb driver)
is selected by ``--arch``:

    PYTHONPATH=src python -m repro.launch.perf_probe \
        --arch granite-20b --shape train_4k \
        --set remat=none attn_probs_dtype=bf16 --no-zero --tag it3

Overrides apply dataclasses.replace on the arch config; measurement always
uses the final analyzer (invariant-aware by default; --naive-analyzer for
the pessimistic count).  Appends a JSON record to perf_iterations.json.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.obs.calibrate import probe_links  # noqa: F401  (library API)
from repro.obs.profile import MachineProfile, save_profile  # noqa: F401


def parse_override(kv: str):
    k, v = kv.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            pass
    if v in ("True", "False"):
        return k, v == "True"
    return k, v


def _parse_mesh_shape(spec: str):
    return tuple(int(s) for s in spec.lower().split("x") if s)


def calibrate_main(args) -> None:
    """Default mode: probe the links, write the machine-profile JSON."""
    if args.devices > 1 and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # a forced-host farm is a CPU run: pin it there, or a machine with
        # a chip would calibrate one real device in place of the farm
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} "
            f"--xla_force_host_platform_device_count={args.devices}").strip()
    import jax
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.mesh import make_mesh

    enable_compile_cache()

    mesh = None
    devs = np.array(jax.devices())
    if args.mesh_shape and len(devs) > 1:
        shape = _parse_mesh_shape(args.mesh_shape)
        names = ("x", "y", "z")[:len(shape)] if len(shape) > 1 else ("t",)
        import math

        mesh = make_mesh(shape, names, devices=devs[:math.prod(shape)])
    tree_axes = tuple(a for a in args.tree_axes.split(",") if a)
    profile = probe_links(mesh, reps=args.reps, tree_axes=tree_axes)
    if args.tune:
        import dataclasses

        from repro.tune import Tuner, save_table

        tuner = Tuner(reps=args.tune_reps,
                      max_candidates=args.tune_candidates or None)
        for spec in args.tune_shapes.split(","):
            if not spec:
                continue
            tm, tn, tk = _parse_mesh_shape(spec)
            tuner.entry_for(tm, tn, tk, dtype=args.tune_dtype)
        table = tuner.table()
        profile = dataclasses.replace(profile, tuning=table)
        if args.tune_out:
            save_table(table, args.tune_out)
            print(f"# wrote {args.tune_out}")
    save_profile(profile, args.profile_out)
    print(json.dumps(profile.to_json(), indent=1, sort_keys=True))
    print(f"# wrote {args.profile_out}")


def cell_probe_main(args) -> None:
    """Legacy perf-iteration mode (``--arch``): one cell, roofline terms."""
    # must precede jax init: the cell probe needs a forced device farm,
    # which is a CPU run (importing repro.launch.dryrun below pins it too)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    from repro.configs import canonical
    from repro.launch.dryrun import lower_cell
    from repro.mesh import make_production_mesh

    overrides = dict(parse_override(kv) for kv in args.set)

    # monkey-patch get_config so lower_cell sees the overridden config
    import dataclasses

    import repro.launch.dryrun as dr
    base_get = dr.get_config

    def patched(name):
        cfg = base_get(name)
        return dataclasses.replace(cfg, **overrides) if overrides else cfg

    dr.get_config = patched

    if args.naive_analyzer:
        import repro.roofline.hlo_stats as hs
        orig = hs.analyze
        hs.analyze = lambda text, invariant_aware=True: orig(text, False)

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    t0 = time.perf_counter()
    rec = lower_cell(canonical(args.arch), args.shape, mesh,
                     remat=args.remat, zero=not args.no_zero)
    rec.update(tag=args.tag, overrides=overrides, zero=not args.no_zero,
               remat=args.remat, analyzer="naive" if args.naive_analyzer
               else "invariant-aware", wall_s=round(time.perf_counter() - t0, 1))
    r = rec["roofline"]
    print(json.dumps({
        "tag": args.tag, "arch": rec["arch"], "shape": rec["shape"],
        "dominant": r["dominant"],
        "compute_s": r["compute_s"], "memory_s": r["memory_s"],
        "collective_s": r["collective_s"], "step_bound_s": r["step_s_bound"],
        "roofline_fraction": r["roofline_fraction"],
        "coll_by_kind": r["coll_by_kind"],
        "peak_GiB": round((rec["memory"]["peak_bytes"] or 0) / 2**30, 2),
    }, indent=1))
    hist = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            hist = json.load(f)
    hist.append(rec)
    with open(args.out, "w") as f:
        json.dump(hist, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    # calibration mode (default)
    ap.add_argument("--profile-out", default="machine_profile.json")
    ap.add_argument("--devices", type=int, default=1,
                    help="forced host device count for CPU calibration")
    ap.add_argument("--mesh-shape", default="",
                    help="e.g. 2x2 or 8 -- mesh to probe axes on")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tree-axes", default="",
                    help="comma-separated inter-pod (DCN-class) mesh axes; "
                         "pooled into a 'dcn' link class instead of 'ici'")
    ap.add_argument("--tune", action="store_true",
                    help="also run the kernel autotune search and embed "
                         "the TuningTable in the profile")
    ap.add_argument("--tune-shapes", default="256x256x256,384x128x256",
                    help="comma-separated MxNxK shapes to tune")
    ap.add_argument("--tune-reps", type=int, default=3)
    ap.add_argument("--tune-candidates", type=int, default=8,
                    help="bound the per-shape candidate search (0 = full)")
    ap.add_argument("--tune-dtype", default="float32")
    ap.add_argument("--tune-out", default="",
                    help="also write the TuningTable as its own JSON")
    # legacy cell-probe mode (selected by --arch)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--set", nargs="*", default=[], metavar="key=val")
    ap.add_argument("--remat", default="config")
    ap.add_argument("--no-zero", action="store_true")
    ap.add_argument("--naive-analyzer", action="store_true")
    ap.add_argument("--tag", default="probe")
    ap.add_argument("--out", default="perf_iterations.json")
    args = ap.parse_args()

    if args.arch is not None:
        if args.shape is None:
            ap.error("--arch requires --shape")
        cell_probe_main(args)
    else:
        calibrate_main(args)


if __name__ == "__main__":
    main()
