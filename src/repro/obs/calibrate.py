"""Calibration pass: microbenchmark the machine's links, fit α–β, and
return a versioned :class:`~repro.obs.profile.MachineProfile`.

``probe_links(mesh)`` is the library entry point
(``repro.launch.perf_probe`` re-exports it and adds the ``__main__`` that
writes the profile JSON the planner consumes):

  * per mesh axis, a ring ``ppermute`` of increasing shard sizes is timed
    (compile excluded, best-of-``reps``) and α–β fitted per axis; a pooled
    fit over every axis becomes the ``"ici"`` link class the planner reads
    by default;
  * without a mesh (or on one device) a device-local copy probe stands in
    as the single ``"local"`` class, so calibration degrades gracefully on
    a laptop;
  * peak matmul FLOPs come from a jit'd square matmul timing.

jax is imported lazily inside the probes -- importing this module (or
``repro.obs``) never initializes a backend.
"""
from __future__ import annotations

import datetime
import time
from typing import Optional, Sequence, Tuple

from .profile import LinkParams, MachineProfile, fit_alpha_beta
from .runtime import span

DEFAULT_SIZES_BYTES: Tuple[int, ...] = (1 << 14, 1 << 17, 1 << 20)


def _time_best(fn, reps: int) -> float:
    """Best-of-``reps`` wall seconds of ``fn()``, compile/warmup excluded."""
    import jax

    jax.block_until_ready(fn())  # warmup: compile + first dispatch
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _probe_axis(mesh, axis: str, size_bytes: int, reps: int) -> float:
    """Seconds for one ring-neighbor ppermute of a ``size_bytes`` shard
    along ``axis`` (jit'd shard_map, timed on device)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    ax_size = int(mesh.shape[axis])
    shard_words = max(size_bytes // 4, 1)
    perm = [(i, (i + 1) % ax_size) for i in range(ax_size)]

    def body(x):
        return jax.lax.ppermute(x, axis, perm)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                              out_specs=P(axis)))
    x = jnp.zeros((ax_size * shard_words,), jnp.float32)
    return _time_best(lambda: f(x), reps)


def _probe_local(size_bytes: int, reps: int) -> float:
    """Device-local copy probe (the no-mesh fallback link class)."""
    import jax
    import jax.numpy as jnp

    words = max(size_bytes // 4, 1)
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((words,), jnp.float32)
    return _time_best(lambda: f(x), reps)


def _probe_peak_flops(reps: int, n: int = 256) -> float:
    """Measured peak matmul FLOPs from a jit'd n³ fp32 multiply."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((n, n), jnp.float32)
    b = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda a, b: a @ b)
    t = _time_best(lambda: f(a, b), reps)
    return 2.0 * n ** 3 / max(t, 1e-9)


def _assemble_links(axis_samples, tree_axes: Sequence[str] = ()):
    """Compose the profile's link-class table from per-axis probe samples.

    ``axis_samples`` is ``[(axis, sizes_bytes, times_s), ...]``.  Every
    measured axis keeps its own ``axis:{name}`` class; the pooled classes
    follow the machine hierarchy: non-tree axes pool into ``"ici"`` (the
    planner's default link class) and ``tree_axes`` into ``"dcn"`` (the
    inter-pod class a hierarchical plan's tree axis belongs to -- DCN
    latency/bandwidth must not be averaged into the ICI fit, or a slow
    inter-pod link would silently *improve* the pooled model).  When every
    measured axis is a tree axis, ``"ici"`` falls back to the dcn fit so
    the profile stays usable by non-hierarchical estimates."""
    tree_axes = frozenset(tree_axes)
    links = []
    ici: Tuple[list, list] = ([], [])
    dcn: Tuple[list, list] = ([], [])
    for axis, sizes, times in axis_samples:
        links.append((f"axis:{axis}", fit_alpha_beta(sizes, times)))
        sink = dcn if axis in tree_axes else ici
        sink[0].extend(sizes)
        sink[1].extend(times)
    pooled = []
    if ici[0]:
        pooled.append(("ici", fit_alpha_beta(*ici)))
    elif dcn[0]:
        pooled.append(("ici", fit_alpha_beta(*dcn)))
    if dcn[0]:
        pooled.append(("dcn", fit_alpha_beta(*dcn)))
    return pooled + links


def probe_links(mesh=None, *,
                sizes_bytes: Sequence[int] = DEFAULT_SIZES_BYTES,
                reps: int = 3,
                tree_axes: Sequence[str] = ()) -> MachineProfile:
    """Microbenchmark every link class of ``mesh`` and return the fitted
    :class:`MachineProfile` (see module docstring).  This is the
    calibration pass the ROADMAP's calibrated-cost-model item asks for;
    persist the result with ``repro.obs.save_profile`` and hand it to
    ``build_plan(profile=...)``.

    ``tree_axes`` names the mesh axes that are inter-pod (DCN-class)
    links: they are excluded from the pooled ``"ici"`` fit and pooled into
    a separate ``"dcn"`` class instead (see ``_assemble_links``), so a
    calibrated ranking can prefer the hierarchical fat-tree plan exactly
    when the inter-pod link is slow.
    """
    import jax

    with span("obs.calibrate", mesh=str(getattr(mesh, "shape", None))):
        links = []
        if mesh is not None and mesh.size > 1:
            samples = []
            for axis in mesh.axis_names:
                if int(mesh.shape[axis]) < 2:
                    continue
                times = [_probe_axis(mesh, axis, s, reps)
                         for s in sizes_bytes]
                samples.append((axis, list(sizes_bytes), times))
            links = _assemble_links(samples, tree_axes)
        if not links:
            times = [_probe_local(s, reps) for s in sizes_bytes]
            fit = fit_alpha_beta(sizes_bytes, times)
            links = [("ici", fit), ("local", fit)]
        return MachineProfile(
            device_kind=jax.devices()[0].device_kind,
            peak_flops=_probe_peak_flops(reps),
            links=tuple(links),
            created=datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
        )
