"""Elastic re-meshing: rebuild a smaller mesh after pod/node loss and
re-place training state onto it.

TPU failures are pod-granular for ICI meshes: losing any chip takes its
slice out of the ICI torus, so the recovery unit is a pod.  The policy here:
drop the failed pod from the ``pod`` axis (multi-pod -> fewer pods, or
single-pod mesh), reshard from the latest checkpoint, continue with the
global batch preserved (per-device batch grows) or reduced, per config.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.sharding_rules import param_shardings


def shrink_after_failure(mesh: Mesh, lost_pod: int = 0) -> Mesh:
    """Return the survivor mesh after losing one pod."""
    names = mesh.axis_names
    if "pod" in names and mesh.shape["pod"] > 1:
        devs = np.asarray(mesh.devices)
        pod_axis = names.index("pod")
        keep = [i for i in range(mesh.shape["pod"]) if i != lost_pod]
        new_devs = np.take(devs, keep, axis=pod_axis)
        if len(keep) == 1:
            new_devs = np.squeeze(new_devs, axis=pod_axis)
            new_names = tuple(n for n in names if n != "pod")
            return Mesh(new_devs, new_names)
        return Mesh(new_devs, names)
    raise ValueError("no pod axis to shrink; replace failed hosts instead")


def replace_state(state: Any, mesh: Mesh) -> Any:
    """Re-place (reshard) an optimizer-state tree onto ``mesh``."""
    psh = param_shardings(state["master"], mesh)
    rep = NamedSharding(mesh, P())
    shardings = {"step": rep, "master": psh, "m": psh, "v": psh}
    return jax.tree.map(lambda x, s: jax.device_put(np.asarray(x), s),
                        state, shardings)
