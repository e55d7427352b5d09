"""Device mesh construction.

The replacement for the reference's two distribution mechanisms — the
intra-process GPU thread ring (MultiGradientMachine,
/root/reference/paddle/gserver/gradientmachines/MultiGradientMachine.h:
62-80) and the socket parameter-server (/root/reference/paddle/pserver/) —
is ONE SPMD story: a `jax.sharding.Mesh` whose axes name the parallelism
kinds, with XLA inserting the collectives over ICI/DCN.

Axis conventions (used by spmd.py and parameter sharding specs):
- "data"  — batch-dim data parallelism (the reference's only mode)
- "model" — tensor parallelism (parameter dim sharding)
- "seq"   — sequence/context parallelism (ring attention)
- "pipe"  — pipeline stages
- "expert"— expert parallelism
Missing axes are simply absent from the mesh; specs referencing only
present axes still work.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ["pipe", "data", "expert", "seq", "model"]


@dataclass(frozen=True)
class MeshSpec:
    axes: Tuple[Tuple[str, int], ...]

    @classmethod
    def parse(cls, spec: str) -> "MeshSpec":
        """Parse "data=8" / "data=4,model=2" / "8" (implicit data)."""
        spec = spec.strip()
        if not spec:
            return cls((("data", len(jax.devices())),))
        axes: List[Tuple[str, int]] = []
        for part in spec.split(","):
            part = part.strip()
            if "=" in part:
                name, _, n = part.partition("=")
                axes.append((name.strip(), int(n)))
            else:
                axes.append(("data", int(part)))
        axes.sort(key=lambda kv: AXIS_ORDER.index(kv[0]) if kv[0] in AXIS_ORDER else 99)
        return cls(tuple(axes))

    @property
    def size(self) -> int:
        n = 1
        for _, k in self.axes:
            n *= k
        return n

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.axes)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(k for _, k in self.axes)


def rescale_mesh_spec(spec: str, orig_hosts: int, cur_hosts: int) -> str:
    """The mesh spec an N-host launch becomes on M surviving hosts —
    reshard-on-relaunch's shape rule (doc/resilience.md "Elastic sharded
    checkpointing"): the "data" axis scales with the host count while
    every other axis keeps its extent, so model/pipe/seq parallelism
    groups stay intact and only the data-parallel width breathes.
    Because the global batch is the config's ``batch_size`` (each
    process takes a 1/num_processes row block — spmd.globalize_batch),
    shrinking the data axis automatically grows the per-host batch and
    the GLOBAL batch (and therefore sync-SGD semantics) is preserved.

    Pure string math — no device queries, so the launcher can call it
    for a pod whose accelerator runtime is the thing that just died. An
    EMPTY spec is identity: the trainer sizes it from jax.devices() at
    startup, which already follows the surviving host set (the
    auto-sized mesh is the most elastic of all). Raises ValueError when
    an explicit spec cannot rescale: no data axis to scale, or a data
    extent not integrally divisible by the host-count ratio."""
    if orig_hosts <= 0 or cur_hosts <= 0:
        raise ValueError(f"host counts must be positive ({orig_hosts}->{cur_hosts})")
    spec = (spec or "").strip()
    if cur_hosts == orig_hosts or not spec:
        return spec
    axes: List[Tuple[str, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if "=" in part:
            name, _, n = part.partition("=")
            axes.append((name.strip(), int(n)))
        else:
            axes.append(("data", int(part)))
    names = [n for n, _ in axes]
    if "data" not in names:
        raise ValueError(
            f"mesh spec {spec!r} has no data axis to rescale for "
            f"{cur_hosts}/{orig_hosts} hosts"
        )
    out = []
    for name, extent in axes:
        if name == "data":
            if (extent * cur_hosts) % orig_hosts:
                raise ValueError(
                    f"data axis {extent} cannot scale by "
                    f"{cur_hosts}/{orig_hosts} integrally"
                )
            extent = extent * cur_hosts // orig_hosts
            if extent < 1:
                raise ValueError(
                    f"data axis vanishes at {cur_hosts}/{orig_hosts} hosts"
                )
        out.append(f"{name}={extent}")
    return ",".join(out)


def make_mesh(spec: str = "", devices: Optional[list] = None) -> Mesh:
    ms = MeshSpec.parse(spec) if isinstance(spec, str) else spec
    devices = devices if devices is not None else jax.devices()
    if ms.size > len(devices):
        raise ValueError(
            f"mesh {ms.axes} needs {ms.size} devices but only {len(devices)} available"
        )
    dev = np.asarray(devices[: ms.size]).reshape(ms.shape)
    return Mesh(dev, ms.names)


def data_only_extent(mesh: Mesh):
    """The data-parallel extent if every OTHER mesh axis is trivial
    (extent 1), else None. Used to gate per-shard shard_map execution of
    the pallas kernels (layers/recurrent.py) — the same purely-data
    question local_sgd.check_data_only asks."""
    d = 1
    for n, e in mesh.shape.items():
        if n == "data":
            d = e
        elif e > 1:
            return None
    return d if d > 1 else None


def shard_map_unchecked(fn, mesh: Mesh, in_specs, out_specs):
    """shard_map with the varying-mesh-axes check off: pallas_call
    out_shapes carry no such annotation, which check_vma would reject."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def replicated_specs(*arrays):
    """A PartitionSpec per array, fully replicated (weights under a
    data-parallel shard_map)."""
    from jax.sharding import PartitionSpec as P

    return tuple(P(*(None,) * a.ndim) for a in arrays)


def data_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """Axes that shard the batch dimension (data and expert act as data
    parallel for the dense path)."""
    return tuple(n for n in mesh.axis_names if n in ("data",))
