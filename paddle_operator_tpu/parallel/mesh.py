"""Device-mesh construction.

The reference has no mesh concept — topology awareness stops at replica
count + rank id (``PADDLE_TRAINER_ID``, SURVEY.md §2); all layout lives in
Paddle Fleet inside user containers.  Here the mesh is first-class: the
``TPUJob`` CRD carries logical axes (api.types.MeshSpec), the launcher builds
the same ``jax.sharding.Mesh`` on every process, and every collective rides
named axes so XLA lays them onto ICI (within a slice) and DCN (across
slices).

Axis convention (outermost → innermost):

    dp    pure data parallel — gradient all-reduce only; DCN-friendly,
          so it is the outermost axis (maps across slices in multislice).
    pp    pipeline stages — point-to-point ppermute between neighbors.
    fsdp  fully-sharded data parallel — params/optimizer sharded, per-layer
          all-gather + reduce-scatter; wants ICI bandwidth.
    cp    context/sequence parallel — ring attention neighbor exchange.
    ep    expert parallel — all-to-all.
    tp    tensor parallel — activations all-reduce every layer; the
          chattiest axis, so innermost (adjacent chips on the torus).

``mesh_utils.create_device_mesh`` maps this logical shape onto the physical
ICI torus; on CPU (tests / dryrun) it degrades to a reshape.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

from paddle_operator_tpu.api.types import MeshSpec

# outermost → innermost (see module docstring)
AXIS_ORDER: Sequence[str] = ("dp", "pp", "fsdp", "cp", "ep", "tp")

# Axes over which a batch is split (data axes): batch sharding and gradient
# reduction happen over these.
DATA_AXES = ("dp", "fsdp")


def mesh_shape(spec: MeshSpec) -> List[int]:
    return [getattr(spec, a) for a in AXIS_ORDER]


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the global Mesh for `spec` over `devices` (default: all).

    The axis product must equal the device count (validated — the CRD-side
    twin of this check is TPUJob.validate()).
    """
    spec = spec or MeshSpec()
    devs = list(devices) if devices is not None else list(jax.devices())
    shape = mesh_shape(spec)
    size = int(np.prod(shape))
    if size != len(devs):
        raise ValueError(
            f"mesh {dict(zip(AXIS_ORDER, shape))} needs {size} devices, "
            f"have {len(devs)}"
        )
    if devices is None and devs and devs[0].platform == "tpu":
        # ICI-topology-aware assignment on real hardware.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape)
    else:
        dev_array = np.array(devs).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def make_serving_mesh(tp: int,
                      devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A single-axis ``("tp",)`` mesh for the serving path (infer/).

    Serving shards ONE way — tensor parallel over heads/ffn/vocab, the
    Megatron recipe — so its mesh carries only the ``tp`` axis and the
    decode kernel's shard_map is full-manual.  Data parallelism in
    serving is separate server replicas, not a mesh axis."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if tp < 1 or tp > len(devs):
        raise ValueError(f"tp={tp} needs 1..{len(devs)} devices")
    return Mesh(np.array(devs[:tp]), ("tp",))


def single_device_mesh() -> Mesh:
    """A 1-chip mesh (all axes size 1) — lets the same pjit train step run
    unmodified on one device."""
    return make_mesh(MeshSpec(), devices=jax.devices()[:1])


def resolve_shard_map_mesh(mesh: Mesh):
    """Mesh argument for a (possibly nested) partial-manual shard_map:
    when tracing already happens inside another manual region, the
    context's abstract mesh must be inherited (pass None) instead of the
    concrete mesh.  Shared by the ring and Ulysses attention wrappers —
    the idiom is subtle enough that two copies would drift.  Returns
    ``(mesh_or_None, axis_sizes_dict)``."""
    ctx = jax.sharding.get_abstract_mesh()
    if ctx is not None and not ctx.empty:
        return None, dict(ctx.shape)
    return mesh, dict(mesh.shape)
