"""Device-mesh management: the TPU-native replacement for the reference's
device lists + NCCLContextMap (``platform/nccl_helper.h:86``).

A ``DistStrategy`` names the parallelism axes (dp/mp/pp/sp/ep) and their
sizes; parameters carry axis-name shardings (``Parameter.sharding``), the
executor lowers them to NamedShardings, and GSPMD inserts ICI collectives —
replacing the reference's multi_devices_graph_pass + allreduce op handles.
"""

import contextlib

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = ["make_mesh", "get_mesh", "set_mesh", "mesh_scope", "scoped_mesh",
           "DistStrategy"]

_current_mesh = None
_scoped_mesh = None  # the mesh of the innermost open mesh_scope()


def make_mesh(axes=None, devices=None):
    """axes: dict name->size (in order, major-to-minor). Defaults to a 1-D
    dp mesh over all local devices. Axis sizes of -1 absorb the remainder."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if not axes:
        axes = {"dp": n}
    names = list(axes)
    sizes = [axes[k] for k in names]
    n_fixed = int(np.prod([s for s in sizes if s > 0]))
    sizes = [s if s > 0 else n // max(n_fixed, 1) for s in sizes]
    if int(np.prod(sizes)) != n:
        raise ValueError("mesh %s does not cover %d devices"
                         % (dict(zip(names, sizes)), n))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def get_mesh():
    return _current_mesh


def set_mesh(mesh):
    global _current_mesh
    _current_mesh = mesh
    return mesh


def scoped_mesh():
    """The mesh of the ``mesh_scope`` block we are inside of, else None —
    unlike :func:`get_mesh`, not what an earlier ``set_mesh`` (a
    transpile) left behind for the rest of the process."""
    return _scoped_mesh


@contextlib.contextmanager
def mesh_scope(mesh):
    global _current_mesh, _scoped_mesh
    prev = (_current_mesh, _scoped_mesh)
    _current_mesh = _scoped_mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _current_mesh, _scoped_mesh = prev


class DistStrategy:
    """Declarative parallelism config — the TPU analog of the reference's
    (BuildStrategy, DistributeTranspilerConfig, trainer env-vars) triple.

    Attributes:
      dp / mp / pp / sp / ep: axis sizes (-1 = absorb remaining devices)
      sharded_embeddings: shard embedding tables marked is_distributed over
        the mp (or ep) axis — the pserver distributed-lookup-table analog.
    """

    def __init__(self, dp=-1, mp=1, pp=1, sp=1, ep=1,
                 sharded_embeddings=False, devices=None):
        self.dp, self.mp, self.pp, self.sp, self.ep = dp, mp, pp, sp, ep
        self.sharded_embeddings = sharded_embeddings
        self.devices = devices

    def build_mesh(self):
        axes = {}
        for name in ("dp", "mp", "pp", "sp", "ep"):
            size = getattr(self, name)
            if size != 1:
                axes[name] = size
        if not axes:
            axes = {"dp": -1}
        return make_mesh(axes, self.devices)
